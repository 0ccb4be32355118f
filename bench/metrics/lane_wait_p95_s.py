"""Lanes: 95th percentile of the wait each answered request's fired batch
spent queued on its execution lane before the lane started it (the
program's ``SolveTelemetry.lane_wait_s``).  None where the program keeps
no such counter."""
import numpy as np


def read(run):
    w = [r.result.telemetry.lane_wait_s for r in run.answered
         if getattr(r.result.telemetry, "lane_wait_s", None) is not None]
    return float(np.percentile(w, 95)) if w else None
