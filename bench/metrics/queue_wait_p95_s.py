"""Dispatcher layer: 95th percentile of the submit-to-fire wait each
answered request spent in ``AsyncDispatcher`` (the program's
``SolveTelemetry.queue_wait_s``)."""
import numpy as np


def read(run):
    w = [r.result.telemetry.queue_wait_s for r in run.answered
         if r.result.telemetry is not None
         and r.result.telemetry.queue_wait_s is not None]
    return float(np.percentile(w, 95)) if w else None
