"""Batch build: median host time from each answered request's solve being
ready on the device to its finished answer (the divergence check's reads,
the coefficient and residual copies, SSE and telemetry: the program's
``engine.fetch`` spans, ``SolveTelemetry.fetch_s``).  None where the
program keeps no such counter."""
import numpy as np


def read(run):
    w = [r.result.telemetry.fetch_s for r in run.answered
         if getattr(r.result.telemetry, "fetch_s", None) is not None]
    return float(np.percentile(w, 50)) if w else None
