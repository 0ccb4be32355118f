"""Batch build: median host time before each answered request's solver
call (the flush's grouping and design lookups, then the padding of y and
a0: the program's ``engine.build`` spans, ``SolveTelemetry.build_s``).
None where the program keeps no such counter."""
import numpy as np


def read(run):
    w = [r.result.telemetry.build_s for r in run.answered
         if getattr(r.result.telemetry, "build_s", None) is not None]
    return float(np.percentile(w, 50)) if w else None
