"""Median latency over every request of the window that got an answer or
an error: from its due time (open loop) or its send (closed loop, where the
two are the same) to the caller holding the result.  Host clock."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.records if r.latency_s is not None]
    return float(np.percentile(lat, 50)) if lat else None
