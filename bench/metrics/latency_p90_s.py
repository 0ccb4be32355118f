"""90th-percentile latency, over the same requests and clock as
``latency_p50_s``: the tail of a window of about a hundred requests, with
ten or more beyond it."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.records if r.latency_s is not None]
    return float(np.percentile(lat, 90)) if lat else None
