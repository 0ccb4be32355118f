"""Dispatcher coalescing: right-hand sides per solver call, the mean of
``ServedSolve.group_size`` over solves (each request carries 1/group_size
of its solve).  In an open loop below capacity the offered rate is fixed,
so coalescing moves latency, not the rate."""


def read(run):
    solves = sum(1.0 / r.result.group_size for r in run.answered)
    return len(run.answered) / solves if solves else None
