"""Solver path: sweeps per solver call (``ServedSolve.n_sweeps``), the
mean over solves."""


def read(run):
    solves = sum(1.0 / r.result.group_size for r in run.answered)
    sweeps = sum(r.result.n_sweeps / r.result.group_size
                 for r in run.answered)
    return sweeps / solves if solves else None
