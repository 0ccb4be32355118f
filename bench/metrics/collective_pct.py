"""Collectives: the share of the chips' busy time in the traced window spent
in cross-chip collective operations (``collective_s`` over ``busy_s`` of
the trace's reduction, both means over the cell's chips: ops named like an
XLA collective, ``bench/trace.py``'s ``COLLECTIVES``), in percent.  0 where
the trace holds none; None without a trace."""


def read(run):
    tr = run.trace
    if tr is None or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
