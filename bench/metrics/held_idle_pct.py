"""Dispatcher: the idle gaps of the traced window that the trace's
reduction names ``dispatch.hold`` (the latest-started program span open at
the gap's midpoint is the dispatcher's wait with a batch pending: its
idle-timeout or deadline-margin hold), in percent of the window.  Such a
gap counts whole, so the fetch and launch idle at the edges of a hold
counts too; clipping each gap to its overlap with the hold spans needs a
reduction in ``bench/trace.py``.  0 where no gap carries that name."""


def read(run):
    tr = run.trace
    if tr is None or not tr.get("window_s"):
        return None
    held = sum(s for name, s in tr["idle_gaps"] if name == "dispatch.hold")
    return 100.0 * held / tr["window_s"]
