"""Requests answered correctly, on the cell's path, by the window's close,
divided by the window.  Host clock."""


def read(run):
    close = run.t0 + run.seconds
    done = sum(1 for r in run.records
               if r.failure is None and r.done is not None and r.done <= close)
    return done / run.seconds
