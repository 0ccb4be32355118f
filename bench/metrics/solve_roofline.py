"""Kernel layer: the least time of the window's solves (``bench/work.py``,
from each solve's design shape, right-hand sides and sweeps, at the
chip's peaks) over the device time of the programs that ran them (every
XLA program that ended inside the traced window), in percent.  Counts the
solves answered by the close; a program that ended before the close but
whose answer came after it only adds to the device time, so the share
errs low, never high."""
from bench.work import least_time_s, least_work


def read(run):
    tr = run.trace
    if tr is None or not tr.get("program_s"):
        return None
    close = run.t0 + run.seconds
    least = 0.0
    for r in run.answered:
        if r.done is None or r.done > close:
            continue
        res = r.result
        obs, nvars = run.shapes[r.design]
        flops, nbytes = least_work(
            obs, nvars, res.group_size, res.n_sweeps,
            vmem_bytes=run.peak["vmem_bytes"], chips=run.chips)
        least += least_time_s(flops, nbytes, run.peak,
                              run.chips) / res.group_size
    return 100.0 * least / tr["program_s"] if least else None
