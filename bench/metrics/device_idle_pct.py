"""Device: the share of the traced window in which no operation ran on the
chip (one minus the union of device-operation intervals over the window),
the mean over the cell's chips."""


def read(run):
    tr = run.trace
    if tr is None or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
