"""Share of the traced window, in percent, in which no operation ran on
the device (averaged over the cell's chips); nothing where the trace
holds no device operation."""


def read(r):
    tr = r.trace
    if not tr.window_s or not tr.busy_s:
        return None
    return (1 - tr.busy_s / tr.window_s) * 100
