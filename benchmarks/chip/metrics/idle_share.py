"""The share of the traced window in which no operation ran on a chip,
averaged over the cell's chips (device trace)."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_mean_s / r.trace.window_s)
