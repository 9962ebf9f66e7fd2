"""The whole ``execute()`` step's share of the chips' peak: the least time
its work needs on one chip, spread over the cell's chips, over the traced
time per ``execute()``.  Work is the algorithm's count from the config
(``work()``), bytes or operations, whichever bounds."""


def read(r):
    if r.trace is None:
        return None
    per_exec_s = r.trace.window_s / len(r.walls)
    return 100.0 * r.least_exec_s / r.chips / per_exec_s
