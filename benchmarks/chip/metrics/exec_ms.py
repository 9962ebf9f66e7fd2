"""Time per deployed run of the design: the whole window over the number
of ``execute()`` calls completed in it (host clock)."""


def read(r):
    return 1e3 * r.window_s / len(r.walls)
