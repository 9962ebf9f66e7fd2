"""Process start to the start of the window: ``compile()``, binding and
data generation, XLA compile or cache load, and the warm-up (host clock)."""


def read(r):
    return r.setup_s
