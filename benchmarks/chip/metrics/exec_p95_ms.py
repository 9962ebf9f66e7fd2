"""The 95th percentile of every ``execute()`` wall in the window, each
ending in ``block_until_ready`` on its outputs (host clock)."""
import numpy as np


def read(r):
    return 1e3 * float(np.percentile(r.walls, 95))
