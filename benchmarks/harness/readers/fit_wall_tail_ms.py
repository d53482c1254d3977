"""The 95th percentile of the wall time of the window's fits, call to model
data on the host, the fits made while the profiler captured left out: the
tail where it is too unsteady from process to process to stand end to end."""

import numpy as np


def read(ctx):
    win = ctx["window"]
    walls = [w for w, traced in zip(win["walls_s"], win["traced"])
             if not traced]
    if len(walls) < 20:
        return None
    return float(np.percentile(walls, 95)) * 1e3
