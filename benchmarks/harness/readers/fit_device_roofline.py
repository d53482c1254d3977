"""The least time the cell's chips could take for the traced fits (counts
from shapes, peaks from the table) over the device-busy time inside those
fits."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    busy = sum(tr["fit_busy_s"])
    if busy <= 0:
        return None
    return 100.0 * len(tr["fit_busy_s"]) * ctx["least"]["seconds"] / busy
