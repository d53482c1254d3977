"""Per RobustScaler fit: the ``passes`` attributes of the program's
``select.fetch`` spans, summed: the whole reads of the table the fit made (33
for a 32-round bisection that first makes its keys; ``fit_device_roofline``
cannot read over 100 over this number); the median over the whole traced
fits."""
from benchmarks.harness import select_spans


def read(ctx):
    return select_spans.read("passes")
