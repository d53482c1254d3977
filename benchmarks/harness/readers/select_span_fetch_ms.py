"""Per RobustScaler fit: the program's ``select.fetch`` spans, summed (the
blocking read of each program's report: the wait for every pass over the
table falls here); the median over the whole traced fits."""
from benchmarks.harness import select_spans


def read(ctx):
    return select_spans.read("fetch")
