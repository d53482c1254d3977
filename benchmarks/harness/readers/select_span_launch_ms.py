"""Per RobustScaler fit: the program's ``select.launch`` spans, summed (each
one selection program enqueued, no wait: the head, then one a pass); the
median over the whole traced fits."""
from benchmarks.harness import select_spans


def read(ctx):
    return select_spans.read("launch")
