"""Per RobustScaler fit: the root span ``RobustScaler.fit`` less the three named
parts (``select.build_program``, ``fit.model``, the stage wrapper, what no
span names); the median over the whole traced fits."""
from benchmarks.harness import select_spans


def read(ctx):
    return select_spans.read("other")
