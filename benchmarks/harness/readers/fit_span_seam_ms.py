"""Per fit: the program's root span ``<Stage>.fit`` less its ``sgd.optimize``
(the stage wrapper, ``fit.extract``, ``fit.model``); the median over the whole
traced fits in the program's ring."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("seam")
