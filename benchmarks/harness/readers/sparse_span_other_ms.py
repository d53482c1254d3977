"""Per sparse LR fit: the program's root ``LogisticRegression.fit`` less the
three other parts (the stage wrapper, ``fit.extract``, ``fit.model``,
``sgd.init_carry``, ``sgd.build_program``, ``sgd.health``); the median over
the whole traced fits whose ``sgd.optimize`` names a sparse path."""
from benchmarks.harness import sparse_spans


def read(ctx):
    return sparse_spans.read("other")
