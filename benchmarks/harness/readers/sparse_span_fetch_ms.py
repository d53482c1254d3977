"""Per sparse LR fit: the program's ``sgd.fetch`` span (the blocking read of
the coefficients, the loss and the bounds under one wait, where the wait for
the rounds falls); the median over the whole traced fits whose
``sgd.optimize`` names a sparse path."""
from benchmarks.harness import sparse_spans


def read(ctx):
    return sparse_spans.read("fetch")
