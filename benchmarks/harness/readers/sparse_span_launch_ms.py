"""Per sparse LR fit: the program's ``sgd.launch`` span (the one program
enqueued, the coefficients its one host operand; no wait); the median over
the whole traced fits whose ``sgd.optimize`` names a sparse path."""
from benchmarks.harness import sparse_spans


def read(ctx):
    return sparse_spans.read("launch")
