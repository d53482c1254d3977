"""Per sparse LR fit: the program's ``sgd.place_inputs`` span
(``ensure_on_mesh`` over the resident ids, values and label, which moves
nothing); the median over the whole traced fits whose ``sgd.optimize`` names
a sparse path."""
from benchmarks.harness import sparse_spans


def read(ctx):
    return sparse_spans.read("place")
