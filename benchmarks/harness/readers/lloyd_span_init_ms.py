"""Per Lloyd fit: the program's ``lloyd.init`` span (the seeded choice of k
rows, the carry's one placement, the rows taken from the column);
the median over the whole traced fits."""
from benchmarks.harness import lloyd_spans


def read(ctx):
    return lloyd_spans.read("init")
