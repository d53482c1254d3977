"""Per Lloyd fit: the program's ``lloyd.place_inputs`` span (the column put on
the mesh);
the median over the whole traced fits."""
from benchmarks.harness import lloyd_spans


def read(ctx):
    return lloyd_spans.read("place")
