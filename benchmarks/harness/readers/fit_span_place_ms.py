"""Per fit: the program's ``sgd.place_inputs`` span (the table's columns put
on the mesh, the ones vector); the median over the whole traced fits."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("place")
