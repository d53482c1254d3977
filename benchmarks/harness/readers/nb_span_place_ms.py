"""Per NaiveBayes fit: the program's ``nb.place_inputs`` span (the two columns
put on the mesh, a no-op for a resident table, and the row count sent up); the
median over the whole traced fits."""
from benchmarks.harness import nb_spans


def read(ctx):
    return nb_spans.read("place")
