"""Per NaiveBayes fit: the program's ``nb.fetch`` span (the blocking read of
the counts: the wait for the pass falls here); the median over the whole
traced fits."""
from benchmarks.harness import nb_spans


def read(ctx):
    return nb_spans.read("fetch")
