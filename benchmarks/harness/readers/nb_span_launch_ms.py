"""Per NaiveBayes fit: the program's ``nb.launch`` span (the counting pass
enqueued, no wait); the median over the whole traced fits."""
from benchmarks.harness import nb_spans


def read(ctx):
    return nb_spans.read("launch")
