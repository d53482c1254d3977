"""Per NaiveBayes fit: the program's ``nb.finalize`` span (the float64 model
on the host: the values present, the logarithms); the median over the whole
traced fits."""
from benchmarks.harness import nb_spans


def read(ctx):
    return nb_spans.read("finalize")
