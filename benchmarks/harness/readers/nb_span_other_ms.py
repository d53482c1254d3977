"""Per NaiveBayes fit: the root span ``NaiveBayes.fit`` less the five named
parts (``nb.build_program``, ``fit.model``, the stage wrapper, what no span
names); the median over the whole traced fits."""
from benchmarks.harness import nb_spans


def read(ctx):
    return nb_spans.read("other")
