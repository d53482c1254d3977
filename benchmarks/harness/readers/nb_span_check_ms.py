"""Per NaiveBayes fit: the program's ``nb.check`` span (the look at the
table's first rows for its range, with the blocking read of its four numbers;
a second look, at every row, where the counts showed the first was short); the
median over the whole traced fits."""
from benchmarks.harness import nb_spans


def read(ctx):
    return nb_spans.read("check")
