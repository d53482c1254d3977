"""Per ANOVA selector fit: the program's ``anova.check`` span (the look: the label
column's range over every row, every column's ends and mean over the table's
first rows, with the blocking read of its ``3 + 3 d`` numbers); the median
over the whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("check")
