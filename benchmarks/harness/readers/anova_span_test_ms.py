"""Per ANOVA selector fit: the program's ``anova.test`` spans, summed (the host's
float64 part: the digits put together, F, the p-values, the selection); the
median over the whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("test")
