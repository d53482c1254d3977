"""Per ANOVA selector fit: the program's ``anova.fetch`` spans, summed (the one
blocking read of a pass's digit sums, counts and maxima through
``read_boundary``, where the wait for the pass falls); the median over the
whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("fetch")
