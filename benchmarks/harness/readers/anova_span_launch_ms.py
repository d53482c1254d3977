"""Per ANOVA selector fit: the program's ``anova.launch`` spans, summed (the
grouped-moments pass enqueued, no wait; twice where a column reached past the
scale its first rows gave); the median over the whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("launch")
