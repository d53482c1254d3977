"""Per ANOVA selector fit: the root ``UnivariateFeatureSelector.fit`` less the
five named spans (``anova.build_program``, ``fit.model``, the counters, the
stage wrapper); the median over the whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("other")
