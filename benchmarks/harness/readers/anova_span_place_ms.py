"""Per ANOVA selector fit: the program's ``anova.place_inputs`` span (two
``ensure_on_mesh`` over the resident feature and label columns, which move
nothing); the median over the whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("place")
