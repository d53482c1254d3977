"""Per ANOVA selector fit: the ``passes`` attributes of the program's
``anova.fetch`` spans, summed: the whole reads of the table the fit made (1; 2
where a column reached past the scale its first rows gave:
``fit_device_roofline`` cannot read over 100 over this number); the median
over the whole traced fits."""
from benchmarks.harness import anova_spans


def read(ctx):
    return anova_spans.read("passes")
