"""Per Lloyd fit: the root span ``KMeans.fit`` less the four named parts
(``lloyd.build_program``, ``lloyd.health``, ``fit.model``, the stage wrapper);
the median over the whole traced fits."""
from benchmarks.harness import lloyd_spans


def read(ctx):
    return lloyd_spans.read("other")
