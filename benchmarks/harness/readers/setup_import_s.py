"""Seconds in the program's ``import:*`` cold spans that have no ``import:*``
above them and lie in no ``first_fit``: every import the program made before
its first fit (the package, its models, jax, scipy), from inside."""
from benchmarks.harness import cold_spans


def read(ctx):
    return cold_spans.read("import")
