"""Per Lloyd fit: the program's ``lloyd.fetch`` span (the blocking reads: the
wait for the rounds falls here);
the median over the whole traced fits."""
from benchmarks.harness import lloyd_spans


def read(ctx):
    return lloyd_spans.read("fetch")
