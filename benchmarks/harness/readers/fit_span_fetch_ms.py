"""Per fit: the program's ``sgd.fetch`` spans (the blocking reads: the wait
for the rounds falls here); the median over the whole traced fits."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("fetch")
