"""Per fit: the program's ``sgd.launch`` spans (the rounds enqueued, no wait);
the median over the whole traced fits."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("launch")
