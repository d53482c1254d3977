"""Per fit: the program's ``sgd.init_carry`` span (the carry's leaves placed
one by one); the median over the whole traced fits."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("carry")
