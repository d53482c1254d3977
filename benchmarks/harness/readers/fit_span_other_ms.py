"""Per fit: what is left of the program's ``sgd.optimize`` span once its five
named children are taken out (the health guard, the checks, what no span
names); the median over the whole traced fits."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("other")
