"""Per fit: the program's ``sgd.build_program`` span (the program looked up or
built, the small program jitted anew in every fit); the median over the whole
traced fits."""
from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.read("build")
