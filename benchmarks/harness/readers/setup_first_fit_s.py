"""Seconds of the program's first ``first_fit`` cold root: the process's
first fit of the cell's stage, call to return, with the imports, the program
builds and jax's traces, lowerings and compiles that fall inside it."""
from benchmarks.harness import cold_spans


def read(ctx):
    return cold_spans.read("first_fit")
