"""``trace_s + lower_s + compile_s`` over the first ``first_fit`` root's
tree: the seconds jax spent building the first fit's programs (loads from
the persistent cache lie inside ``compile_s``), each second counted once."""
from benchmarks.harness import cold_spans


def read(ctx):
    return cold_spans.read("first_fit_build")
