"""Of ``setup_import_s``, the seconds in the spans not named
``import:flink_ml_tpu*`` (jax, numpy, scipy.stats, scipy.cluster): the third
parties' share of the program's imports before its first fit."""
from benchmarks.harness import cold_spans


def read(ctx):
    return cold_spans.read("import_deps")
