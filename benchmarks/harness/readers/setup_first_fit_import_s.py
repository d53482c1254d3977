"""Seconds in the topmost ``import:*`` cold spans inside the first
``first_fit`` root (``import:pallas`` where the fit takes a kernel; 0.0 where
it imports nothing)."""
from benchmarks.harness import cold_spans


def read(ctx):
    return cold_spans.read("first_fit_import")
