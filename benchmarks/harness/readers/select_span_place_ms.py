"""Per RobustScaler fit: the program's ``select.place_inputs`` span (the column put
on the mesh, a no-op for a resident table; the ranks, held from the first fit); the median over the whole traced
fits."""
from benchmarks.harness import select_spans


def read(ctx):
    return select_spans.read("place")
