"""Per sparse LR fit: the device time of the gradient's scatter, in ms: the
self time over the traced cycles of the two operations that add the
window's terms into the ``size`` buckets, over the cycles. On a TPU v5e the
compiler names them ``fusion.20`` (the scatter-add of the entries the
column's index does not name hot, 2.6M a round at the cell's shapes, the
terms' multiply fused into it) and ``fusion.21`` (the 13 hot buckets' row
sums added), ``scope sgd.sparse_gradient/scatter-add`` both: the names the
chip's trace of the ``split-scatter`` program shows (PERF.md, section 6)
and ``tests/test_sparse_device_sgd.py`` holds for a v5e ahead of
time. None where the trace holds neither (a program without the sparse
fit, or another cell's)."""

SCATTER_OPS = ("fusion.20", "fusion.21")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["cycles"]:
        return None
    found = [s for name, s in tr["device_ops"] if name in SCATTER_OPS]
    if not found:
        return None
    return 1e3 * sum(found) / tr["cycles"]
