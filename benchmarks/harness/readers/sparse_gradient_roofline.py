"""The least time the cell's chips could take for one fit's gradient
(``counts/sgd_sparse_scatter.py``: each entry's id and term read once, the
gradient written once a round, whatever form takes them) over the device
time the program's gradient operations took (``sparse_gradient_device_ms``),
in %."""
from benchmarks.harness import counts
from benchmarks.harness.counts import sgd_sparse_scatter
from benchmarks.harness.readers import sparse_gradient_device_ms


def read(ctx, records=None):
    ms = sparse_gradient_device_ms.read(ctx, records)
    if not ms:
        return None
    least = counts.least_seconds(sgd_sparse_scatter.from_fit(ctx["count"]),
                                 ctx["peaks"], ctx["chips"])["seconds"]
    return 100.0 * least / (ms / 1e3)
