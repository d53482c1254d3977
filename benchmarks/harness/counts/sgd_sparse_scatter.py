"""The gradient's scatter of a sparse SGD fit alone (``sgd_sparse`` counts
the whole fit): every round reads each entry of its batch once, an id and a
term, 8 bytes, and writes the ``numFeatures`` float32 gradient once; an add
an entry. The least any form of the scatter moves, whatever implements it.

``from_fit(count)`` gives the same numbers from ``sgd_sparse``'s count of
the fit, the count the harness hands a reader (``readers/
sparse_grad_roofline.py``): ``k`` from its FLOPs (``4 k`` a row) and rounds
x ``numFeatures`` from the bytes it counts past the rows' (8 a bucket a
round)."""

from . import F32


def count(stage_params: dict, data_params: dict) -> dict:
    rounds = int(stage_params["maxIter"])
    batch = min(int(stage_params["globalBatchSize"]),
                int(data_params["numValues"]))
    k = int(data_params["numericFields"]) + int(
        data_params["categoricalFields"])
    rows = rounds * batch
    return {"rows": rows,
            "bytes": rows * k * 2 * F32
            + rounds * F32 * int(data_params["numFeatures"]),
            "flops": rows * k}


def from_fit(fit: dict) -> dict:
    rows = fit["rows"]
    k = fit["flops"] // (4 * rows)
    buckets = (fit["bytes"] - rows * (2 * k + 1) * F32) // (2 * F32)
    return {"rows": rows, "bytes": rows * k * 2 * F32 + buckets * F32,
            "flops": rows * k}
