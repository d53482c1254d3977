"""Minibatch SGD over dense rows: every round reads its batch once — ``d``
features, a label and a weight per row — and does two matrix-vector
products over it (the margins and the gradient): ``4*d`` FLOPs a row."""

from . import F32


def count(stage_params: dict, data_params: dict) -> dict:
    rounds = int(stage_params["maxIter"])
    batch = min(int(stage_params["globalBatchSize"]),
                int(data_params["numValues"]))
    d = int(data_params["vectorDim"])
    rows = rounds * batch
    return {"rows": rows, "bytes": rows * (d + 2) * F32,
            "flops": rows * 4 * d}
