"""Minibatch SGD over a sparse column of ``k`` entries a row
(``numericFields + categoricalFields``): every round reads each entry of its
batch once — an id and a value, 8 bytes — and a row's label, and reads and
writes the ``numFeatures`` coefficients once; the margins and the gradient
are a multiply and an add an entry each: ``4*k`` FLOPs a row."""

from . import F32


def count(stage_params: dict, data_params: dict) -> dict:
    rounds = int(stage_params["maxIter"])
    batch = min(int(stage_params["globalBatchSize"]),
                int(data_params["numValues"]))
    k = int(data_params["numericFields"]) + int(
        data_params["categoricalFields"])
    rows = rounds * batch
    coefficients = rounds * 2 * F32 * int(data_params["numFeatures"])
    return {"rows": rows, "bytes": rows * (2 * k + 1) * F32 + coefficients,
            "flops": rows * 4 * k}
