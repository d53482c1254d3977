"""Contingency counts over dense categorical rows: one fit reads every row
once — ``d`` features and a label — and does one increment a value (its
``(feature, label, value)`` count) and one a label (its ``doc`` count),
whatever implements it: a scatter-add, a one-hot product on the MXU (whose
multiplies by zero are the implementation's, not the algorithm's) or a loop
on the host. A second pass over the table, a look at it beforehand, changes
the time only. Bytes bound the cell."""

from . import F32


def count(stage_params: dict, data_params: dict) -> dict:
    rows, d = int(data_params["numValues"]), int(data_params["vectorDim"])
    return {"rows": rows, "bytes": rows * (d + 1) * F32,
            "flops": rows * (d + 1)}
