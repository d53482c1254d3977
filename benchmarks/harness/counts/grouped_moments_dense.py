"""Grouped moments over dense rows with a class label: one fit reads every
row once — ``d`` features and a label — and does for every feature an add
(its class's sum), a multiply and an add (its class's sum of squares),
whatever implements it: masked sums on the VPU, a one-hot product on the
MXU over fixed-point digits (whose extra passes and multiplies by zero are
the implementation's, not the algorithm's) or a loop on the host. A second
read of the table, a look at it beforehand, the host's F and p change the
time only: a form that reads the table twice cannot read over 50 % of this
roofline. Bytes bound the cell."""

from . import F32


def count(stage_params: dict, data_params: dict) -> dict:
    rows, d = int(data_params["numValues"]), int(data_params["vectorDim"])
    return {"rows": rows, "bytes": rows * (d + 1) * F32,
            "flops": rows * d * 3}
