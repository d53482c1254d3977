"""Per-column order statistics over dense rows: one fit must read every
element once — ``d`` features a row — and compare it once a probability
(``lower``, the median, ``upper``: ``m`` = 3), whatever implements it: a
sort, a bisection on keys, a histogram. A second pass over the table, a copy
of it, a sample or a fallback changes the time only, so a form that reads
the table ``P`` times cannot read over ``100 / P`` % of this roofline.
Bytes bound the cell."""

from . import F32

#: lower, median, upper
PROBABILITIES = 3


def count(stage_params: dict, data_params: dict) -> dict:
    rows, d = int(data_params["numValues"]), int(data_params["vectorDim"])
    return {"rows": rows, "bytes": rows * d * F32,
            "flops": rows * d * PROBABILITIES}
