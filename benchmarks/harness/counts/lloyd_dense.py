"""Lloyd rounds over dense rows: every round reads every row once — ``d``
features — finds the nearest of ``k`` centroids (``k`` dot products of
length ``d`` a row: ``2*d*k`` FLOPs) and adds the row into that centroid's
sum (``d`` FLOPs), whatever implements the round."""

from . import F32


def count(stage_params: dict, data_params: dict) -> dict:
    rounds, k = int(stage_params["maxIter"]), int(stage_params["k"])
    d = int(data_params["vectorDim"])
    rows = rounds * int(data_params["numValues"])
    return {"rows": rows, "bytes": rows * d * F32,
            "flops": rows * (2 * d * k + d)}
