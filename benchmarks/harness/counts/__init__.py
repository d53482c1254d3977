"""What a fit needs, counted from the cell's shapes: the bytes the
algorithm must read and the floating-point operations it must do, whatever
implements it. A relayout copy, a padded mask or a kernel swapped for XLA
changes the time only.

A configuration names its count by module: ``counts/<name>.py`` holds
``count(stage_params, data_params)``, which returns for ONE fit
``{"rows", "bytes", "flops"}``; ``rows`` is what ``fit_rows_per_s`` counts.
"""

from __future__ import annotations

import importlib

F32 = 4


def per_fit(name: str, stage_params: dict, data_params: dict) -> dict:
    try:
        module = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError:
        raise KeyError(f"no count {name!r} under harness/counts/") from None
    return module.count(stage_params, data_params)


def least_seconds(count: dict, peaks: dict, chips: int) -> dict:
    """The least time ``chips`` chips could take for one fit: the larger of
    FLOPs over peak FLOP/s and bytes over peak bandwidth; says which."""
    by_flops = count["flops"] / (peaks["peak_flops_per_s"] * chips)
    by_bytes = count["bytes"] / (peaks["peak_hbm_bytes_per_s"] * chips)
    return {"seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops > by_bytes else "bytes"}
