"""Plain references: the semantics a configuration states, in straightforward
``jax.numpy`` at ``highest`` matmul precision with float64 accumulation on
the host, block by block so that they fit beside the resident table. They
import nothing of the program and take nothing it has made.

A reference module has ``run(columns, params, tasks, precision=..., fault=...)``
returning ``{model-data column: array}`` (+ keys starting with ``_``), and
``compare(answer, reference) -> {number: value}``. ``precision="bfloat16"``
is the control: the same semantics one precision below what the
configuration states. ``fault`` plants one of the module's ``FAULTS``, the
faults the tests and the limits are read against.
"""

from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")


def task_views(array, tasks: int):
    """``[(single-device array, offset)]``: where task ``s`` of ``tasks``
    finds its contiguous rows ``[s*n/tasks, (s+1)*n/tasks)``. Row-sharded
    arrays are read shard by shard on the device that holds the shard."""
    n = array.shape[0]
    if n % tasks:
        raise ValueError(f"{n} rows do not divide over {tasks} tasks")
    local_n = n // tasks
    shards = []
    for shard in array.addressable_shards:
        rows = shard.index[0]
        lo = rows.start or 0
        hi = n if rows.stop is None else rows.stop
        shards.append((lo, hi, shard.data))
    views = []
    for s in range(tasks):
        lo, hi = s * local_n, (s + 1) * local_n
        for a, b, data in shards:
            if a <= lo and hi <= b:
                views.append((data, lo - a))
                break
        else:
            raise ValueError(f"rows [{lo}, {hi}) span several shards")
    return views


def np_dtype(precision: str):
    """The host-side state's dtype: float64 accumulation for the reference,
    bfloat16 state for the control."""
    import ml_dtypes
    import numpy as np

    return np.dtype({"float32": np.float64,
                     "bfloat16": ml_dtypes.bfloat16}[precision])


def device_precision(precision: str):
    """``(dtype name on the device, matmul precision)``. ``float32`` is the
    reference; ``bfloat16`` the control."""
    import jax

    return {"float32": ("float32", jax.lax.Precision.HIGHEST),
            "bfloat16": ("bfloat16", jax.lax.Precision.HIGHEST)}[precision]


def worst_gap(answer, reference) -> float:
    """max |answer - reference| over max |reference|; infinite where the
    shapes differ or the answer is not finite."""
    import numpy as np

    a = np.asarray(answer, np.float64)
    r = np.asarray(reference, np.float64)
    if a.shape != r.shape or not np.isfinite(a).all():
        return float("inf")
    return float(np.max(np.abs(a - r)) / max(np.max(np.abs(r)), 1e-300))
