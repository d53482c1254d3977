"""RobustScaler's model data as the configuration states it (upstream
``RobustScaler.java``): for every column ``j`` of the input vectors

    medians[j] = the element of 1-based rank ceil(0.5 n)
    ranges[j]  = the element of rank ceil(upper n) less that of rank
                 ceil(lower n)

each an element of the column (upstream's ``QuantileSummary.query`` returns
one too, within ``relativeError * n`` ranks of that rank; this reference and
the configuration allow none).

By a full sort: blocks of columns of the resident table are sorted along
the rows on the device, in float32 (``jnp.sort``), as many columns a block
as ``BLOCK_BYTES`` holds, and the three elements of each column are read
at their ranks; the differences are taken in float64 on the host. No
selection, no bisection, no count: another algorithm than the program's.
A table over several shards is first gathered onto the first shard's
device.

``precision="bfloat16"`` is the control: the block rounded to bfloat16
before the sort. ``compare`` gives ``median_gap`` and ``range_gap``, the
largest absolute difference; infinite where the sizes differ or the answer
is not finite."""

from __future__ import annotations

import functools
import math

import numpy as np

#: faults this reference can plant (``tools/limits_faults.py`` reads them)
FAULTS = ("state_unchanged", "half_blocks", "one_round_short")
#: bytes a block of columns may take (the sort keeps some four of them)
BLOCK_BYTES = 1_000_000_000
#: row blocks the ``half_blocks`` fault cuts the table into (every second
#: one is left out): 250,000 rows each at 12M
FAULT_BLOCKS = 48


@functools.lru_cache(maxsize=None)
def _sort_program(rows: int, cols: int, ranks: tuple, precision: str,
                  keep: tuple):
    import jax
    import jax.numpy as jnp

    def sorted_at(x, first_col):
        """``(len(ranks), cols)``: the elements of 0-based rank ``ranks``
        of each of ``cols`` columns from ``first_col`` on."""
        block = jax.lax.dynamic_slice_in_dim(x, first_col, cols, axis=1)
        if precision == "bfloat16":
            block = block.astype(jnp.bfloat16)
        if keep is not None:
            # the half_blocks fault: every second row block is left out
            # (it sorts behind every kept row)
            every, block_rows = keep
            at = jnp.arange(rows) // block_rows
            block = jnp.where((at % every == 0)[:, None], block, jnp.inf)
        ordered = jnp.sort(block, axis=0, stable=False)
        return ordered[jnp.asarray(ranks)].astype(jnp.float32)

    return jax.jit(sorted_at)


def _on_one_device(x):
    """The table as one single-device array: itself where it has one shard,
    else its shards' rows put together on the first shard's device."""
    import jax
    import jax.numpy as jnp

    shards = sorted(x.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    if len(shards) == 1:
        return shards[0].data
    device = shards[0].data.devices().pop()
    return jnp.concatenate([jax.device_put(s.data, device)
                            for s in shards], axis=0)


def ranks_of(probs, n: int) -> tuple:
    """0-based index of the element of 1-based rank ``ceil(q n)``."""
    return tuple(min(n - 1, max(1, math.ceil(q * n)) - 1) for q in probs)


def _one_step_up(values: np.ndarray) -> np.ndarray:
    """Each float32 with the lowest bit of its order-preserving integer
    key set: what a bisection that stopped one round early returns (the
    upper end of its last bracket of two)."""
    bits = np.asarray(values, np.float32).view(np.uint32)
    negative = bits >= np.uint32(0x80000000)
    key = np.where(negative, ~bits, bits | np.uint32(0x80000000))
    key = key | np.uint32(1)
    back = np.where(key >= np.uint32(0x80000000),
                    key & np.uint32(0x7FFFFFFF), ~key)
    return back.astype(np.uint32).view(np.float32)


def run(columns: dict, params: dict, tasks: int,
        precision: str = "float32", fault: str = None) -> dict:
    x = columns[params.get("inputCol", "input")]
    n, d = x.shape
    probs = (float(params.get("lower", 0.25)), 0.5,
             float(params.get("upper", 0.75)))
    if fault == "state_unchanged":
        return {"medians": np.zeros(d), "ranges": np.zeros(d)}
    keep, kept = None, n
    if fault == "half_blocks":
        keep = (2, max(1, -(-n // FAULT_BLOCKS)))
        kept = int(np.sum((np.arange(n) // keep[1]) % 2 == 0))
    ranks = ranks_of(probs, kept)
    table = _on_one_device(x)
    cols = max(1, min(d, BLOCK_BYTES // (4 * n)))
    program = _sort_program(n, cols, ranks, precision, keep)
    found = np.zeros((3, d), np.float32)
    for first in range(0, d, cols):
        start = min(first, d - cols)
        found[:, start:start + cols] = np.asarray(
            program(table, np.int32(start)))
    if fault == "one_round_short":
        found = _one_step_up(found)
    lo, med, hi = found.astype(np.float64)
    return {"medians": med, "ranges": hi - lo}


def _gap(answer, reference) -> float:
    a = np.asarray(answer, np.float64)
    r = np.asarray(reference, np.float64)
    if a.size != r.size or not np.isfinite(a).all():
        return float("inf")
    return float(np.max(np.abs(a.reshape(r.shape) - r), initial=0.0))


def compare(answer: dict, reference: dict) -> dict:
    if "medians" not in answer or "ranges" not in answer:
        return {"median_gap": float("inf"), "range_gap": float("inf")}
    return {"median_gap": _gap(answer["medians"], reference["medians"]),
            "range_gap": _gap(answer["ranges"], reference["ranges"])}
