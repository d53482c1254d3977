"""Lloyd's k-means as the configuration states it (upstream
``KMeans.java``): the initial centroids are the rows
``numpy.random.default_rng(seed).choice(n, k, replace=False)`` of the table
(upstream's ``selectRandomCentroids`` draws k distinct input points and
fixes no order); in each of exactly ``maxIter`` rounds every row goes to
its nearest centroid by squared euclidean distance — direct squared
differences, the first smallest index on ties — and each centroid becomes
the mean of its rows, its weight their count; a cluster that got no row
keeps its centroid (the program's stated departure from upstream, which
divides by zero there).

Block by block on the device that holds the block, in float32 at
``highest`` matmul precision; the blocks' sums and counts are added in
float64 on the host. ``precision="bfloat16"`` is the control: rows and
centroids rounded to bfloat16 and the distances computed in it, the
centroid state kept in bfloat16.

``compare`` gives three numbers. ``centroid_gap`` and ``weight_gap`` hold
the answer to the reference. ``round_gap`` holds it to the number of
rounds: the answer's distance to the reference after ``maxIter`` rounds
over its distance to the reference after ``maxIter - 1``. On uniform data
Lloyd does not settle in ten rounds, and what float32 rounding does to a
few near-tie rows in the first rounds has grown to 7e-5 by the tenth,
while the tenth round itself moves the centroids by 2e-3: ``centroid_gap``
alone tells a fit that stopped a round early from a sound one by a factor
of 25, this ratio by that factor squared."""

from __future__ import annotations

import functools

import numpy as np

from . import device_precision, np_dtype, worst_gap

#: faults this reference can plant (``tools/limits_faults.py`` reads them)
FAULTS = ("state_unchanged", "half_batch", "one_round_short")
#: rows a block: 100 MB at d = 100
BLOCK_ROWS = 250_000


@functools.lru_cache(maxsize=None)
def _block_program(rows: int, precision: str):
    import jax
    import jax.numpy as jnp

    dtype_name, hi = device_precision(precision)
    dtype = jnp.dtype(dtype_name)

    def partials(x, c, start):
        k = c.shape[0]
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows).astype(dtype)
        cb = c.astype(dtype)
        with jax.default_matmul_precision("highest"):
            d2 = jnp.stack([jnp.sum(jnp.square(xb - cb[j]), axis=1)
                            for j in range(k)], axis=1)
            one_hot = (jnp.argmin(d2, axis=1)[:, None]
                       == jnp.arange(k)[None, :]).astype(dtype)
            sums = jnp.dot(one_hot.T, xb, precision=hi,
                           preferred_element_type=jnp.float32)
        return sums, jnp.sum(one_hot.astype(jnp.float32), axis=0)

    return jax.jit(partials)


def _blocks(x):
    """``[(single-device array, start, rows)]`` over every row, shard by
    shard on the device that holds the shard."""
    out = []
    for shard in sorted(x.addressable_shards,
                        key=lambda s: s.index[0].start or 0):
        local = shard.data.shape[0]
        for start in range(0, local, BLOCK_ROWS):
            out.append((shard.data, start, min(BLOCK_ROWS, local - start)))
    return out


def _initial_rows(x, index) -> np.ndarray:
    """Rows ``index`` of the table, fetched shard by shard."""
    rows = np.zeros((len(index), x.shape[1]), np.float64)
    for shard in x.addressable_shards:
        lo = shard.index[0].start or 0
        hi = lo + shard.data.shape[0]
        for at, i in enumerate(index):
            if lo <= i < hi:
                rows[at] = np.asarray(shard.data[int(i) - lo], np.float64)
    return rows


def run(columns: dict, params: dict, tasks: int,
        precision: str = "float32", fault: str = None) -> dict:
    if params.get("distanceMeasure", "euclidean") != "euclidean":
        raise NotImplementedError("this reference covers euclidean only")
    x = columns[params.get("featuresCol", "features")]
    n, d = x.shape
    k, rounds = int(params["k"]), int(params["maxIter"])
    if k > n:
        raise NotImplementedError("this reference needs k <= numValues")
    state = np_dtype(precision)
    index = np.random.default_rng(int(params["seed"])).choice(
        n, size=k, replace=False)
    c = _initial_rows(x, index).astype(state)
    counts = np.zeros(k, np.float64)
    if fault == "state_unchanged":
        rounds = 0
    elif fault == "one_round_short":
        rounds -= 1
    blocks = _blocks(x)
    before_last = c
    for _ in range(rounds):
        before_last = c
        device_c = np.asarray(c, np.float32)
        pending = [
            _block_program(rows, precision)(data, device_c, start)
            for at, (data, start, rows) in enumerate(blocks)
            if not (fault == "half_batch" and at % 2)]
        sums = sum(np.asarray(p[0], np.float64) for p in pending)
        counts = sum(np.asarray(p[1], np.float64) for p in pending)
        means = sums / np.maximum(counts, 1.0)[:, None]
        c = np.where(counts[:, None] > 0, means,
                     np.asarray(c, np.float64)).astype(state)
    return {"centroid": np.asarray(c, np.float64), "weight": counts,
            "_centroid_before_last": np.asarray(before_last, np.float64),
            "_rounds": rounds, "_n": n}


def compare(answer: dict, reference: dict) -> dict:
    weight = np.asarray(answer.get("weight"), np.float64)
    want = reference["weight"]
    if weight.shape != want.shape or not np.isfinite(weight).all():
        weight_gap = float("inf")
    else:
        weight_gap = float(np.max(np.abs(weight - want)) / reference["_n"])
    full = worst_gap(answer.get("centroid"), reference["centroid"])
    short = worst_gap(answer.get("centroid"),
                      reference["_centroid_before_last"])
    if np.array_equal(reference["centroid"],
                      reference["_centroid_before_last"]):
        round_gap = 0.0     # the last round moved nothing: nothing to tell
    elif full == 0.0:
        round_gap = 0.0
    elif short > 0.0 and np.isfinite(full):
        round_gap = full / short
    else:
        round_gap = float("inf")
    return {"centroid_gap": full, "weight_gap": weight_gap,
            "round_gap": round_gap}
