"""Pallas TPU kernels.

The framework's hot device loops are mostly single fused matmuls that XLA
already schedules well (SURVEY.md §7 layer 1: "Pallas where XLA fusion is
insufficient"). The case where hand-tiling pays is nearest-centroid
assignment with large k: XLA materializes the (n, k) distance matrix in HBM
between the matmul and the argmin; this kernel keeps each (tile_n, k)
distance block in VMEM and writes only the argmin — HBM traffic drops from
O(n·k) to O(n·d + k·d + n).

Six kernels: ``assign_nearest`` (KMeans predict), ``lloyd_partial_sums``
(KMeans fit), ``category_counts`` (NaiveBayes fit), ``grouped_moments``
(the ANOVA F-test), ``segment_reduce_sum`` (scatter-add by segment id) and
``knn_topk_indices`` (KNN). Each runs on a real TPU backend where its shape
gate admits the input; elsewhere the plain XLA path runs. Tests exercise
the kernels in interpreter mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flink_ml_tpu._cold import importing
from flink_ml_tpu.ops.fixedpoint import (
    MOMENTS_DIGITS, add_units, moment_digits)

# the cold span ``import:pallas``: over a second inside the first fit of a
# process that takes a kernel, Mosaic's front end with it
with importing("pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

#: row tiles the Lloyd and assign kernels may take, widest first: a fit
#: at 12M x 100, k 10 on a v5e took 99.6 / 82.1 / 74.5 ms at 1024 / 2048 /
#: 4096 rows a tile (PERF.md section 6, PR 30)
TILES_N = (4096, 2048, 1024, 512, 256)
TILE_N = TILES_N[0]


def _split3(v):
    """``(hi, mid, lo)``, three bfloat16 arrays whose float32 sum is ``v``
    bit for bit: the three parts the MXU's own float32 product
    (``Precision.HIGHEST``) makes of an operand,
    made here once so that both products of a tile share them and so that
    the parts that are zero, or that fit one pass together, are not
    multiplied one by one. ``hi`` is ``v`` rounded to bfloat16's eight
    bits, ``mid`` the same of what is left, ``lo`` the rest, which has
    eight bits or fewer. The rounding is Veltkamp's, in float32 arithmetic
    (``g = 65537 v; hi = g - (g - v)``): a ``v - f32(bf16(v))`` is 3 ms a
    fit slower in the kernel, and in an XLA program it is 0 — XLA on the
    TPU drops a float32 -> bfloat16 -> float32 round trip, and the middle
    and low parts with it (PERF.md section 6, PR 30).

    The range is ``|v| < 2**128 / 65537`` (5.19e33): above it ``g``
    overflows and the parts are NaN where ``HIGHEST`` stayed finite (a
    squared distance overflows from 1.8e19 already). Under ``2**-103``
    (1e-31) the low part's last bits are subnormal, and a device that
    flushes them loses 1e-38, absolute; a CPU keeps them."""
    def top(u):
        g = u * jnp.float32(65537.0)
        return g - (g - u)

    hi = top(v)
    rest = v - hi
    mid = top(rest)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (rest - mid).astype(jnp.bfloat16))


def _dot(a, b, contract=((1,), (0,))):
    """One MXU pass over bfloat16 parts, accumulated in float32."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _cx(x3, c):
    """``c @ x``, ``(k, tile)``: the float32 product as ``HIGHEST`` forms
    it, the six largest of the nine part-products, in three MXU passes
    where it takes six. ``x3`` is the three parts of the ``(d, tile)``
    tile, ``c`` the ``(k, d)`` float32 centroids, whose parts are ``3k``
    rows of a 128-column array: stacked they ride one pass per part of
    ``x``."""
    k = c.shape[0]
    x_hi, x_mid, x_lo = x3
    c_hi, c_mid, c_lo = _split3(c)
    by_hi = _dot(jnp.concatenate([c_hi, c_mid, c_lo], axis=0), x_hi)
    by_mid = _dot(jnp.concatenate([c_hi, c_mid], axis=0), x_mid)
    by_lo = _dot(c_hi, x_lo)
    # smallest terms first: lo.hi + mid.mid + hi.lo, mid.hi + hi.mid, hi.hi
    return ((by_hi[2 * k:] + by_mid[k:] + by_lo)
            + (by_hi[k:2 * k] + by_mid[:k])) + by_hi[:k]


def _nearest(x3, c):
    """``(idx, first)``: ``idx`` the ``(k, tile)`` centroid index of every
    entry and ``first`` the ``(1, tile)`` index of each row's nearest
    centroid, the first smallest on ties. ``x3`` is the three parts of the
    ``(d, tile)`` tile — rows in lanes, so every per-row quantity is
    lane-dense."""
    k = c.shape[0]
    # ‖x−c‖² up to the per-row constant ‖x‖² (irrelevant to the argmin)
    d2 = jnp.sum(c * c, axis=1, keepdims=True) - 2.0 * _cx(x3, c)
    idx = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    first = jnp.min(jnp.where(d2 == jnp.min(d2, axis=0, keepdims=True),
                              idx, k), axis=0, keepdims=True)
    return idx, first


def _assign_kernel(xt_ref, c_ref, out_ref):
    # what the ragged last tile reads past the array gives indices past
    # the output, which are never written back
    out_ref[:] = _nearest(_split3(xt_ref[:]), c_ref[:])[1]


def _row_tiles(x, centroids, out_specs, prefetch: int = 0):
    """``(the (d, n) view the kernels read, grid spec)`` over row tiles of
    the widest width the shapes allow; ``out_specs(tile)`` gives the
    outputs' block specs. A resident ``(n, d)`` float32 table
    lies column-major tiled on the TPU (d = 100: 416 B a row), so inside a
    program the transpose is a relabelling and every per-row quantity is
    lane-dense; ``(tile, d)`` row blocks instead cost a relayout copy of
    the whole table in every fit (6.1 GB at 12M rows). The last tile is
    ragged: nothing is padded."""
    n, d = x.shape
    k = centroids.shape[0]
    tile = lloyd_tile(k, d) or TILES_N[-1]
    return x.T, pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=prefetch,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            pl.BlockSpec((d, tile), lambda i, *s: (0, i)),
            pl.BlockSpec((k, d), lambda i, *s: (0, 0)),
        ],
        out_specs=out_specs(tile))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _assign_tiles(x, centroids, interpret=False):
    xt, grid_spec = _row_tiles(
        x, centroids,
        lambda tile: pl.BlockSpec((1, tile), lambda i: (0, i)))
    return pl.pallas_call(
        _assign_kernel,
        name="assign_nearest",
        out_shape=jax.ShapeDtypeStruct((1, x.shape[0]), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(xt, centroids)[0]


def assign_nearest(x, centroids, interpret: bool = False):
    """Nearest-centroid index per row of x — fused distance+argmin, in
    float32, the first smallest index on ties.

    x: (n, d) float32; centroids: (k, d) float32 → (n,) int32. Any n: the
    last tile is ragged, nothing is padded.
    """
    x = jnp.asarray(x, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    if x.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    return _assign_tiles(x, centroids, interpret=interpret)


def pallas_supported() -> bool:
    """True when the default backend compiles pallas kernels (a TPU).
    Together with each kernel's shape gate this is the whole selection:
    a kernel that is chosen and then fails to lower, compile or run
    raises — no call site retries on the XLA twin."""
    return jax.default_backend() == "tpu"


# -- fused Lloyd round: assign + accumulate (KMeans fit) ---------------------

#: VMEM the kernel's working set may claim, under Mosaic's 16 MiB scoped
#: limit, by ``_lloyd_working_bytes``' count. The compiler's own count
#: (the least ``vmem_limit_bytes`` it compiles at: ``python
#: scripts/lloyd_vmem_bisect.py``, which needs no chip) is 2-24 % under
#: that sum at every ``(k, d)`` tried from (4, 6) to (1000, 64) and
#: (10, 1000), and 46 % under at the gate's widest k (1280 at d 100); at
#: d 100, k 10: 9.2 MiB at 4096 rows a tile (10.9 by the sum), 18.4 at
#: 8192. ``tests/test_lloyd_gate_compiles.py`` compiles both
#: kernels for the chip at the gate's edges, the largest k of every tile.
LLOYD_VMEM_BUDGET_BYTES = 12 << 20


def _lloyd_working_bytes(k: int, d: int, tile: int) -> int:
    """Bytes of VMEM one grid step of the Lloyd kernel is counted at. A
    row of the tile: the double-buffered float32 ``(d, tile)`` block, the
    masked tile, one float32 remainder of the split and the three bfloat16
    parts, made once (22 d); the ``(3k + 2k + k, tile)`` product blocks as
    far as they live together, the distances, the index and the one-hot
    (20 k). Beside the tile: the centroids with their stacked parts and
    the ``(k, d)`` + ``(k, 128)`` accumulators, double-buffered. ``d`` and
    ``k`` in whole bfloat16 sublane tiles."""
    dp, kp = -(-d // 16) * 16, -(-k // 16) * 16
    return tile * (22 * dp + 20 * kp) + kp * (28 * dp + 1024)


def lloyd_tile(k: int, d: int) -> int:
    """The widest row tile whose working set fits the VMEM budget for
    these shapes, 0 when none does (callers run the XLA round) — the shape
    gate of the Lloyd and assign kernels. One feature has none: Mosaic
    does not lower a bfloat16 product with one output column (the sums at
    d 1 fail its verifier)."""
    if d < 2:
        return 0
    for tile in TILES_N:
        if _lloyd_working_bytes(k, d, tile) <= LLOYD_VMEM_BUDGET_BYTES:
            return tile
    return 0


def lloyd_kernel_fits(k: int, d: int) -> bool:
    """True when the fused Lloyd kernel has a tile for these shapes —
    the gate kmeans.fit applies."""
    return lloyd_tile(k, d) > 0


def _lloyd_accum_kernel(nv_ref, xt_ref, c_ref, sums_ref, counts_ref):
    """One row tile of a Lloyd round, entirely in VMEM: nearest-centroid
    assignment and the (sums, counts) accumulation read the tile ONCE —
    the XLA round reads the shard for the pairwise matmul, again for the
    row norms, and a third time for the one_hot.T @ x sums. The TPU grid
    iterates sequentially per core, so the outputs accumulate across
    tiles (init at step 0). Counts accumulate per lane, ``(k, 128)``: the
    caller adds the lanes up."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)

    # the tile's rows whose index is under n_valid: what the ragged last
    # tile reads past the array is masked here, never padded in HBM
    tile = xt_ref.shape[1]
    valid = i * tile + jax.lax.broadcasted_iota(
        jnp.int32, (1, tile), 1) < nv_ref[0]
    x_hi, x_mid, x_lo = x3 = _split3(
        jnp.where(valid, xt_ref[:], 0.0))          # 3 x (d, tile)
    idx, first = _nearest(x3, c_ref[:])
    hit = (idx == first) & valid                   # (k, tile)
    # 0 and 1 are whole in bfloat16: the one-hot has no middle or low part,
    # and the three passes that would multiply by them are left out
    one_hot = hit.astype(jnp.bfloat16)
    rows = ((1,), (1,))                            # one_hot @ part.T
    sums_ref[:] += (_dot(one_hot, x_lo, rows) + _dot(one_hot, x_mid, rows)
                    ) + _dot(one_hot, x_hi, rows)
    counted = hit.astype(jnp.float32)
    counts_ref[:] += sum(counted[:, lane:lane + 128]
                         for lane in range(0, tile, 128))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lloyd_tiles(x, n_valid, centroids, interpret=False):
    k, d = centroids.shape
    xt, grid_spec = _row_tiles(
        x, centroids,
        lambda tile: (pl.BlockSpec((k, d), lambda i, s: (0, 0)),
                      pl.BlockSpec((k, 128), lambda i, s: (0, 0))),
        prefetch=1)
    sums, counts = pl.pallas_call(
        _lloyd_accum_kernel,
        name="lloyd_partial_sums",
        out_shape=(jax.ShapeDtypeStruct((k, d), jnp.float32),
                   jax.ShapeDtypeStruct((k, 128), jnp.float32)),
        grid_spec=grid_spec,
        interpret=interpret,
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), xt, centroids)
    return jnp.concatenate(
        [sums, jnp.sum(counts, axis=1, keepdims=True)], axis=1)


def lloyd_partial_sums(x, n_valid, centroids, interpret: bool = False):
    """Per-shard Lloyd partials — fused assign+accumulate, one pass over
    x, in float32.

    x: (n, d) float32; n_valid: scalar, rows ``[0, n_valid)`` count and the
    rest (a shard's zero padding) do not; centroids: (k, d) float32 →
    (k, d+1) float32 = [sums | counts]. Any n: the mask comes from
    ``n_valid`` and an iota inside the kernel and the last tile is ragged,
    so nothing is padded or copied. Euclidean only (assignment by the same
    csq − 2·c·xᵀ argmin as ``assign_nearest``). Callers psum the result
    across data shards and renormalize.
    """
    x = jnp.asarray(x, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    if x.shape[0] == 0:  # an empty grid would skip the step-0 init
        k, d = centroids.shape
        return jnp.zeros((k, d + 1), jnp.float32)
    return _lloyd_tiles(x, jnp.asarray(n_valid, jnp.int32), centroids,
                        interpret=interpret)


# -- one-hot contingency counts (NaiveBayes fit) ------------------------------

#: row tiles the counting kernel may take, widest first. 2048 is the widest
#: at which a tile's packed sums are exact (``_counts_kernel``); a pass over
#: 12M x 100 with 20 values and 10 labels on a v5e took 11.8 / 13.2 ms at
#: 2048 / 1024 rows a tile (PERF.md section 6, PR 33)
COUNTS_TILES_N = (2048, 1024, 512, 256)
#: what the odd value of a pair weighs in the packed one-hot: a tile has at
#: most 2048 rows, so the even value's count stays under it, and the sum
#: ``even + 4096 * odd <= 4096 * 2048 = 2**23`` is whole in float32
COUNTS_ODD_WEIGHT = 4096
#: VMEM the counting kernel's working set may claim, by
#: ``_counts_working_bytes``' count, under Mosaic's 16 MiB scoped limit
COUNTS_VMEM_BUDGET_BYTES = 12 << 20
#: pairs of values up to which the kernel's loop over them is unrolled
COUNTS_UNROLL_MAX = 16


def _counts_working_bytes(d: int, labels: int, values: int,
                          tile: int) -> int:
    """Bytes of VMEM one grid step of the counting kernel is counted at.
    A row of the tile: the double-buffered float32 ``(d, tile)`` block, the
    half, the weight, one pair's select and its bfloat16 one-hot (22 d),
    the label block and the labels' one-hot (10 L). Beside the tile: the
    ``(V, L, d)`` int32 counts, double-buffered, and one pair's float32
    product and its two int32 halves."""
    dp, lp = -(-d // 16) * 16, -(-labels // 16) * 16
    out = lp * (-(-d // 128) * 128) * 4
    return tile * (22 * dp + 10 * lp) + (2 * values + 6) * out


def counts_tile(d: int, labels: int, values: int) -> int:
    """The widest row tile whose working set fits the VMEM budget, 0 when
    none does (callers run the XLA form) — the counting kernel's shape
    gate."""
    if d < 2:       # see ``lloyd_tile``: no product with one output column
        return 0
    for tile in COUNTS_TILES_N:
        if (_counts_working_bytes(d, labels, values, tile)
                <= COUNTS_VMEM_BUDGET_BYTES):
            return tile
    return 0


def counts_kernel_fits(d: int, labels: int, values: int) -> bool:
    """True when the counting kernel has a tile for these shapes — the
    gate ``ops/contingency.py`` applies."""
    return counts_tile(d, labels, values) > 0


def _counts_kernel(nv_ref, xt_ref, y_ref, out_ref):
    """One row tile of the ``(value, label, feature)`` contingency counts,
    entirely in VMEM. ``A = onehot(y)`` is ``(L, tile)``; for the pair of
    values ``(2g, 2g + 1)`` ``B`` is ``(d, tile)`` and holds 1 where
    ``x == 2g``, 4096 where ``x == 2g + 1`` and 0 elsewhere: 0, 1 and a
    power of two are whole in bfloat16, so ``A @ B.T`` on the MXU with
    float32 accumulation is ``even + 4096 * odd``, the two exact counts of
    the tile's rows with label ``l``, packed: ``even <= 2048`` fits under
    the odd one's weight and the sum is at most 2**23, whole in float32.
    Two values ride one pass of the MXU, and one compare and select on the
    VPU, where a plain one-hot takes two (19.2 ms against 11.8 a pass over
    12M x 100, PERF.md section 6, PR 33). Across tiles the two halves add
    up in int32. Nothing is scattered and no integer key is ever formed.

    The rows whose index is not under ``n_valid`` — a shard's zero padding,
    and what the ragged last tile reads past the array — are masked out of
    ``A``, so whatever ``B`` holds there multiplies a zero. An entry that
    is not a whole number in ``[0, V)``, or a label not one in ``[0, L)``,
    matches nothing and is not counted: the counts of a table of ``n``
    rows add up to ``n * d`` exactly when every entry was in range."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    slots, labels, _ = out_ref.shape            # slots: values, made even
    tile = xt_ref.shape[1]
    valid = i * tile + jax.lax.broadcasted_iota(
        jnp.int32, (1, tile), 1) < nv_ref[0]
    label = jax.lax.broadcasted_iota(
        jnp.int32, (labels, tile), 0).astype(jnp.float32)
    a = ((label == y_ref[:]) & valid).astype(jnp.bfloat16)   # (L, tile)
    x = xt_ref[:]                                            # (d, tile)
    half = jnp.floor(x * 0.5)
    odd = x - 2.0 * half        # 0 or 1 for a whole number, exactly
    weight = jnp.where(odd == 0.0, 1.0, jnp.where(
        odd == 1.0, float(COUNTS_ODD_WEIGHT), 0.0))

    def one_pair(g, carry):
        b = jnp.where(half == g.astype(jnp.float32), weight,
                      0.0).astype(jnp.bfloat16)
        packed = _dot(a, b, ((1,), (1,))).astype(jnp.int32)  # (L, d)
        out_ref[pl.ds(2 * g, 2)] += jnp.stack(
            [packed & (COUNTS_ODD_WEIGHT - 1),
             packed >> (COUNTS_ODD_WEIGHT.bit_length() - 1)])
        return carry

    jax.lax.fori_loop(0, slots // 2, one_pair, 0,
                      unroll=slots // 2 <= COUNTS_UNROLL_MAX)


@functools.partial(jax.jit,
                   static_argnames=("labels", "values", "interpret"))
def _counts_tiles(x, y, n_valid, labels, values, interpret=False):
    n, d = x.shape
    tile = counts_tile(d, labels, values) or COUNTS_TILES_N[-1]
    slots = values + values % 2
    return pl.pallas_call(
        _counts_kernel,
        name="category_counts",
        out_shape=jax.ShapeDtypeStruct((slots, labels, d), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tile),),
            in_specs=[pl.BlockSpec((d, tile), lambda i, s: (0, i)),
                      pl.BlockSpec((1, tile), lambda i, s: (0, i))],
            out_specs=pl.BlockSpec((slots, labels, d),
                                   lambda i, s: (0, 0, 0))),
        interpret=interpret,
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), x.T,
      y[None, :])[:values]


def category_counts(x, y, n_valid, labels: int, values: int,
                    interpret: bool = False):
    """``counts[v, l, j]``: how many of the rows ``[0, n_valid)`` have
    label ``l`` and ``x[:, j] == v`` — one pass over ``x`` where it lies,
    exact, in int32.

    x: (n, d) float32; y: (n,) float32; → (values, labels, d) int32. An
    entry that is not a whole number in ``[0, values)`` (a label: in
    ``[0, labels)``) is not counted, so the counts add up to ``n_valid *
    d`` exactly when every entry was in range: the caller's check. Any n:
    the mask comes from ``n_valid`` and an iota inside the kernel and the
    last tile is ragged, so nothing is padded or copied (the ``(d, n)``
    view is a relabelling of the resident table: see ``_row_tiles``).
    Callers psum the result across data shards.
    """
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if x.shape[0] == 0:  # an empty grid would skip the step-0 init
        return jnp.zeros((values, labels, x.shape[1]), jnp.int32)
    return _counts_tiles(x, y, jnp.asarray(n_valid, jnp.int32),
                         labels=labels, values=values, interpret=interpret)


# -- grouped moments by exact fixed-point digits (ANOVA F-test) ---------------

#: row tiles the grouped-moments kernel may take, widest first; none under
#: 1024: the label column is read as it lies, a 1-D array in tiles of 1024
MOMENTS_TILES_N = (4096, 2048, 1024)
#: VMEM the kernel's working set may claim, by ``_moments_working_bytes``'
#: count, under Mosaic's 16 MiB scoped limit
MOMENTS_VMEM_BUDGET_BYTES = 12 << 20
def _moments_working_bytes(d: int, labels: int, tile: int) -> int:
    """Bytes of VMEM one grid step of the moments kernel is counted at. A
    row of the tile: the double-buffered float32 ``(d, tile)`` block, the
    scaled value, its square, one remainder and one digit in float32 and
    the eight bfloat16 digits (38 d), the label block and the labels'
    one-hot (12 L). Beside the tile: the ``(8, L, d)`` int32 ``lo`` and
    ``hi``, double-buffered, and the per-lane counts and maxima."""
    dp, lp = -(-d // 16) * 16, -(-labels // 16) * 16
    digits = sum(MOMENTS_DIGITS)
    out = lp * (-(-d // 128) * 128) * 4
    return tile * (38 * dp + 12 * lp) + 4 * digits * out + 1024 * (dp + lp)


def moments_tile(d: int, labels: int) -> int:
    """The widest row tile whose working set fits the VMEM budget, 0 when
    none does (callers run the XLA form) — the moments kernel's shape
    gate."""
    if d < 2:       # see ``lloyd_tile``: no product with one output column
        return 0
    for tile in MOMENTS_TILES_N:
        if (_moments_working_bytes(d, labels, tile)
                <= MOMENTS_VMEM_BUDGET_BYTES):
            return tile
    return 0


def moments_kernel_fits(d: int, labels: int) -> bool:
    """True when the moments kernel has a tile for these shapes — the gate
    ``ops/stats.py`` applies."""
    return moments_tile(d, labels) > 0


def _moments_kernel(magic, nv_ref, xt_ref, y_ref, pivot_ref, inv_ref,
                    lo_ref, hi_ref, counts_ref, top_ref):
    """One row tile of the grouped moments, entirely in VMEM. An element
    becomes ``w = (x - pivot) / scale`` (its column's, the scale a power of
    two: ``inv`` is exact) and ``w * w`` in float32, and each of the two its
    fixed-point digits (:func:`fixed_digits`); ``A = onehot(y)`` is ``(L,
    tile)``; ``A @ digit.T`` on the MXU with float32 accumulation is the
    tile's sum of that digit by label, exact (whole units, under 2**24),
    and the units add up across tiles in int32 with a carry. What comes out
    is the exact sum of every ``w`` and of every float32 ``w * w`` to the
    digits' last place, whatever the order of the rows or the tiles: an
    answer over four shards is the answer over one, bit for bit.

    ``top`` is the largest ``|w|`` of every column, lane by lane: over 1, a
    first digit was past 256 units and not whole in bfloat16, and the
    caller runs the pass again with the scale that holds it. The rows
    whose index is not under ``n_valid`` are masked out of ``A`` and of
    ``w`` (what the ragged last tile reads past the array may be anything).
    A label that is not a whole number in ``[0, L)`` matches nothing: the
    counts add up to ``n_valid`` exactly when every label was in range."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        lo_ref[:] = jnp.zeros_like(lo_ref)
        hi_ref[:] = jnp.zeros_like(hi_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        top_ref[:] = jnp.zeros_like(top_ref)

    labels = counts_ref.shape[0]
    tile = xt_ref.shape[1]
    chunks = range(0, tile, 128)
    valid = i * tile + jax.lax.broadcasted_iota(
        jnp.int32, (1, tile), 1) < nv_ref[0]
    label = jax.lax.broadcasted_iota(
        jnp.int32, (labels, 128), 0).astype(jnp.float32)
    # the label column comes as it lies, a (tile,) block of a 1-D array (a
    # (1, n) row would be a 48 MB copy a fit): 128 of it are the labels of
    # 128 table rows, already in lanes
    hit = jnp.concatenate(
        [label == y_ref[pl.ds(lane, 128)].reshape(1, 128)
         for lane in chunks], axis=1) & valid                # (L, tile)
    a = hit.astype(jnp.bfloat16)
    counted = hit.astype(jnp.int32)
    counts_ref[:] += sum(counted[:, lane:lane + 128] for lane in chunks)
    w = jnp.where(valid, (xt_ref[:] - pivot_ref[:]) * inv_ref[:], 0.0)
    size = jnp.abs(w)
    top = size[:, :128]
    for lane in chunks[1:]:
        top = jnp.maximum(top, size[:, lane:lane + 128])
    top_ref[:] = jnp.maximum(top_ref[:], top)
    for at, (k, part) in enumerate(moment_digits(w, magic)):
        units = (_dot(a, part.astype(jnp.bfloat16), ((1,), (1,)))
                 * jnp.float32(2.0 ** (8 * k))).astype(jnp.int32)  # (L, d)
        lo_ref[at], hi_ref[at] = add_units(lo_ref[at], hi_ref[at], units)


@functools.partial(jax.jit, static_argnames=("labels", "interpret"))
def _moments_tiles(x, y, n_valid, pivot, inv, labels, interpret=False):
    n, d = x.shape
    tile = moments_tile(d, labels) or MOMENTS_TILES_N[-1]
    digits = sum(MOMENTS_DIGITS)
    column = pl.BlockSpec((d, 1), lambda i, s: (0, 0))
    sums = pl.BlockSpec((digits, labels, d), lambda i, s: (0, 0, 0))
    lo, hi, counts, top = pl.pallas_call(
        # (interpreted, the body runs through XLA, whose simplifier undoes
        # the magic rounding: ``fixed_digits``)
        functools.partial(_moments_kernel, not interpret),
        name="grouped_moments",
        out_shape=(jax.ShapeDtypeStruct((digits, labels, d), jnp.int32),
                   jax.ShapeDtypeStruct((digits, labels, d), jnp.int32),
                   jax.ShapeDtypeStruct((labels, 128), jnp.int32),
                   jax.ShapeDtypeStruct((d, 128), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tile),),
            in_specs=[pl.BlockSpec((d, tile), lambda i, s: (0, i)),
                      pl.BlockSpec((tile,), lambda i, s: (i,)),
                      column, column],
            out_specs=(sums, sums,
                       pl.BlockSpec((labels, 128), lambda i, s: (0, 0)),
                       pl.BlockSpec((d, 128), lambda i, s: (0, 0)))),
        interpret=interpret,
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), x.T,
      y, pivot[:, None], inv[:, None])
    return lo, hi, jnp.sum(counts, axis=1), jnp.max(top, axis=1)


def grouped_moments(x, y, n_valid, pivot, inv, labels: int,
                    interpret: bool = False):
    """``(lo, hi, counts, top)`` over the rows ``[0, n_valid)``: for every
    label ``l < labels`` and column ``j`` the exact sums of the fixed-point
    digits of ``w = (x - pivot[j]) * inv[j]`` and of the float32 ``w * w``
    (``lo + (hi << 20)`` units of digit ``at``, ``(digits, labels, d)``
    int32 each: the digits of ``w`` first), the rows of every label
    ``(labels,)`` int32, and every column's largest ``|w|`` ``(d,)`` — one
    pass over ``x`` where it lies.

    x: (n, d) float32; y: (n,) float32; pivot, inv: (d,) float32, ``inv`` a
    power of two. Any n: the mask comes from ``n_valid`` and an iota inside
    the kernel and the last tile is ragged, so nothing is padded or copied.
    The sums are exact where ``top <= 1``. Callers add ``lo``, ``hi`` and
    ``counts`` and take the largest ``top`` across data shards."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    return _moments_tiles(x, y, jnp.asarray(n_valid, jnp.int32),
                          jnp.asarray(pivot, jnp.float32),
                          jnp.asarray(inv, jnp.float32), labels=labels,
                          interpret=interpret)


# -- fused segment-reduce (scatter-add by segment id) ------------------------

SEGREDUCE_TILE_N = 512

#: VMEM one grid step may claim: double-buffered (tile, d) value blocks,
#: the (tile, u) one-hot block, the ids tile and the (u, d) accumulator
#: that persists across grid steps
SEGREDUCE_VMEM_BUDGET_BYTES = 8 << 20


def segment_reduce_fits(num_segments: int, d: int) -> bool:
    """True when the fused segment-reduce kernel's working set fits the
    VMEM budget for these shapes — the gate callers apply. Scatter-add
    here is a one-hot matmul, so the segment domain must be small enough
    for a (tile, u) block; wide domains (hashed 2^18 features) keep
    XLA's native scatter."""
    t = SEGREDUCE_TILE_N
    working = (2 * t * d + t * num_segments + 2 * num_segments * d
               + 2 * t) * 4
    return 0 < num_segments and working <= SEGREDUCE_VMEM_BUDGET_BYTES


def _segreduce_kernel(x_ref, ids_ref, out_ref):
    """One row tile of a segment-sum, entirely in VMEM: the (tile, u)
    one-hot block exists only here — XLA's scatter-add lowers to a
    serialized per-row update on shapes this small, while the one-hot
    matmul runs on the MXU and reads the tile ONCE. The TPU grid
    iterates sequentially per core, so out_ref accumulates across tiles
    (init at step 0 — the Lloyd-partials idiom above). Out-of-range ids
    (negative padding included) match no one-hot column and contribute
    nothing, mirroring jax.ops.segment_sum's drop semantics."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    x = x_ref[:]                       # (tile, d)
    ids = ids_ref[:]                   # (tile, 1) int32
    u = out_ref.shape[0]
    one_hot = (ids == jax.lax.broadcasted_iota(
        jnp.int32, (1, u), 1)).astype(x.dtype)        # (tile, u)
    out_ref[:] += jnp.dot(one_hot.T, x,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _segreduce_padded(x, ids, num_segments, interpret=False):
    n, d = x.shape
    return pl.pallas_call(
        _segreduce_kernel,
        name="segment_reduce_sum",
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        grid=(n // SEGREDUCE_TILE_N,),
        in_specs=[
            pl.BlockSpec((SEGREDUCE_TILE_N, d), lambda i: (i, 0)),
            pl.BlockSpec((SEGREDUCE_TILE_N, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, d), lambda i: (0, 0)),
        interpret=interpret,
    )(x, ids)


def segment_reduce_sum(values, segment_ids, num_segments: int,
                       interpret: bool = False):
    """Fused per-segment sums — ``out[s] = Σ values[i] where
    segment_ids[i] == s`` — the segment-reduce shape XLA serializes as a
    per-row scatter. values: (n,) or (n, d) float32; segment_ids: (n,)
    int32 → (num_segments,) / (num_segments, d) float32. Callers gate
    with :func:`segment_reduce_fits`; rows with out-of-range ids are
    dropped (segment_sum parity). Pads n up to the tile size with id -1
    rows; euclidean of use: the FTRL sparse program's per-coordinate
    gradient/weight sums."""
    values = jnp.asarray(values, jnp.float32)
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    ids = jnp.asarray(segment_ids, jnp.int32)
    n = values.shape[0]
    if n == 0:  # empty grid would skip the step-0 init and return garbage
        out = jnp.zeros((num_segments, values.shape[1]), jnp.float32)
        return out[:, 0] if squeeze else out
    pad = (-n) % SEGREDUCE_TILE_N
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    out = _segreduce_padded(values, ids[:, None], num_segments,
                            interpret=interpret)
    return out[:, 0] if squeeze else out


# -- fused distance + top-k (KNN) -------------------------------------------

KNN_TILE_N = 256   # test rows per grid step
KNN_TILE_T = 2048  # train rows streamed per grid step
#: VMEM one grid step may claim — callers gate on
#: _knn_step_vmem_bytes(d, k) (the authoritative per-step estimate);
#: n_train itself is unbounded (streamed over the second grid axis)
KNN_VMEM_BUDGET_BYTES = 32 << 20


def _knn_step_vmem_bytes(d: int, k: int) -> int:
    """Upper estimate of one grid step's VMEM working set (bytes): the
    train/test tiles plus six (KNN_TILE_N, k + KNN_TILE_T)-ish blocks —
    d2, cross, tile_idx, comb_d, comb_i, and the fori_loop's masked
    comb_d copy. Deliberately generous: a shape this gate admits must
    compile, because a Mosaic VMEM overflow fails the predict."""
    return 4 * (KNN_TILE_T * d + KNN_TILE_N * d
                + 6 * KNN_TILE_N * (k + KNN_TILE_T))


def _knn_kernel(k: int, x_ref, t_ref, tsq_ref, idx_ref, bd_ref):
    """One test tile vs one STREAMED train tile: grid axis 1 walks the
    train set; the (KNN_TILE_N, k) best-distance/best-index carries ride
    in the revisited output blocks (the accumulate-across-grid idiom of
    the Lloyd partials above), so the (n_test, n_train) distance matrix
    never exists anywhere — not even tile-wise in HBM. Each step merges
    the carried top-k with the new tile's candidates in k argmin+mask
    passes (k is small; Mosaic has no native top_k).

    Tie-break: carried candidates (all from earlier tiles, hence lower
    train indices) sit BEFORE the new tile's columns in the merge block,
    and argmin takes the first minimum — so equal distances resolve to
    the lowest train index, matching lax.top_k. Padded train rows enter
    with tsq = +inf so they can never win a pick while a finite candidate
    remains (callers keep k ≤ n_train)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        bd_ref[:] = jnp.full(bd_ref.shape, jnp.inf, jnp.float32)
        idx_ref[:] = jnp.zeros(idx_ref.shape, jnp.int32)

    x = x_ref[:]                        # (tile_n, d)
    t = t_ref[:]                        # (tile_t, d)
    cross = jnp.dot(x, t.T, preferred_element_type=jnp.float32)
    # ‖x−t‖² up to the per-row constant ‖x‖² (rank-invariant)
    d2 = tsq_ref[:][None, :] - 2.0 * cross
    tile_n, tile_t = d2.shape
    tile_idx = j * tile_t + jax.lax.broadcasted_iota(
        jnp.int32, (tile_n, tile_t), 1)
    comb_d = jnp.concatenate([bd_ref[:], d2], axis=1)
    comb_i = jnp.concatenate([idx_ref[:], tile_idx], axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile_n, k + tile_t), 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (tile_n, k), 1)

    def pick(p, carry):
        comb_d, bd, bi = carry
        m = jnp.min(comb_d, axis=1)
        taken = cols == jnp.argmin(comb_d, axis=1).astype(
            jnp.int32)[:, None]
        chosen = jnp.sum(jnp.where(taken, comb_i, 0), axis=1)
        # column p of the carries, written as a select: Mosaic lowers no
        # dynamic_update_slice at a traced lane offset
        bd = jnp.where(kcols == p, m[:, None], bd)
        bi = jnp.where(kcols == p, chosen[:, None], bi)
        return jnp.where(taken, jnp.inf, comb_d), bd, bi

    _, bd, bi = jax.lax.fori_loop(
        0, k, pick, (comb_d, jnp.zeros((tile_n, k), jnp.float32),
                     jnp.zeros((tile_n, k), jnp.int32)))
    bd_ref[:] = bd
    idx_ref[:] = bi


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _knn_padded(x, train, k, interpret=False):
    n, d = x.shape
    nt = train.shape[0]
    tsq = jnp.sum(train * train, axis=1)
    pad_t = (-nt) % KNN_TILE_T
    if pad_t:
        train = jnp.pad(train, ((0, pad_t), (0, 0)))
        tsq = jnp.pad(tsq, (0, pad_t), constant_values=jnp.inf)
    kernel = functools.partial(_knn_kernel, k)
    idx, _ = pl.pallas_call(
        kernel,
        name="knn_topk_indices",
        out_shape=(jax.ShapeDtypeStruct((n, k), jnp.int32),
                   jax.ShapeDtypeStruct((n, k), jnp.float32)),
        grid=(n // KNN_TILE_N, (nt + pad_t) // KNN_TILE_T),
        in_specs=[
            pl.BlockSpec((KNN_TILE_N, d), lambda i, j: (i, 0)),
            pl.BlockSpec((KNN_TILE_T, d), lambda i, j: (j, 0)),
            pl.BlockSpec((KNN_TILE_T,), lambda i, j: (j,)),
        ],
        out_specs=(pl.BlockSpec((KNN_TILE_N, k), lambda i, j: (i, 0)),
                   pl.BlockSpec((KNN_TILE_N, k), lambda i, j: (i, 0))),
        interpret=interpret,
    )(x, train, tsq)
    return idx


def knn_topk_indices(x, train, k: int, interpret: bool = False):
    """Indices of the k nearest train rows per test row — fused
    distance+top-k streaming over train tiles; the distance matrix exists
    only as one (KNN_TILE_N, KNN_TILE_T) block in VMEM. x: (n, d);
    train: (n_train, d), ANY n_train — callers gate on
    _knn_step_vmem_bytes(d, k) ≤ KNN_VMEM_BUDGET_BYTES → (n, k) int32.
    Ties resolve to the lowest index (argmin), matching lax.top_k."""
    x = jnp.asarray(x, jnp.float32)
    train = jnp.asarray(train, jnp.float32)
    k = min(k, train.shape[0])
    n = x.shape[0]
    pad = (-n) % KNN_TILE_N
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return _knn_padded(x, train, k, interpret=interpret)[:n]
