"""One-way ANOVA F-test of every feature against a class label, and the
features of smallest p-value, as the configuration states it (upstream
``UnivariateFeatureSelector.java`` with ``featureType`` continuous and
``labelType`` categorical, whose numbers are ``ANOVATest.java``'s). For
rows ``i < n``, a feature ``x`` and classes ``l`` with ``n_l`` rows:

    mean_l = sum_{y_i = l} x_i / n_l          mean = sum_i x_i / n
    ssb    = sum_l n_l (mean_l - mean)^2      ssw  = sum_i (x_i - mean_{y_i})^2
    F      = (ssb / (L - 1)) / (ssw / (n - L))
    p      = P(F(L - 1, n - L) > F) = I_{dfw / (dfw + dfb F)}(dfw / 2, dfb / 2)

with ``L`` the number of classes present; the model is the
``selectionThreshold`` (50) features of smallest ``p``, ties to the lower
index.

F is a small difference of large sums (with a label that says nothing the
class means differ by ``sigma / sqrt(n_l)``), so the class sums are taken
EXACTLY: two walks over the table, block by block on the device that holds
each block, per class by comparison and masked sums (no one-hot product, no
scatter, no pivot). The first walk reads every entry as the whole number
``x * 2**23`` (the generator's grid: uniform float32 values are multiples of
``2**-23``, categorical ones whole numbers), cuts it into three bytes with
integer shifts and adds each byte by class in int32; blocks are added in
int64 on the host and the class means are float64. The second walk takes
``r = x * 2**23 - mean_{y_i} * 2**23`` (the mean rounded to the grid: whole
numbers both), squares it in int32 pieces and adds the pieces over the
block, which the host puts together in float64 (the textbook two-pass form,
not ``Q - S^2 / n``; the float32 product ``(x - mean)^2`` would round the
same way for every row of a table of zeros and ones: 9e-8 of F). A table
off that grid (an entry that is negative, 2 or more, or no multiple of
``2**-23``) is refused, not approximated.

``precision="bfloat16"`` is the control: the same sums accumulated in
bfloat16, on the device and over the blocks, which has eight bits: a sum
stops growing at 256. ``fault="float32_chain"`` accumulates them in float32
throughout (exact where every sum is a whole number under 2**24, as on a
table of zeros and ones; lost on continuous values).

``compare`` gives four numbers. ``f_gap`` is the largest ``|F - F_ref| /
F_ref`` over the features, ``p_gap`` the largest absolute difference of the
p-values, ``dof_gap`` 0 where the degrees of freedom are the reference's,
infinite otherwise, and ``selected_gap`` 0 where the selected index sets are
equal or differ only in features whose reference p-values lie within
``SELECT_TIE`` (the configuration's ``p_gap`` limit) of the last one
selected, infinite otherwise. All four are infinite where a statistic is
missing."""

from __future__ import annotations

import functools

import numpy as np

#: faults this reference can plant (``tools/limits_faults.py`` reads them)
FAULTS = ("half_blocks", "one_row_short", "float32_chain",
          "labels_off_by_one_class")
#: rows a block: 100 MB at d = 100
BLOCK_ROWS = 250_000
#: the grid the first walk reads entries on: ``x * GRID`` is a whole number
GRID = float(1 << 23)
#: reference p-values this near the last selected one's may swap places
#: (the configuration's ``p_gap`` limit)
SELECT_TIE = 1e-8
#: labels this reference compares against, one by one
MAX_LABELS = 256


def _state_dtype(precision: str):
    import ml_dtypes

    return np.dtype({"float32": np.float32,
                     "bfloat16": ml_dtypes.bfloat16}[precision])


@functools.lru_cache(maxsize=None)
def _range_program(rows: int):
    import jax
    import jax.numpy as jnp

    def block_range(x, y, start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        on_grid = jnp.all(xb * GRID == jnp.floor(xb * GRID))
        return (jnp.min(yb), jnp.max(yb), jnp.all(yb == jnp.floor(yb)),
                jnp.min(xb), jnp.max(xb), on_grid)

    return jax.jit(block_range)


@functools.lru_cache(maxsize=None)
def _bytes_program(rows: int, labels: int):
    import jax
    import jax.numpy as jnp

    def block_bytes(x, y, start, limit):
        """``(counts (L,), sums (3, L, d))`` int32 over the block's rows
        ``[0, limit)``: the three bytes of ``x * 2**23``, low byte first."""
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        q = (xb * GRID).astype(jnp.int32)
        live = jnp.arange(rows) < limit
        counts, sums = [], []
        for label in range(labels):
            of = (yb == label) & live
            counts.append(jnp.sum(of, dtype=jnp.int32))
            sums.append(jnp.stack([
                jnp.sum(jnp.where(of[:, None], (q >> shift) & 255, 0),
                        axis=0, dtype=jnp.int32) for shift in (0, 8, 16)]))
        return jnp.stack(counts), jnp.stack(sums, axis=1)

    return jax.jit(block_bytes)


@functools.lru_cache(maxsize=None)
def _float_program(rows: int, labels: int, precision: str):
    import jax
    import jax.numpy as jnp

    acc = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]

    def block_sums(x, y, start, limit):
        """``(counts (L,), sums (L, d), squares (L, d))`` accumulated in
        ``acc``: the control's and the float32 chain's first walk."""
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows).astype(acc)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        live = jnp.arange(rows) < limit
        counts, sums, squares = [], [], []
        for label in range(labels):
            of = ((yb == label) & live)[:, None]
            counts.append(jnp.sum(of, dtype=acc))
            sums.append(jnp.sum(jnp.where(of, xb, 0), axis=0, dtype=acc))
            squares.append(jnp.sum(jnp.where(of, xb * xb, 0), axis=0,
                                   dtype=acc))
        return jnp.stack(counts), jnp.stack(sums), jnp.stack(squares)

    return jax.jit(block_sums)


@functools.lru_cache(maxsize=None)
def _within_program(rows: int, labels: int):
    import jax
    import jax.numpy as jnp

    def block_within(x, y, start, limit, means):
        """``(6, d)`` int32: ``r = x * 2**23 - mean of the row's class``
        (whole numbers both) squared and added over the block's rows ``[0,
        limit)``, exactly: ``r = 4096 r1 + r0``, and of ``r1^2``, ``r1 r0``
        and ``r0^2`` (each under 2**24) the low twelve bits and the rest,
        added apart."""
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        own = jnp.zeros(xb.shape, jnp.int32)
        for label in range(labels):
            own = jnp.where((yb == label)[:, None], means[label], own)
        live = (jnp.arange(rows) < limit)[:, None]
        r = jnp.where(live, (xb * GRID).astype(jnp.int32) - own, 0)
        r1, r0 = r >> 12, r & 4095
        return jnp.stack([
            jnp.sum(part, axis=0, dtype=jnp.int32)
            for t in (r1 * r1, r1 * r0, r0 * r0)
            for part in (t & 4095, t >> 12)])

    return jax.jit(block_within)


def _blocks(x, y):
    """``[(x shard, y shard, start, rows, first global row)]`` over every
    row, shard by shard on the device that holds the shard."""
    def by_start(array):
        return sorted(array.addressable_shards,
                      key=lambda s: s.index[0].start or 0)

    out = []
    for xs, ys in zip(by_start(x), by_start(y)):
        first = xs.index[0].start or 0
        local = xs.data.shape[0]
        for start in range(0, local, BLOCK_ROWS):
            out.append((xs.data, ys.data, start,
                        min(BLOCK_ROWS, local - start), first + start))
    return out


def survival(f, dfb: int, dfw: int):
    """``P(F(dfb, dfw) > f)`` by the regularised incomplete beta function,
    float64; NaN stays NaN, an infinite F gives 0."""
    from scipy import special

    f = np.asarray(f, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        return special.betainc(dfw / 2.0, dfb / 2.0, dfw / (dfw + dfb * f))


def test_of(counts, sums, ssw):
    """``(F, p, dfw)`` in float64 from the class row counts ``(L,)``, class
    sums ``(L, d)`` and the within-class sum of squares ``(d,)``; classes
    without a row are left out."""
    counts = np.asarray(counts, np.float64)
    present = counts > 0
    counts = counts[present][:, None]
    sums = np.asarray(sums, np.float64)[present]
    n, classes = counts.sum(), int(present.sum())
    means = sums / counts
    grand = sums.sum(axis=0) / n
    ssb = (counts * (means - grand) ** 2).sum(axis=0)
    dfb, dfw = classes - 1, int(n) - classes
    with np.errstate(invalid="ignore", divide="ignore"):
        f = (ssb / dfb) / (np.asarray(ssw, np.float64) / dfw)
    return f, survival(f, dfb, dfw), dfw


def select(p, k: int):
    """The ``k`` indices of smallest ``p`` (NaN last, ties to the lower
    index), ascending."""
    return np.sort(np.argsort(p, kind="stable")[:k])


def _limits(blocks, n_counted, fault):
    """``[(block, rows of it that count)]`` as the fault leaves them."""
    return [(blk, max(0, min(blk[3], n_counted - blk[4])))
            for at, blk in enumerate(blocks)
            if not (fault == "half_blocks" and at % 2)]


def run(columns: dict, params: dict, tasks: int,
        precision: str = "float32", fault: str = None) -> dict:
    if (params.get("featureType"), params.get("labelType")) != (
            "continuous", "categorical"):
        raise NotImplementedError(
            "this reference covers continuous features against a "
            "categorical label")
    if params.get("selectionMode", "numTopFeatures") != "numTopFeatures":
        raise NotImplementedError("this reference covers numTopFeatures")
    top = int(params.get("selectionThreshold") or 50)
    x = columns[params.get("featuresCol", "features")]
    y = columns[params.get("labelCol", "label")]
    n, d = x.shape
    blocks = _blocks(x, y)
    ranges = [[np.asarray(v) for v in _range_program(rows)(xs, ys, start)]
              for xs, ys, start, rows, _ in blocks]
    labels = int(max(r[1] for r in ranges)) + 1
    if (min(r[0] for r in ranges) < 0 or labels > MAX_LABELS
            or not all(r[2] for r in ranges)):
        raise NotImplementedError(
            "this reference covers labels that are whole numbers from 0 up")
    if (min(r[3] for r in ranges) < 0 or max(r[4] for r in ranges) >= 2
            or not all(r[5] for r in ranges)):
        raise NotImplementedError(
            "this reference covers entries in [0, 2) on the grid 2**-23")
    merged = labels - 2 if fault == "labels_off_by_one_class" else None
    live = _limits(blocks, n - 1 if fault == "one_row_short" else n, fault)

    if precision == "bfloat16" or fault == "float32_chain":
        state = _state_dtype(precision)
        counts = np.zeros((labels,), state)
        sums = np.zeros((labels, d), state)
        squares = np.zeros((labels, d), state)
        for (xs, ys, start, rows, _), limit in live:
            c, s, q = _float_program(rows, labels, precision)(
                xs, ys, start, limit)
            counts = (counts + np.asarray(c).astype(state)).astype(state)
            sums = (sums + np.asarray(s).astype(state)).astype(state)
            squares = (squares + np.asarray(q).astype(state)).astype(state)
        counts, sums, squares = (np.asarray(a, np.float64)
                                 for a in (counts, sums, squares))
        with np.errstate(invalid="ignore", divide="ignore"):
            ssw = (squares - sums * sums / counts[:, None])[
                counts > 0].sum(axis=0)
    else:
        pending = [_bytes_program(blk[3], labels)(blk[0], blk[1], blk[2],
                                                  limit)
                   for blk, limit in live]
        counts = np.zeros((labels,), np.int64)
        units = np.zeros((labels, d), np.int64)
        for c, s in pending:
            counts += np.asarray(c, np.int64)
            s = np.asarray(s, np.int64)
            units += s[0] + (s[1] << 8) + (s[2] << 16)
        sums = units.astype(np.float64) / GRID   # exact: under 2**53 units
        if merged is not None:
            counts[merged] += counts[merged + 1]
            sums[merged] += sums[merged + 1]
            counts[merged + 1], sums[merged + 1] = 0, 0.0
        with np.errstate(invalid="ignore", divide="ignore"):
            means = np.where(counts[:, None] > 0,
                             sums / counts[:, None], 0.0)
        if merged is not None:
            means[merged + 1] = means[merged]
        on_grid = np.round(means * GRID)
        pending = [_within_program(blk[3], labels)(
            blk[0], blk[1], blk[2], limit, on_grid.astype(np.int32))
            for blk, limit in live]
        # r^2 = 2**24 r1^2 + 2**13 r1 r0 + r0^2, each product as 4096 hi + lo
        weights = np.asarray([2.0 ** 24, 2.0 ** 36, 2.0 ** 13, 2.0 ** 25,
                              1.0, 2.0 ** 12]) / GRID ** 2
        ssw = np.zeros((d,), np.float64)
        for parts in pending:
            ssw += weights @ np.asarray(parts, np.float64)
        # the means on the grid are not the means: sum (x - g)^2 is sum
        # (x - m)^2 + n_l (m - g)^2
        ssw -= (counts[:, None] * (means - on_grid / GRID) ** 2).sum(axis=0)
    f, p, dfw = test_of(counts, sums, ssw)
    return {"indices": select(p, top).astype(np.float64), "fValues": f,
            "pValues": p, "degreesOfFreedom": np.full(d, dfw, np.int64),
            "_counts": np.asarray(counts, np.float64), "_n": n}


def _gap(answer, reference, relative: bool) -> float:
    """max |answer - reference| (over |reference| where ``relative``) with
    entries that are equal (infinite and NaN ones too) at 0; infinite where
    the sizes differ or a NaN is on one side only."""
    a = np.asarray(answer, np.float64).ravel()
    r = np.asarray(reference, np.float64).ravel()
    if a.size != r.size:
        return float("inf")
    same = (a == r) | (np.isnan(a) & np.isnan(r))
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(a - r) / (np.abs(r) if relative else 1.0)
    gap = np.where(same, 0.0, gap)
    return float("inf") if np.isnan(gap).any() else float(
        np.max(gap, initial=0.0))


def compare(answer: dict, reference: dict) -> dict:
    inf = float("inf")
    keys = ("indices", "fValues", "pValues", "degreesOfFreedom")
    if any(k not in answer for k in keys):
        return {"f_gap": inf, "p_gap": inf, "dof_gap": inf,
                "selected_gap": inf}
    ours = {int(i) for i in np.asarray(answer["indices"]).ravel()}
    theirs = {int(i) for i in np.asarray(reference["indices"]).ravel()}
    p_ref = np.asarray(reference["pValues"], np.float64)
    selected = 0.0
    if ours != theirs:
        last = np.nanmax(p_ref[sorted(theirs)]) if theirs else np.nan
        swapped = np.asarray(sorted(ours ^ theirs), np.int64)
        near = (len(ours) == len(theirs)
                and swapped.max(initial=0) < p_ref.size
                and np.all(np.abs(p_ref[swapped] - last) <= SELECT_TIE))
        selected = 0.0 if near else inf
    same_dof = np.array_equal(
        np.asarray(answer["degreesOfFreedom"], np.float64).ravel(),
        np.asarray(reference["degreesOfFreedom"], np.float64).ravel())
    return {"f_gap": _gap(answer["fValues"], reference["fValues"], True),
            "p_gap": _gap(answer["pValues"], reference["pValues"], False),
            "dof_gap": 0.0 if same_dof else inf,
            "selected_gap": selected}
