"""Feature↔label statistical tests.

Ref parity: the numeric cores of flink-ml-lib stats/{chisqtest,anovatest,
fvaluetest} and the univariate feature selector. The distributions are
scipy's, on the host in float64. The sums under the ANOVA F-test of a
device-resident table are the device's: exact grouped moments in one read
of the table (``moments_on_device``); the chi-square test counts on the
host, the F-value test reduces on the device in two float32 passes.

Each function takes features (n, d) and labels (n,) and returns
(statistics (d,), p_values (d,), degrees_of_freedom (d,)).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

from flink_ml_tpu._cold import importing
from flink_ml_tpu.observability.tracing import cold_build, tracer

with importing("scipy.stats"):  # a second of a cold start: a cold span
    from scipy import stats as sstats

Arrays = Tuple[np.ndarray, np.ndarray, np.ndarray]


def chi_square_test(features: np.ndarray, labels: np.ndarray) -> Arrays:
    """Pearson chi-squared independence test per feature column
    (ref: stats/chisqtest/ChiSqTest.java — categorical feature vs
    categorical label)."""
    features = np.asarray(features)
    labels = np.asarray(labels)
    stats_, ps, dofs = [], [], []
    for j in range(features.shape[1]):
        col = features[:, j]
        f_vals, f_idx = np.unique(col, return_inverse=True)
        l_vals, l_idx = np.unique(labels, return_inverse=True)
        table = np.zeros((len(f_vals), len(l_vals)))
        np.add.at(table, (f_idx, l_idx), 1.0)
        chi2, p, dof, _ = sstats.chi2_contingency(table, correction=False)
        stats_.append(chi2)
        ps.append(p)
        dofs.append(dof)
    return np.asarray(stats_), np.asarray(ps), np.asarray(dofs, np.int64)


def _is_device(x) -> bool:
    return not isinstance(x, np.ndarray) and hasattr(x, "addressable_shards")


# -- grouped moments: counts, sums and sums of squares by class ---------------
#
# The one-way ANOVA statistic is a small difference of large sums: with a
# label that says nothing about a feature the class means differ by
# ``sigma / sqrt(n_l)``, and a class sum that is off by 3e-8 of itself moves
# F by 1e-4. So the device adds exactly (``ops/fixedpoint.py``): every
# element as ``w = (x - pivot) / scale`` of its column, ``w`` and the
# float32 ``w * w`` each cut into fixed-point digits that a one-hot product
# adds by class without rounding; the host puts the digits together in
# int64 and takes F and p in float64. The pivot (0, or for a column whose
# mean dwarfs its spread a point on a coarse grid beside the mean: ``x -
# pivot`` is exact either way) keeps the within-class sum of squares from
# cancelling against the mean's square; pivot and scale come from a look at the
# first rows, and the pass itself reports every column's largest ``|w|``:
# over 1 and it is run again with the scale that holds it. One read of the
# table where it lies, two where the first rows misjudged a column.

#: labels the device path takes: whole numbers in [0, _MAX_DEVICE_LABELS)
_MAX_DEVICE_LABELS = 256
#: the table's first rows, which a fit looks at for its pivots and scales
_LOOK_ROWS = 4096
#: rows a block of the XLA form takes (a block's units stay whole in
#: float32 to ``fixedpoint.MAX_EXACT_ROWS``)
XLA_BLOCK_ROWS = 8192


def grouped_moments_xla(x, y, n_valid, pivot, inv, labels: int,
                        operand_dtype=None):
    """The XLA form of ``pallas_kernels.grouped_moments``, block by block in
    a loop, to the same integers: ``(lo, hi, counts, top)``. The last block
    is the table's last ``rows`` rows, masked down to the rows no earlier
    block has added. ``operand_dtype`` is float32 where the backend
    multiplies no bfloat16 (XLA's CPU): as exact, not as fast."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.ops.fixedpoint import (
        MOMENTS_DIGITS, add_units, moment_digits)

    n, d = x.shape
    operand_dtype = operand_dtype or jnp.bfloat16
    digits = sum(MOMENTS_DIGITS)
    rows = max(1, min(n, XLA_BLOCK_ROWS))
    label = jnp.arange(labels, dtype=y.dtype)

    def block(i, acc):
        lo, hi, counts, top = acc
        start = jnp.minimum(i * rows, n - rows)
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        at = start + jnp.arange(rows)
        mine = (at >= i * rows) & (at < n_valid)
        hit = (yb[:, None] == label) & mine[:, None]           # (rows, L)
        w = jnp.where(mine[:, None], (xb - pivot) * inv, 0.0)
        a = hit.astype(operand_dtype)
        units = jnp.stack([
            (jax.lax.dot_general(
                a, part.astype(operand_dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
             * jnp.float32(2.0 ** (8 * k))).astype(jnp.int32)
            for k, part in moment_digits(w)])               # (digits, L, d)
        lo, hi = add_units(lo, hi, units)
        return (lo, hi, counts + jnp.sum(hit, axis=0, dtype=jnp.int32),
                jnp.maximum(top, jnp.max(jnp.abs(w), axis=0)))

    zeros = jnp.zeros((digits, labels, d), jnp.int32)
    return jax.lax.fori_loop(
        0, -(-n // rows) if n else 0, block,
        (zeros, zeros, jnp.zeros((labels,), jnp.int32),
         jnp.zeros((d,), jnp.float32)))


#: steps of the grid on which the look takes a column's mean: whole
#: numbers under it add up exactly in float32 over ``_LOOK_ROWS`` rows, in
#: any order
_LOOK_STEPS = 2047


@functools.lru_cache(maxsize=32)
@cold_build("anova_look")
def moments_look_program(mesh, rows: int = _LOOK_ROWS):
    """``look(xs, ys, n_valid) -> (3 + 3 d,)`` replicated float32: what a
    fit learns of a table before it can shape a one-hot and scale a column.
    ``[the smallest label, the largest, 1 where every label is a whole
    number else 0]`` over EVERY row (the label column is 4 bytes a row:
    microseconds; a shard's zero padding is a whole label 0), then every
    column's smallest and largest entry over the table's first ``rows``
    rows and the sum of their places on a grid of ``_LOOK_STEPS`` steps
    between the two (a guess at the column's mean and reach: the pass
    checks it). Minima, maxima and sums of small whole numbers do not
    depend on the order they are taken in, so the look reads the same on
    every mesh, to the bit."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import mapreduce as mr
    from flink_ml_tpu.parallel.mesh import data_axes, data_pspec

    axes = data_axes(mesh)

    def anova_look(xl, yl, n_valid):
        whole = jnp.all(yl == jnp.floor(yl))
        head = xl[:rows]
        at = mr.shard_index(axes) * xl.shape[0] + jnp.arange(head.shape[0])
        mine = (at < jnp.minimum(n_valid, rows))[:, None]
        # one reduce_max over the shards: a smallest and an all are the
        # largest of the negated
        ends = mr.reduce_max(jnp.concatenate([
            jnp.stack([-jnp.min(yl), jnp.max(yl), -whole.astype(yl.dtype)]),
            -jnp.min(jnp.where(mine, head, jnp.inf), axis=0),
            jnp.max(jnp.where(mine, head, -jnp.inf), axis=0)]), axes)
        d = xl.shape[1]
        lo, hi = -ends[3:3 + d], ends[3 + d:]
        wide = jnp.where(hi > lo, hi - lo, 1.0)
        place = jnp.round((head - lo) / wide * _LOOK_STEPS)
        places = mr.reduce_sum(
            jnp.sum(jnp.where(mine, place, 0.0), axis=0), axes)
        return jnp.concatenate([
            ends[:3] * jnp.asarray([-1.0, 1.0, -1.0], yl.dtype), lo, hi,
            places])

    spec0 = data_pspec(mesh)
    return mr.map_shards(anova_look, mesh,
                         in_specs=(P(spec0, None), P(spec0), P()),
                         out_specs=P())


@functools.lru_cache(maxsize=32)
@cold_build("anova_moments")
def moments_program(mesh, labels: int, use_kernel: bool):
    """``moments(xs, ys, n_valid, pivot, inv) -> (lo, hi, counts, top)``
    replicated, over the rows ``[0, n_valid)`` of the row-sharded table:
    each shard adds its own rows' digits (``pallas_kernels.grouped_moments``
    where the backend and the shape gate admit it, :func:`grouped_moments_xla`
    elsewhere: the same integers), the shards' sums are added and their
    maxima taken once. ``lo`` and ``hi`` are ``(digits, labels, d)`` int32,
    ``counts`` ``(labels,)`` int32, ``top`` ``(d,)`` float32."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import mapreduce as mr
    from flink_ml_tpu.parallel.mesh import data_axes, data_pspec

    axes = data_axes(mesh)
    on_cpu = mesh.devices.flat[0].platform == "cpu"
    operand_dtype = jnp.float32 if on_cpu else jnp.bfloat16

    def anova_moments(xl, yl, n_valid, pivot, inv):
        local_n = xl.shape[0]
        nl = jnp.clip(n_valid - mr.shard_index(axes) * local_n, 0, local_n)
        if use_kernel:
            from flink_ml_tpu.ops.pallas_kernels import grouped_moments
            lo, hi, counts, top = grouped_moments(xl, yl, nl, pivot, inv,
                                                  labels)
        else:
            lo, hi, counts, top = grouped_moments_xla(
                xl, yl, nl, pivot, inv, labels, operand_dtype)
        return (mr.reduce_sum(lo, axes), mr.reduce_sum(hi, axes),
                mr.reduce_sum(counts, axes), mr.reduce_max(top, axes))

    spec0 = data_pspec(mesh)
    return mr.map_shards(
        anova_moments, mesh,
        in_specs=(P(spec0, None), P(spec0), P(), P(), P()),
        out_specs=(P(), P(), P(), P()))


class Moments(NamedTuple):
    """What the device pass hands the host, in float64: ``counts (L,)`` rows
    a label ``0 .. L - 1``; ``sums (L, d)`` and ``squares (L, d)`` of ``x -
    pivot`` and of its square by label (the F-test does not ask where the
    pivot lay); ``passes``, the whole reads of the table it took."""
    counts: np.ndarray
    sums: np.ndarray
    squares: np.ndarray
    passes: int


def pivot_and_scale(lo, hi, places, rows: int):
    """``(pivot, scale)`` float32 ``(d,)`` from what the look saw of every
    column: its smallest and largest entry and the sum of its entries'
    places between them over ``rows`` rows. A column whose mean lies
    further from 0 than twice its reach gets a pivot: the mean rounded to a
    grid an eighth of the spread wide (a power of two: few bits at the
    spread's own height), so that the within-class sum of squares does not
    cancel against the mean's square; every entry of such a column is
    larger than its distance from the pivot, so ``x - pivot`` is exact in
    float32. Any other column keeps pivot 0 and its entries whole. The
    scale is the power of two that holds twice the largest distance seen
    from the pivot (a normal column's 12M rows reach 5.4 sigma where 4,096
    show 3.7)."""
    lo, hi = np.asarray(lo, np.float64), np.asarray(hi, np.float64)
    with np.errstate(invalid="ignore", over="ignore", divide="ignore"):
        mean = lo + np.asarray(places, np.float64) / (
            rows * _LOOK_STEPS) * (hi - lo)
        spread = np.maximum(hi - mean, mean - lo)
        ok = np.isfinite(mean) & np.isfinite(spread) & (spread > 0)
        grid = np.where(ok, 2.0 ** (np.ceil(np.log2(spread)) - 3), 0.0)
        on_grid = np.where(ok, np.round(mean / np.where(ok, grid, 1.0))
                           * grid, np.where(np.isfinite(mean), mean, 0.0))
    far = ~ok | (np.abs(on_grid) > 2.0 * (spread + grid))
    pivot = np.where(far, on_grid, 0.0).astype(np.float32)
    reach = np.where(ok, spread + np.abs(pivot.astype(np.float64) - mean),
                     0.5)
    return pivot, scale_for(2.0 * reach)


def scale_for(reach):
    """The power of two at or over ``reach`` (float32; 1 for 0)."""
    reach = np.asarray(reach, np.float64)
    with np.errstate(divide="ignore"):
        exp = np.where(reach > 0, np.ceil(np.log2(np.where(
            reach > 0, reach, 1.0))), 0.0)
    return (2.0 ** np.clip(exp, -120, 120)).astype(np.float32)


def moments_on_device(x, y) -> Optional[Moments]:
    """Counts, sums and sums of squares by label of a DEVICE ``(n, d)``
    column against an ``(n,)`` label column (on the device, or on the host
    and placed here), or None where the table does not qualify (a label
    that is not a whole number in ``[0, 256)``, an entry that is not
    finite, no row): the caller tests on the host.

    The one driver of :func:`moments_look_program` and
    :func:`moments_program`. Spans ``anova.place_inputs``, ``anova.check``
    (the look, with the blocking read of its ``3 + 3 d`` numbers),
    ``anova.build_program``, then ``anova.launch`` (enqueue only) and
    ``anova.fetch`` (the one blocking read, through ``read_boundary``;
    ``passes``: the whole reads of the table it waited for) a pass: one,
    or two where a column reached past the scale its first rows gave.
    Counters ``ml.anova passes`` and ``classes``."""
    import jax

    from flink_ml_tpu.common.metrics import ML_GROUP, metrics
    from flink_ml_tpu.iteration.iteration import read_boundary
    from flink_ml_tpu.ops.fixedpoint import MOMENTS_DIGITS, digits_value
    from flink_ml_tpu.parallel.collective import ensure_on_mesh
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    n, d = x.shape
    if n == 0:
        return None
    mesh = default_mesh()
    axes = data_axes(mesh)
    with tracer.span("anova.place_inputs"):
        xs, _ = ensure_on_mesh(mesh, x, axes, np.float32)
        ys, _ = ensure_on_mesh(mesh, y, axes, np.float32)
    with tracer.span("anova.check", rows=min(_LOOK_ROWS, n)):
        seen = np.asarray(read_boundary(
            (moments_look_program(mesh)(xs, ys, np.int32(n)),))[0],
            np.float64)
    y_lo, y_hi, whole = seen[:3]
    if not whole or y_lo < 0 or not y_hi < _MAX_DEVICE_LABELS:
        return None
    labels = int(y_hi) + 1
    pivot, scale = pivot_and_scale(seen[3:3 + d], seen[3 + d:3 + 2 * d],
                                   seen[3 + 2 * d:], min(_LOOK_ROWS, n))
    with tracer.span("anova.build_program"):
        use_kernel = False
        if jax.default_backend() == "tpu":   # no Pallas import elsewhere
            from flink_ml_tpu.ops.pallas_kernels import moments_kernel_fits

            use_kernel = moments_kernel_fits(d, labels)
        program = moments_program(mesh, labels, use_kernel)
    for passes in (1, 2):
        with tracer.span("anova.launch", path="grouped-moments", rows=n,
                         d=d, labels=labels,
                         program="pallas" if use_kernel else "xla"):
            # (the row count, the pivots and the scales ride the call as
            # host operands: a handful of floats a column)
            out = program(xs, ys, np.int32(n), pivot,
                          (1.0 / scale).astype(np.float32))
        with tracer.span("anova.fetch", passes=1):
            # the blocking read, where the wait for the pass falls
            lo, hi, counts, top = read_boundary(out)
        if not np.all(np.isfinite(top)):
            return None
        if np.all(top <= 1.0):
            break
        # a column reached past what its first rows showed: the pass has
        # said how far, and the second is exact
        scale = np.where(top > 1.0, scale_for(
            top.astype(np.float64) * scale), scale).astype(np.float32)
    else:
        return None     # (a second pass fits by construction)
    if counts.sum() != n:
        return None
    group = metrics.group(ML_GROUP, "anova")
    group.counter("passes", passes)
    group.counter("classes", int(np.count_nonzero(counts)))
    n_sums = MOMENTS_DIGITS[0]
    scale = scale.astype(np.float64)
    return Moments(
        counts=counts.astype(np.float64),
        sums=digits_value(lo[:n_sums], hi[:n_sums]) * scale,
        squares=digits_value(lo[n_sums:], hi[n_sums:]) * scale * scale,
        passes=passes)


def anova_from_moments(m: Moments) -> Arrays:
    """F, p and the within-class degrees of freedom of every column from
    grouped moments about a pivot, in float64. The classes are the labels
    with a row; between- and within-class sums of squares are taken about
    the class means, which the pivot keeps near 0:
    ``ssb = sum n_l (s_l / n_l - s / n)^2`` and ``ssw = sum (q_l - s_l^2 /
    n_l)``, with ``s`` and ``q`` the sums and squares of ``x - pivot``."""
    present = m.counts > 0
    counts = m.counts[present][:, None]
    sums, squares = m.sums[present], m.squares[present]
    n, c = counts.sum(), int(present.sum())
    d = sums.shape[1]
    means = sums / counts
    grand = sums.sum(axis=0) / n
    ssb = (counts * (means - grand[None, :]) ** 2).sum(axis=0)
    ssw = np.maximum((squares - sums * means).sum(axis=0), 0.0)
    dfb, dfw = c - 1, n - c
    # IEEE semantics mirror scipy.f_oneway: ssw=0 with signal → F=inf
    # (p=0); 0/0 (constant feature) → NaN, as on the host path
    with np.errstate(divide="ignore", invalid="ignore"):
        f = (ssb / dfb) / (ssw / dfw)
    return f, sstats.f.sf(f, dfb, dfw), np.full(d, int(dfw), np.int64)


def anova_f_test(features, labels, report: dict = None) -> Arrays:
    """One-way ANOVA F-test per feature (ref: stats/anovatest/ANOVATest.java
    — continuous feature vs categorical label).

    A device-resident feature matrix is read once where it lies
    (:func:`moments_on_device`: exact grouped moments, the label read on
    the device; path ``grouped-moments``) and only the ``(L, 2 d + 1)``
    moments cross to the host, where F and p are float64. Any other table,
    and one the device path declines, is tested on the host by
    ``scipy.stats.f_oneway`` (``host-anova``). ``report``, where given,
    takes ``path`` and ``passes``."""
    report = {} if report is None else report
    if _is_device(features):
        found = moments_on_device(features, labels)
        if found is not None:
            report.update(path="grouped-moments", passes=found.passes)
            with tracer.span("anova.test"):
                return anova_from_moments(found)
    with tracer.span("anova.launch", path="host-anova",
                     rows=features.shape[0], d=features.shape[1], passes=1):
        features = np.asarray(features, np.float64)
        labels = np.asarray(labels)
        classes = np.unique(labels)
        stats_, ps, dofs = [], [], []
        n = features.shape[0]
        for j in range(features.shape[1]):
            groups = [features[labels == cl, j] for cl in classes]
            f, p = sstats.f_oneway(*groups)
            stats_.append(f)
            ps.append(p)
            dofs.append(n - len(classes))
    report.update(path="host-anova", passes=1)
    return np.asarray(stats_), np.asarray(ps), np.asarray(dofs, np.int64)


def _sums_kernel(x, y):
    import jax.numpy as jnp

    return jnp.concatenate([jnp.sum(x, axis=0), jnp.sum(y)[None]])


def _centered_products_kernel(x, y, xmean, ymean):
    import jax.numpy as jnp

    xc = x - xmean[None, :]
    yc = y - ymean
    return jnp.stack([jnp.sum(xc * yc[:, None], axis=0),
                      jnp.sum(xc * xc, axis=0),
                      jnp.full(x.shape[1], jnp.sum(yc * yc))])


def f_value_test(features: np.ndarray, labels: np.ndarray) -> Arrays:
    """Univariate linear-regression F-test per feature
    (ref: stats/fvaluetest/FValueTest.java — continuous vs continuous).

    Device-resident features reduce on device (two float32-stable passes);
    the (d,)-sized correlation → F → p tail runs in float64 on host."""
    if _is_device(features):
        from flink_ml_tpu.ops import columnar

        n, d = features.shape
        y32 = np.asarray(labels, np.float32)
        sums = np.asarray(columnar.apply_multi(
            _sums_kernel, (features, y32)), np.float64)
        xmean, ymean = sums[:-1] / n, sums[-1] / n
        packed = np.asarray(columnar.apply_multi(
            _centered_products_kernel, (features, y32),
            consts=(xmean.astype(np.float32), np.float32(ymean))),
            np.float64)
        sxy, sxx, syy = packed[0], packed[1], packed[2][0]
        dof = n - 2
        denom = np.sqrt(sxx * syy)
        corr = np.where(denom > 0, sxy / np.where(denom > 0, denom, 1.0),
                        0.0)
        corr = np.clip(corr, -1.0, 1.0)
        f = np.where(corr ** 2 < 1.0,
                     corr ** 2 / np.maximum(1.0 - corr ** 2, 1e-300) * dof,
                     np.inf)
        p = sstats.f.sf(f, 1, dof)
        return f, p, np.full(d, dof, np.int64)
    x = np.asarray(features, np.float64)
    y = np.asarray(labels, np.float64)
    n, d = x.shape
    dof = n - 2
    xc = x - x.mean(axis=0)
    yc = y - y.mean()
    denom = np.sqrt((xc * xc).sum(axis=0) * (yc * yc).sum())
    corr = np.where(denom > 0, (xc * yc[:, None]).sum(axis=0)
                    / np.where(denom > 0, denom, 1.0), 0.0)
    corr = np.clip(corr, -1.0, 1.0)
    f = np.where(corr ** 2 < 1.0,
                 corr ** 2 / np.maximum(1.0 - corr ** 2, 1e-300) * dof,
                 np.inf)
    p = sstats.f.sf(f, 1, dof)
    return f, p, np.full(d, dof, np.int64)
