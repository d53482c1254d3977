"""Feature↔label statistical tests.

Ref parity: the numeric cores of flink-ml-lib stats/{chisqtest,anovatest,
fvaluetest} and the univariate feature selector. Implemented with scipy
(host-side — these are keyed aggregations over modest cardinalities, not
MXU work).

Each function takes features (n, d) and labels (n,) and returns
(statistics (d,), p_values (d,), degrees_of_freedom (d,)).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from flink_ml_tpu._cold import importing

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


def _group_sums_kernel(x, y, c):
    import jax.numpy as jnp
    import jax.nn

    oh = jax.nn.one_hot(y.astype(jnp.int32), c, dtype=x.dtype)  # (n, c)
    return jnp.concatenate([oh.sum(axis=0)[:, None], oh.T @ x], axis=1)


def _group_ssw_kernel(x, y, means):
    import jax.numpy as jnp

    centered = x - means[y.astype(jnp.int32)]
    return jnp.sum(centered * centered, axis=0)


def anova_f_test(features: np.ndarray, labels: np.ndarray) -> Arrays:
    """One-way ANOVA F-test per feature (ref: stats/anovatest/ANOVATest.java
    — continuous feature vs categorical label).

    A device-resident feature matrix reduces ON device (two passes: group
    counts/sums, then centered within-group sum of squares against the
    replicated group means — float32-stable); only the (c, d) group stats
    cross to host, where the F/p math runs in float64."""
    labels = np.asarray(labels)
    classes, y_idx = np.unique(labels, return_inverse=True)
    c = len(classes)
    if _is_device(features):
        from flink_ml_tpu.ops import columnar

        n, d = features.shape
        y32 = y_idx.astype(np.int32)
        packed = np.asarray(columnar.apply_multi(
            _group_sums_kernel, (features, y32), static=(c,)), np.float64)
        counts, sums = packed[:, 0], packed[:, 1:]
        means = sums / np.maximum(counts[:, None], 1.0)
        ssw = np.asarray(columnar.apply_multi(
            _group_ssw_kernel, (features, y32),
            consts=(means.astype(np.float32),)), np.float64)
        grand = sums.sum(axis=0) / n
        ssb = (counts[:, None] * (means - grand[None, :]) ** 2).sum(axis=0)
        dfb, dfw = c - 1, n - c
        # IEEE semantics mirror scipy.f_oneway: ssw=0 with signal → F=inf
        # (p=0); 0/0 (constant feature) → NaN, as on the host path
        with np.errstate(divide="ignore", invalid="ignore"):
            f = (ssb / dfb) / (ssw / dfw)
        p = sstats.f.sf(f, dfb, dfw)
        return f, p, np.full(d, dfw, np.int64)
    features = np.asarray(features, np.float64)
    stats_, ps, dofs = [], [], []
    n = features.shape[0]
    for j in range(features.shape[1]):
        groups = [features[labels == cl, j] for cl in classes]
        f, p = sstats.f_oneway(*groups)
        stats_.append(f)
        ps.append(p)
        dofs.append(n - len(classes))
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
