"""Scaler + selector tests vs sklearn/numpy oracles (ref: feature/*Test.java)."""

import numpy as np
import pytest

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.feature import (
    MaxAbsScaler,
    MinMaxScaler,
    RobustScaler,
    StandardScaler,
    StandardScalerModel,
    UnivariateFeatureSelector,
    VarianceThresholdSelector,
)


@pytest.fixture
def xtable(rng):
    x = rng.normal(size=(50, 4)) * np.array([1.0, 5.0, 0.1, 10.0]) + \
        np.array([0.0, 3.0, -1.0, 100.0])
    return Table.from_columns(input=x), x


def test_standard_scaler(xtable):
    table, x = xtable
    model = StandardScaler().fit(table)
    np.testing.assert_allclose(model.mean, x.mean(axis=0), rtol=1e-12)
    np.testing.assert_allclose(model.std, x.std(axis=0, ddof=1), rtol=1e-12)
    # default: withStd only
    out = model.transform(table)[0]["output"]
    np.testing.assert_allclose(out, x / x.std(axis=0, ddof=1), rtol=1e-6)
    # withMean too
    model.set_with_mean(True)
    out = model.transform(table)[0]["output"]
    np.testing.assert_allclose(out.mean(axis=0), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.std(axis=0, ddof=1), 1.0, rtol=1e-6)


def test_standard_scaler_save_load(xtable, tmp_path):
    table, _ = xtable
    model = StandardScaler().set_with_mean(True).fit(table)
    model.save(str(tmp_path / "ss"))
    reloaded = StandardScalerModel.load(str(tmp_path / "ss"))
    assert reloaded.with_mean is True
    np.testing.assert_array_equal(reloaded.mean, model.mean)
    np.testing.assert_allclose(reloaded.transform(table)[0]["output"],
                               model.transform(table)[0]["output"])


def test_standard_scaler_model_data_round_trip(xtable):
    table, _ = xtable
    model = StandardScaler().fit(table)
    (md,) = model.get_model_data()
    fresh = StandardScalerModel().set_model_data(md)
    np.testing.assert_allclose(fresh.mean, model.mean)
    np.testing.assert_allclose(fresh.std, model.std)


def test_min_max_scaler(xtable):
    table, x = xtable
    model = MinMaxScaler().fit(table)
    out = model.transform(table)[0]["output"]
    np.testing.assert_allclose(out.min(axis=0), 0.0, atol=1e-12)
    np.testing.assert_allclose(out.max(axis=0), 1.0, atol=1e-12)
    # custom range
    model2 = MinMaxScaler(min=-1.0, max=1.0).fit(table)
    out2 = model2.transform(table)[0]["output"]
    np.testing.assert_allclose(out2.min(axis=0), -1.0, atol=1e-12)
    np.testing.assert_allclose(out2.max(axis=0), 1.0, atol=1e-12)


def test_min_max_scaler_constant_dim():
    x = np.array([[1.0, 5.0], [1.0, 7.0], [1.0, 6.0]])
    model = MinMaxScaler().fit(Table.from_columns(input=x))
    out = model.transform(Table.from_columns(input=x))[0]["output"]
    np.testing.assert_allclose(out[:, 0], 0.5)  # constant → midpoint


def test_max_abs_scaler(xtable):
    table, x = xtable
    model = MaxAbsScaler().fit(table)
    out = model.transform(table)[0]["output"]
    np.testing.assert_allclose(out, x / np.abs(x).max(axis=0), rtol=1e-5)
    assert np.abs(out).max() <= 1.0 + 1e-12


def test_robust_scaler(rng):
    from sklearn.preprocessing import RobustScaler as SkRobust
    x = rng.normal(size=(200, 3)) * [1, 10, 100]
    table = Table.from_columns(input=x)
    model = RobustScaler().set_with_centering(True).fit(table)
    out = model.transform(table)[0]["output"]
    sk = SkRobust().fit_transform(x)
    # quantile method differs slightly ('lower' vs interpolation)
    np.testing.assert_allclose(out, sk, atol=0.15)


def test_variance_threshold_selector(rng):
    x = np.column_stack([
        rng.normal(size=100) * 10,      # high variance → kept
        np.full(100, 3.0),              # zero variance → removed
        rng.normal(size=100) * 0.01,    # tiny variance
    ])
    table = Table.from_columns(input=x)
    model = VarianceThresholdSelector().fit(table)
    assert list(model.indices) == [0, 2]
    out = model.transform(table)[0]["output"]
    assert out.shape == (100, 2)
    model2 = VarianceThresholdSelector(variance_threshold=1.0).fit(table)
    assert list(model2.indices) == [0]


def test_univariate_selector_anova(rng):
    # feature 0 strongly separates classes; features 1-3 are noise
    y = rng.integers(0, 2, 300).astype(float)
    x = rng.normal(size=(300, 4))
    x[:, 0] += y * 5
    table = Table.from_columns(features=x, label=y)
    model = UnivariateFeatureSelector(
        feature_type="continuous", label_type="categorical",
        selection_mode="numTopFeatures", selection_threshold=1).fit(table)
    assert list(model.indices) == [0]
    out = model.transform(table)[0]["output"]
    np.testing.assert_allclose(out[:, 0], x[:, 0])


def test_univariate_selector_fpr_modes(rng):
    from sklearn.feature_selection import f_regression
    y = rng.normal(size=200)
    x = rng.normal(size=(200, 5))
    x[:, 2] = y * 2 + rng.normal(size=200) * 0.1
    table = Table.from_columns(features=x, label=y)
    model = UnivariateFeatureSelector(
        feature_type="continuous", label_type="continuous",
        selection_mode="fpr", selection_threshold=1e-4).fit(table)
    assert 2 in list(model.indices)
    # our f-values match sklearn's
    from flink_ml_tpu.ops.stats import f_value_test
    f_ours, p_ours, _ = f_value_test(x, y)
    f_sk, p_sk = f_regression(x, y)
    np.testing.assert_allclose(f_ours, f_sk, rtol=1e-8)
    np.testing.assert_allclose(p_ours, p_sk, rtol=1e-8, atol=1e-12)


def test_device_resident_fit_stats_match_host(rng):
    """A device-resident input column computes fit statistics ON device;
    results must match the float64 host path within float32 tolerance
    (the dtype policy), for every stat-fitting estimator with a device
    branch."""
    import numpy as np

    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.feature import (
        IDF,
        MaxAbsScaler,
        MinMaxScaler,
        RobustScaler,
        StandardScaler,
        VarianceThresholdSelector,
    )
    from flink_ml_tpu.ops import columnar

    x = rng.normal(size=(500, 6)) * [1, 2, 3, 4, 5, 6] + 10
    t_host = Table.from_columns(input=x)
    t_dev = Table.from_columns(input=columnar.to_device(
        x.astype(np.float32)))

    pairs = [
        (StandardScaler(input_col="input", output_col="o"),
         lambda m: (m.mean, m.std)),
        (MinMaxScaler(input_col="input", output_col="o"),
         lambda m: (m.data_min, m.data_max)),
        (MaxAbsScaler(input_col="input", output_col="o"),
         lambda m: (m.max_abs,)),
        (IDF(input_col="input", output_col="o"),
         lambda m: (m.idf, m.doc_freq)),
    ]
    for est, stats in pairs:
        m_h = est.fit(t_host)
        m_d = est.fit(t_dev)
        for a, b in zip(stats(m_h), stats(m_d)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=2e-4, atol=1e-4,
                                       err_msg=type(est).__name__)

    # RobustScaler's device path is the sort-free rank-select kernel:
    # rank-exact order statistics with method='lower' semantics — the
    # same element-of-dataset contract as the host GK path and the
    # reference's QuantileSummary; oracle is numpy's 'lower' quantile
    rs_d = RobustScaler(input_col="input", output_col="o").fit(t_dev)
    x32 = x.astype(np.float32)
    np.testing.assert_allclose(
        rs_d.medians, np.quantile(x32, 0.5, axis=0, method="lower"),
        rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(
        rs_d.ranges,
        np.quantile(x32, 0.75, axis=0, method="lower")
        - np.quantile(x32, 0.25, axis=0, method="lower"),
        rtol=2e-3, atol=1e-3)

    sel_h = VarianceThresholdSelector(
        input_col="input", output_col="o",
        variance_threshold=4.0).fit(t_host)
    sel_d = VarianceThresholdSelector(
        input_col="input", output_col="o",
        variance_threshold=4.0).fit(t_dev)
    np.testing.assert_array_equal(sel_h.indices, sel_d.indices)


def test_scalers_sparse_paths_match_dense(rng):
    """MaxAbsScaler (fit+transform), StandardScaler (fit; std-only
    transform) and MinMaxScaler (fit) on CSR input must match their dense
    results, O(nnz), and only densify when the math demands it (mean
    centering; min-max offset)."""
    import numpy as np

    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.linalg.sparse import is_csr_column
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.models.feature import (
        MaxAbsScaler,
        MinMaxScaler,
        StandardScaler,
    )

    n, d = 60, 5
    dense = np.where(rng.random((n, d)) < 0.5, rng.normal(size=(n, d)), 0.0)
    col = np.empty(n, dtype=object)
    for i in range(n):
        nz = np.nonzero(dense[i])[0]
        col[i] = SparseVector(d, nz, dense[i, nz])
    t_sparse = Table.from_columns(v=col)
    t_dense = Table.from_columns(v=dense)

    ms = MaxAbsScaler(input_col="v", output_col="o").fit(t_sparse)
    md = MaxAbsScaler(input_col="v", output_col="o").fit(t_dense)
    np.testing.assert_allclose(ms.max_abs, md.max_abs, rtol=1e-6)
    o = ms.transform(t_sparse)[0].column("o")
    assert is_csr_column(o)
    np.testing.assert_allclose(
        o.to_dense(), np.asarray(md.transform(t_dense)[0].column("o")),
        rtol=1e-5, atol=1e-7)

    ss = StandardScaler(input_col="v", output_col="o").fit(t_sparse)
    sd = StandardScaler(input_col="v", output_col="o").fit(t_dense)
    np.testing.assert_allclose(ss.mean, sd.mean, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(ss.std, sd.std, rtol=1e-9, atol=1e-12)
    o = ss.transform(t_sparse)[0].column("o")
    assert is_csr_column(o)  # with_mean=False default: stays sparse
    np.testing.assert_allclose(
        o.to_dense(), np.asarray(sd.transform(t_dense)[0].column("o")),
        rtol=1e-5, atol=1e-6)
    ss.set(StandardScaler.WITH_MEAN, True)
    o = ss.transform(t_sparse)[0].column("o")
    assert not is_csr_column(o)  # centering densifies by necessity

    mm = MinMaxScaler(input_col="v", output_col="o").fit(t_sparse)
    mmd = MinMaxScaler(input_col="v", output_col="o").fit(t_dense)
    np.testing.assert_allclose(mm.data_min, mmd.data_min, rtol=1e-6)
    np.testing.assert_allclose(mm.data_max, mmd.data_max, rtol=1e-6)


def test_selectors_sparse_paths_match_dense(rng):
    """VarianceThresholdSelector fit and the index-selector transforms on
    CSR input must match the dense path and keep the output sparse."""
    import numpy as np

    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.linalg.sparse import is_csr_column
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.models.feature import VarianceThresholdSelector

    n, d = 80, 6
    dense = np.where(rng.random((n, d)) < 0.5, rng.normal(size=(n, d)), 0.0)
    dense[:, 3] = 0.0  # zero-variance dim must be dropped on both paths
    col = np.empty(n, dtype=object)
    for i in range(n):
        nz = np.nonzero(dense[i])[0]
        col[i] = SparseVector(d, nz, dense[i, nz])
    t_sparse = Table.from_columns(v=col)
    t_dense = Table.from_columns(v=dense)

    sel = dict(input_col="v", output_col="o", variance_threshold=0.05)
    ms = VarianceThresholdSelector(**sel).fit(t_sparse)
    md = VarianceThresholdSelector(**sel).fit(t_dense)
    np.testing.assert_array_equal(ms.indices, md.indices)
    assert 3 not in ms.indices

    o = ms.transform(t_sparse)[0].column("o")
    assert is_csr_column(o)
    np.testing.assert_allclose(
        o.to_dense(), np.asarray(md.transform(t_dense)[0].column("o")),
        rtol=1e-5, atol=1e-7)


def test_variance_selector_sparse_large_offset_stability(rng):
    """The sparse variance must be two-pass stable: stored values at a
    large offset (1e9 + noise, true variance ~1) must not cancel to zero
    — both paths must keep the feature."""
    import numpy as np

    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.models.feature import VarianceThresholdSelector

    n, d = 200, 2
    dense = np.zeros((n, d))
    dense[:, 0] = 1e9 + rng.normal(size=n)      # huge offset, var ~ 1
    dense[::2, 1] = rng.normal(size=n // 2) * 3  # half-sparse dim
    col = np.empty(n, dtype=object)
    for i in range(n):
        nz = np.nonzero(dense[i])[0]
        col[i] = SparseVector(d, nz, dense[i, nz])

    sel = dict(input_col="v", output_col="o", variance_threshold=0.5)
    ms = VarianceThresholdSelector(**sel).fit(Table.from_columns(v=col))
    md = VarianceThresholdSelector(**sel).fit(Table.from_columns(v=dense))
    np.testing.assert_array_equal(ms.indices, md.indices)
    assert 0 in ms.indices


def test_rank_select_device_exact_on_adversarial_columns(rng):
    """The sort-free device selection (``ops/quantile.select_on_device``,
    the entry point since PR 36) must return the EXACT method='lower' order
    statistic even when the value range is hostile: huge outliers
    (RobustScaler's core use case), infinities, denormals, signed zeros —
    counts over integer keys are range-independent."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.quantile import select_on_device

    cases = [
        (rng.normal(size=(5000, 4)) * [1, 10, 0.01, 1000]),
        np.concatenate([rng.random((9999, 2)), [[1e30, -1e30]]]),
        np.concatenate([rng.random((999, 2)), [[np.inf, -np.inf]]]),
        rng.random((500, 1)) * 1e-40,
        np.array([[-0.0], [0.0], [1.0], [-1.0]]),
    ]
    probs = [0.0, 0.25, 0.5, 0.75, 1.0]
    for x in cases:
        x32 = np.asarray(x, np.float32)
        got, passes = select_on_device(jnp.asarray(x32), probs)
        assert passes >= 1
        exp = np.quantile(x32.astype(np.float64), probs, axis=0,
                          method="lower").astype(np.float32)
        np.testing.assert_array_equal(got, exp)
