"""Sparse (CSR) training and predict paths.

Ref parity: the reference trains on SparseVector input without densifying —
FTRL's sparse gradient branch (OnlineLogisticRegression.java:364-388,
per-coordinate weight sums at touched indices only) and sparse dots
(BLAS.java:78 hDot). These tests pin the CSR plumbing, the dense↔sparse
semantic difference, and the bounded-memory wide-feature path
(HashingTF at 2^18 dims → FTRL).
"""

import numpy as np
import pytest

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.linalg import sparse
from flink_ml_tpu.linalg.vectors import DenseVector, SparseVector


def _sparse_column_from_dense(x, keep_all=True, rng=None):
    """Dense (n,d) → object column of SparseVectors; keep_all=True keeps
    every coordinate (so sparse/dense semantics coincide)."""
    out = np.empty(x.shape[0], dtype=object)
    for i, row in enumerate(x):
        if keep_all:
            idx = np.arange(x.shape[1])
        else:
            idx = np.flatnonzero(row != 0.0)
        out[i] = SparseVector(x.shape[1], idx, row[idx])
    return out


def test_column_to_csr_roundtrip(rng):
    x = rng.random((50, 8))
    x[x < 0.6] = 0.0
    col = _sparse_column_from_dense(x, keep_all=False)
    m = sparse.column_to_csr(col)
    assert m.shape == (50, 8)
    np.testing.assert_allclose(m.toarray(), x)
    back = sparse.csr_to_column(m)
    np.testing.assert_allclose(back[3].to_array(), x[3])


def test_mixed_dense_sparse_column_and_ragged_raise(rng):
    """A column mixing DenseVector and SparseVector rows forms one CSR
    (dense rows become fully-present sparse rows, the reference's per-row
    instanceof dispatch); ragged sizes raise instead of scattering out of
    bounds."""
    col = np.empty(3, dtype=object)
    col[0] = SparseVector(4, [1, 3], [1.0, 2.0])
    col[1] = DenseVector(np.asarray([5.0, 0.0, 6.0, 0.0]))
    col[2] = SparseVector(4, [0], [7.0])
    assert sparse.is_sparse_column(col)
    m = sparse.column_to_csr(col)
    np.testing.assert_allclose(
        m.toarray(), [[0, 1, 0, 2], [5, 0, 6, 0], [7, 0, 0, 0]])

    bad = np.empty(2, dtype=object)
    bad[0] = SparseVector(4, [0], [1.0])
    bad[1] = SparseVector(9, [8], [1.0])
    with pytest.raises(ValueError, match="ragged"):
        sparse.column_to_csr(bad)

    # dense-first mixed columns still take the sparse path
    rev = col[::-1].copy()
    assert sparse.is_sparse_column(rev)


def test_ftrl_sparse_full_pattern_matches_dense(rng):
    """With every coordinate present in each SparseVector, the sparse
    branch reduces to the dense branch. The dense branch now runs as a
    compiled float32 device program (docs/deviations.md dtype policy)
    while sparse stays float64 host, so agreement is allclose, not
    bit-for-bit."""
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    n, d = 400, 6
    x = rng.normal(size=(n, d))
    true_w = rng.normal(size=d)
    y = (x @ true_w > 0).astype(np.float64)
    init = Table.from_columns(coefficient=[DenseVector(np.zeros(d))])

    def fit(features_col):
        est = OnlineLogisticRegression(
            features_col="features", label_col="label",
            global_batch_size=100)
        est.set_initial_model_data(init)
        return est.fit(Table.from_columns(features=features_col, label=y))

    dense_model = fit(x)
    sparse_model = fit(_sparse_column_from_dense(x, keep_all=True))
    np.testing.assert_allclose(sparse_model.coefficients,
                               dense_model.coefficients,
                               rtol=1e-5, atol=1e-7)
    assert sparse_model.model_version == dense_model.model_version


def test_ftrl_sparse_per_coordinate_weight_sums(rng):
    """The reference's sparse branch normalizes each coordinate's gradient
    by the weight that actually touched it — a coordinate seen in half the
    rows gets half the weight sum. One hand-checked batch."""
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    # two rows: row0 touches coords {0,1}, row1 touches {1}
    col = np.empty(2, dtype=object)
    col[0] = SparseVector(3, [0, 1], [1.0, 2.0])
    col[1] = SparseVector(3, [1], [3.0])
    y = np.asarray([1.0, 0.0])
    init = Table.from_columns(coefficient=[DenseVector(np.zeros(3))])
    est = OnlineLogisticRegression(features_col="f", label_col="l",
                                   global_batch_size=2, alpha=0.5, beta=1.0)
    est.set_initial_model_data(init)
    model = est.fit(Table.from_columns(f=col, l=y))
    # by hand: p = sigmoid(0) = 0.5 for both rows
    grad = np.asarray([(0.5 - 1.0) * 1.0,
                       (0.5 - 1.0) * 2.0 + (0.5 - 0.0) * 3.0, 0.0])
    wsum = np.asarray([1.0, 2.0, 0.0])
    g = np.where(wsum != 0, grad / np.where(wsum != 0, wsum, 1), 0.0)
    sigma = np.sqrt(g * g) / 0.5  # n starts at 0
    z = g  # z += g - sigma*coeffs, coeffs = 0
    nacc = g * g
    expect = np.where(np.abs(z) <= 0.0, 0.0,
                      (np.sign(z) * 0.0 - z) / ((1.0 + np.sqrt(nacc)) / 0.5))
    np.testing.assert_allclose(model.coefficients, expect, rtol=1e-12)


def test_ftrl_wide_hashed_features_bounded_memory():
    """HashingTF at 2^18 dims → FTRL without densifying: a dense stack
    would need n×262144×8 bytes; the CSR path stays O(nnz)."""
    from flink_ml_tpu.models.feature import HashingTF
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    rng = np.random.default_rng(7)
    n, m = 2000, 1 << 18
    vocab = [f"tok{i}" for i in range(500)]
    docs = np.empty(n, dtype=object)
    for i in range(n):
        docs[i] = list(rng.choice(vocab, size=rng.integers(3, 10)))
    labels = rng.integers(0, 2, n).astype(np.float64)
    t = Table.from_columns(doc=docs, label=labels)
    hashed = HashingTF(input_col="doc", output_col="features",
                       num_features=m).transform(t)[0]
    assert sparse.is_sparse_column(hashed.column("features"))

    init = Table.from_columns(
        coefficient=[DenseVector(np.zeros(m))])
    est = OnlineLogisticRegression(features_col="features",
                                   label_col="label",
                                   global_batch_size=500)
    est.set_initial_model_data(init)
    model = est.fit(hashed)
    assert model.coefficients.shape == (m,)
    assert np.isfinite(model.coefficients).all()
    # predict on the sparse column without densifying
    out = model.transform(hashed)[0]
    assert out.column(model.prediction_col).shape == (n,)


def test_sgd_csr_matches_dense_fit(rng):
    """LogisticRegression on a SparseVector column (full pattern) agrees
    with the dense device fit — same batch slicing, update and
    termination semantics by construction."""
    from flink_ml_tpu.models.classification import LogisticRegression
    n, d = 600, 5
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)

    def fit(col):
        return LogisticRegression(
            features_col="features", label_col="label",
            global_batch_size=120, max_iter=20).fit(
                Table.from_columns(features=col, label=y))

    dense = fit(x).coefficients
    csr = fit(_sparse_column_from_dense(x, keep_all=True)).coefficients
    np.testing.assert_allclose(csr, dense, rtol=2e-3, atol=2e-4)


def test_sgd_csr_regularized_and_svc(rng):
    """CSR path applies the same regularization formulas (elastic net) and
    serves LinearSVC's hinge loss too."""
    from flink_ml_tpu.models.classification import LinearSVC
    n, d = 400, 4
    x = rng.normal(size=(n, d))
    y = (x[:, 0] > 0).astype(np.float64)

    def fit(col):
        return LinearSVC(features_col="features", label_col="label",
                         global_batch_size=100, max_iter=15,
                         reg=0.01, elastic_net=0.5).fit(
                             Table.from_columns(features=col, label=y))

    dense = fit(x).coefficients
    csr = fit(_sparse_column_from_dense(x, keep_all=True)).coefficients
    np.testing.assert_allclose(csr, dense, rtol=5e-3, atol=5e-4)


def test_sparse_predict_matches_dense(rng):
    from flink_ml_tpu.models.classification import LogisticRegression
    n, d = 100, 5
    x = rng.normal(size=(n, d))
    y = (x[:, 0] > 0).astype(np.float64)
    model = LogisticRegression(features_col="features", label_col="label",
                               global_batch_size=50).fit(
        Table.from_columns(features=x, label=y))
    dense_pred = model.transform(
        Table.from_columns(features=x, label=y))[0]["prediction"]
    sparse_pred = model.transform(Table.from_columns(
        features=_sparse_column_from_dense(x, keep_all=False),
        label=y))[0]["prediction"]
    np.testing.assert_array_equal(np.asarray(dense_pred),
                                  np.asarray(sparse_pred))


def test_sparse_fit_host_mode_matches_plain(rng):
    """CSR fit through the host iteration driver (listeners/checkpoint
    hooks) must equal the plain CSR loop (SGD.java:308-360 parity: state
    persistence is representation-agnostic)."""
    from flink_ml_tpu.iteration.iteration import IterationConfig
    from flink_ml_tpu.models.classification import LogisticRegression
    x = rng.normal(size=(60, 4))
    y = (x[:, 0] > 0).astype(np.float64)
    t = Table.from_columns(f=_sparse_column_from_dense(x), l=y)

    def est():
        return LogisticRegression(features_col="f", label_col="l",
                                  global_batch_size=16, max_iter=9)

    expected = est().fit(t).coefficients
    host = est().set_iteration_config(IterationConfig(mode="host")) \
        .fit(t).coefficients
    np.testing.assert_allclose(host, expected, rtol=1e-12)


def test_sparse_fit_crash_resume_identical_result(rng, tmp_path):
    """Mid-fit crash + resume on the CSR path reproduces the uninterrupted
    result exactly (the BoundedAllRoundCheckpointITCase bar, now for
    wide-sparse training — VERDICT r2 ask #8)."""
    from flink_ml_tpu.iteration.checkpoint import CheckpointManager
    from flink_ml_tpu.iteration.iteration import (IterationConfig,
                                                  IterationListener)
    from flink_ml_tpu.models.classification import LogisticRegression

    class _Crash(Exception):
        pass

    class _CrashAt(IterationListener):
        def __init__(self, at):
            self.at = at

        def on_epoch_watermark_incremented(self, epoch, carry):
            if epoch == self.at:
                raise _Crash()

    x = rng.normal(size=(80, 6))
    y = (x @ rng.normal(size=6) > 0).astype(np.float64)
    t = Table.from_columns(f=_sparse_column_from_dense(x), l=y)

    def est():
        return LogisticRegression(features_col="f", label_col="l",
                                  global_batch_size=32, max_iter=10)

    expected = est().fit(t).coefficients

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    cfg = IterationConfig(mode="host", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    with pytest.raises(_Crash):
        est().set_iteration_config(cfg, listeners=[_CrashAt(6)]).fit(t)
    assert mgr.list_checkpoints()

    resumed = est().set_iteration_config(cfg).fit(t).coefficients
    np.testing.assert_allclose(resumed, expected, rtol=1e-12)


def test_csr_vector_column_indexing_and_concat():
    """CsrVectorColumn must behave like the object column it replaces:
    negative scalar indices, slices, out-of-bounds errors, and concat with
    an object column on EITHER side (keeping CSR backing both ways)."""
    import scipy.sparse as sp

    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.linalg.sparse import CsrVectorColumn, is_csr_column

    m = sp.csr_matrix(np.asarray([[0.0, 1.0], [2.0, 0.0], [0.0, 3.0]]))
    col = CsrVectorColumn(m)
    assert col[-1] == col[2] and col[-1].values.tolist() == [3.0]
    assert len(col[0:2]) == 2 and col[0:2][1].values.tolist() == [2.0]
    with pytest.raises(IndexError):
        col[3]
    with pytest.raises(IndexError):
        col[-4]

    # dense off-ramp narrows before densifying (no float64 temp), dtype kept
    assert col.to_dense(np.float32).dtype == np.float32

    obj = np.empty(2, dtype=object)
    obj[0] = SparseVector(2, [0], [9.0])
    obj[1] = DenseVector(np.asarray([7.0, 8.0]))
    t_csr = Table.from_columns(v=col)
    t_obj = Table.from_columns(v=obj)
    both = t_csr.concat(t_obj)
    rev = t_obj.concat(t_csr)
    assert is_csr_column(both.column("v"))
    assert is_csr_column(rev.column("v"))
    assert both.column("v")[3].to_array().tolist() == [9.0, 0.0]
    assert rev.column("v")[0].to_array().tolist() == [9.0, 0.0]
    assert rev.column("v")[2].to_array().tolist() == [0.0, 1.0]


def test_ftrl_sparse_device_path_matches_host(rng, monkeypatch):
    """Large sparse batches (>= the nnz gate) update on DEVICE via the
    segment-sum SPMD program; the result must match the float64 host CSR
    engine within float32 tolerance, with executionPath provenance."""
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    n, d = 600, 40
    x = rng.normal(size=(n, d))
    x[rng.random((n, d)) < 0.5] = 0.0
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    col = _sparse_column_from_dense(x, keep_all=False)
    init = Table.from_columns(coefficient=[DenseVector(np.zeros(d))])

    def fit():
        est = OnlineLogisticRegression(features_col="f", label_col="l",
                                       global_batch_size=200)
        est.set_initial_model_data(init)
        m = est.fit(Table.from_columns(f=col, l=y))
        return est.last_execution_path, m

    monkeypatch.setenv("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ", "1")
    path_dev, m_dev = fit()
    assert path_dev == "device-csr-batches"
    monkeypatch.setenv("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ", str(1 << 60))
    path_host, m_host = fit()
    assert path_host == "host-csr-batches"
    np.testing.assert_allclose(m_dev.coefficients, m_host.coefficients,
                               rtol=1e-3, atol=1e-5)
    assert m_dev.model_version == m_host.model_version
    # versioned history snapshots materialize from device identically
    np.testing.assert_allclose(m_dev.history[-1][1],
                               m_host.history[-1][1], rtol=1e-3,
                               atol=1e-5)


def test_ftrl_sparse_device_weighted_rows(rng, monkeypatch):
    """weightCol flows into the device path's per-coordinate weight sums."""
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    n, d = 300, 12
    x = rng.normal(size=(n, d))
    x[rng.random((n, d)) < 0.6] = 0.0
    y = (rng.random(n) > 0.5).astype(np.float64)
    w = rng.random(n) + 0.5
    col = _sparse_column_from_dense(x, keep_all=False)
    init = Table.from_columns(coefficient=[DenseVector(np.zeros(d))])

    def fit():
        est = OnlineLogisticRegression(features_col="f", label_col="l",
                                       weight_col="w",
                                       global_batch_size=150)
        est.set_initial_model_data(init)
        return est.fit(Table.from_columns(f=col, l=y, w=w))

    monkeypatch.setenv("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ", "1")
    m_dev = fit()
    monkeypatch.setenv("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ", str(1 << 60))
    m_host = fit()
    np.testing.assert_allclose(m_dev.coefficients, m_host.coefficients,
                               rtol=1e-3, atol=1e-5)
