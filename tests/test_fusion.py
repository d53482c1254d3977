"""Segment-boundary fusion tests (ISSUE 11 satellite): the fused
one-transfer-per-boundary path must be BIT-IDENTICAL to the pre-fusion
scalar-by-scalar path — plain fits, checkpointed segment fits, and
chaos mid-fit restarts — for SGD segment mode and KMeans segment mode,
at mesh sizes 1 and 8. Fusion only changes how the already-computed
boundary scalars reach the host, never what the programs compute, so
every comparison here is exact (assert_array_equal, no tolerance)."""

import numpy as np
import pytest

import jax

from flink_ml_tpu.common.metrics import metrics
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.iteration.iteration import (
    read_boundary,
    segment_fusion_enabled,
)
from flink_ml_tpu.models.clustering import kmeans as km_mod
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.ops.losses import BinaryLogisticLoss
from flink_ml_tpu.ops.optimizer import SGD, SGDParams
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.resilience import faults
from flink_ml_tpu.resilience.policy import InjectedFault

FUSION_ENV = "FLINK_ML_TPU_SEGMENT_FUSION"


def _mesh_of(n_dev):
    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} devices")
    return create_mesh((n_dev,), devices=jax.devices()[:n_dev])


def _sgd_data(rng, n=640, d=6):
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    return x, y


def _counter(name):
    snap = metrics.snapshot().get("ml.iteration", {}).get("counters", {})
    return int(snap.get(name, 0))


def _boundary_counts():
    return _counter("boundaryFetches"), _counter("boundaries")


def _boundary_waits():
    return _counter("boundaryWaits")


def test_fusion_env_gate(monkeypatch):
    monkeypatch.delenv(FUSION_ENV, raising=False)
    assert segment_fusion_enabled()
    monkeypatch.setenv(FUSION_ENV, "0")
    assert not segment_fusion_enabled()
    monkeypatch.setenv(FUSION_ENV, "1")
    assert segment_fusion_enabled()


def test_read_boundary_counts_transfers():
    """The fused form costs ONE counted transfer; the pre-fusion tuple
    form counts one per scalar."""
    import jax.numpy as jnp

    f0, _ = _boundary_counts()
    vals = read_boundary(jnp.asarray([3, 1]))
    assert [int(v) for v in vals] == [3, 1]
    f1, _ = _boundary_counts()
    assert f1 - f0 == 1
    vals = read_boundary((jnp.int32(4), jnp.asarray(False),
                          jnp.asarray(True)))
    assert int(vals[0]) == 4 and not bool(vals[1]) and bool(vals[2])
    f2, _ = _boundary_counts()
    assert f2 - f1 == 3


@pytest.mark.parametrize("n_dev", [1, 8])
def test_sgd_segment_fusion_bit_identical(monkeypatch, rng, n_dev,
                                          tmp_path):
    """Checkpointed SGD segment fits: fusion on vs the pre-fusion path
    produce byte-identical coefficients and loss."""
    mesh = _mesh_of(n_dev)
    x, y = _sgd_data(rng)
    prm = SGDParams(learning_rate=0.05, global_batch_size=64,
                    max_iter=9, tol=0.0, reg=0.01, elastic_net=0.3)

    def fit(fused, sub):
        monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
        cfg = IterationConfig(
            mode="device", checkpoint_interval=3,
            checkpoint_manager=CheckpointManager(str(tmp_path / sub)))
        return SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(6), x, y,
                                 mesh=mesh, config=cfg)

    c_fused, l_fused = fit(True, f"f{n_dev}")
    c_plain, l_plain = fit(False, f"p{n_dev}")
    np.testing.assert_array_equal(c_fused, c_plain)
    assert l_fused == l_plain


@pytest.mark.parametrize("n_dev", [1, 8])
def test_kmeans_segment_fusion_bit_identical(monkeypatch, rng, n_dev,
                                             tmp_path):
    """Checkpointed KMeans segment fits (the generic segmented device
    loop): fusion on vs off — identical centroids and weights, and both
    identical to the plain uncheckpointed fit (a checkpoint must never
    change the result)."""
    mesh = _mesh_of(n_dev)
    monkeypatch.setattr(km_mod, "default_mesh", lambda: mesh)
    x = rng.normal(size=(240, 4)).astype(np.float32)
    table = Table.from_columns(features=as_dense_vector_column(x))

    def fit(fused, sub=None):
        monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
        est = KMeans(k=3, seed=7, max_iter=8)
        if sub is not None:
            est.set_iteration_config(IterationConfig(
                mode="device", checkpoint_interval=2,
                checkpoint_manager=CheckpointManager(
                    str(tmp_path / sub))))
        return est.fit(table)

    m_fused = fit(True, f"f{n_dev}")
    m_plain = fit(False, f"p{n_dev}")
    m_device = fit(True)
    np.testing.assert_array_equal(m_fused.centroids, m_plain.centroids)
    np.testing.assert_array_equal(m_fused.weights, m_plain.weights)
    np.testing.assert_array_equal(m_fused.centroids, m_device.centroids)


def test_fused_boundary_is_one_transfer(monkeypatch, rng, tmp_path):
    """The acceptance bar: segment-mode device→host transfers per
    boundary == 1 fused, > 1 on the pre-fusion path. The fitted state's
    two leaves (coefficients, loss) are counted too, once a fit and
    apart from the boundaries': they cross under one wait of their own,
    so a fit waits once a boundary and once more."""
    x, y = _sgd_data(rng)
    prm = SGDParams(learning_rate=0.05, global_batch_size=64,
                    max_iter=8, tol=0.0)
    final_leaves = 2

    def fetches_per_boundary(fused, sub):
        monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
        cfg = IterationConfig(
            mode="device", checkpoint_interval=2,
            checkpoint_manager=CheckpointManager(str(tmp_path / sub)))
        f0, b0 = _boundary_counts()
        w0 = _boundary_waits()
        SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(6), x, y,
                          config=cfg)
        f1, b1 = _boundary_counts()
        assert b1 - b0 == 4  # 8 rounds / K=2
        assert _boundary_waits() - w0 == 4 + 1
        return (f1 - f0 - final_leaves) / (b1 - b0)

    assert fetches_per_boundary(True, "fused") == 1.0
    assert fetches_per_boundary(False, "plain") == 2.0


@pytest.mark.parametrize("fused, leaves", [(True, 3), (False, 4)])
def test_a_plain_fit_waits_for_its_results_once(monkeypatch, rng, fused,
                                                leaves):
    """No checkpoint, no listener: the boundary nobody needs before the
    final state crosses with it — bundle (or epoch and stop),
    coefficients and loss under ONE wait."""
    monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
    x, y = _sgd_data(rng)
    prm = SGDParams(learning_rate=0.05, global_batch_size=64,
                    max_iter=8, tol=0.0)
    (f0, b0), w0 = _boundary_counts(), _boundary_waits()
    sgd = SGD(prm)
    sgd.optimize(BinaryLogisticLoss(), np.zeros(6), x, y)
    assert sgd.last_execution_path == "xla-while"
    (f1, b1), w1 = _boundary_counts(), _boundary_waits()
    assert (f1 - f0, b1 - b0, w1 - w0) == (leaves, 0, 1)


def test_sgd_fusion_chaos_restart_parity(monkeypatch, rng, tmp_path):
    """Chaos mid-fit restart under fusion: a fit killed at a segment
    boundary resumes from its checkpoint to the EXACT uninterrupted
    trajectory, fused and unfused alike (the PR 2 recovery bar composed
    with the fused boundary)."""
    x, y = _sgd_data(rng)
    prm = SGDParams(learning_rate=0.05, global_batch_size=64,
                    max_iter=12, tol=0.0)

    def fit_with(fused, sub, chaos_at=None):
        monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
        mgr = CheckpointManager(str(tmp_path / sub))
        cfg = IterationConfig(mode="device", checkpoint_interval=3,
                              checkpoint_manager=mgr)

        def run():
            return SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(6),
                                     x, y, config=cfg)

        if chaos_at is None:
            with faults.suppressed():
                return run()
        with faults.chaos(at={"epoch-boundary": chaos_at}):
            with pytest.raises(InjectedFault):
                run()
            return run()  # restart: restores from the checkpoint

    clean = fit_with(True, "clean")
    fused = fit_with(True, "chaos-fused", chaos_at=[1])
    plain = fit_with(False, "chaos-plain", chaos_at=[1])
    np.testing.assert_array_equal(fused[0], clean[0])
    np.testing.assert_array_equal(plain[0], clean[0])
    assert fused[1] == clean[1] == plain[1]


def test_kmeans_fusion_chaos_restart_parity(monkeypatch, rng, tmp_path):
    """KMeans segment mode under chaos: kill at a segment boundary,
    restart, byte-identical model — with fusion on and off."""
    x = rng.normal(size=(240, 4)).astype(np.float32)
    table = Table.from_columns(features=as_dense_vector_column(x))

    def fit_with(fused, sub, chaos_at=None):
        monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
        mgr = CheckpointManager(str(tmp_path / sub))
        est = KMeans(k=3, seed=7, max_iter=8).set_iteration_config(
            IterationConfig(mode="device", checkpoint_interval=2,
                            checkpoint_manager=mgr))
        if chaos_at is None:
            with faults.suppressed():
                return est.fit(table)
        with faults.chaos(at={"epoch-boundary": chaos_at}):
            with pytest.raises(InjectedFault):
                est.fit(table)
            return est.fit(table)

    clean = fit_with(True, "clean")
    fused = fit_with(True, "chaos-fused", chaos_at=[1])
    plain = fit_with(False, "chaos-plain", chaos_at=[1])
    np.testing.assert_array_equal(fused.centroids, clean.centroids)
    np.testing.assert_array_equal(plain.centroids, clean.centroids)
    np.testing.assert_array_equal(fused.weights, clean.weights)


def test_sgd_fusion_with_health_sentinel(monkeypatch, rng, tmp_path):
    """With health telemetry armed the sentinel rides the fused bundle:
    results stay identical to the unfused health path, and a diverging
    fit still fails fast at a segment boundary."""
    from flink_ml_tpu.resilience import NonFiniteState

    monkeypatch.setenv("FLINK_ML_TPU_HEALTH", "1")
    x, y = _sgd_data(rng)
    prm = SGDParams(learning_rate=0.05, global_batch_size=64,
                    max_iter=9, tol=0.0)

    def fit(fused, sub):
        monkeypatch.setenv(FUSION_ENV, "1" if fused else "0")
        cfg = IterationConfig(
            mode="device", checkpoint_interval=3,
            checkpoint_manager=CheckpointManager(str(tmp_path / sub)))
        return SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(6), x, y,
                                 config=cfg)

    cf, lf = fit(True, "hf")
    cp, lp = fit(False, "hp")
    np.testing.assert_array_equal(cf, cp)
    assert lf == lp

    monkeypatch.setenv(FUSION_ENV, "1")
    from flink_ml_tpu.ops.losses import LeastSquareLoss

    bad = SGDParams(learning_rate=1e12, global_batch_size=64,
                    max_iter=9, tol=0.0)
    cfg = IterationConfig(
        mode="device", checkpoint_interval=3,
        checkpoint_manager=CheckpointManager(str(tmp_path / "nan")))
    with pytest.raises(NonFiniteState):
        SGD(bad).optimize(LeastSquareLoss(), np.zeros(6), x, y,
                          config=cfg)


def test_final_boundary_snapshot_skipped(monkeypatch, rng, tmp_path):
    """The completing run's final-boundary snapshot (which clear() would
    delete two lines later) is skipped — but every interior boundary
    still checkpoints, and a mid-fit kill still restores."""
    from flink_ml_tpu.iteration.iteration import run_segmented

    saved = []

    class SpyManager(CheckpointManager):
        def save(self, carry, epoch):
            saved.append(epoch)
            return super().save(carry, epoch)

    def run_segment(carry, epoch0, limit):
        for e in range(epoch0, limit):
            carry = carry * 1.5 + e
        return carry, limit, False

    mgr = SpyManager(str(tmp_path / "ckpt"))
    with faults.suppressed():
        run_segmented(run_segment, np.float64(1.0), 12, 4, mgr)
    # boundaries at 4, 8, 12 — the final one (12) saves nothing
    assert saved == [4, 8]
    assert mgr.list_checkpoints() == []  # completed run cleared
