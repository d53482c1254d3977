"""The map-reduce programming layer (parallel/mapreduce.py) and the
cross-replica sharded update (parallel/update_sharding.py).

Pins the layer's contracts: the named primitives match their raw
semantics (including on hybrid meshes and the 1-device degenerate case),
``MapReduceProgram`` runs identically at N=1 and N=8, the
reduce-scatter / owned-slice pairing is exact, and — the acceptance bar
of ISSUE 9 — sharded-update fits (SGD, KMeans, FTRL) are numerically
equivalent to the replicated path at mesh sizes {1, 2, 8}, the sharded
state round-trips through the v2 checkpoint manifest mid-fit, donated
carries are consumed without warnings, and per-replica optimizer-state
bytes shrink 1/N.
"""

import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.parallel import (
    DATA_AXIS,
    create_mesh,
    mapreduce as mr,
    mesh as mesh_mod,
    update_sharding as upd,
)

MESH_SIZES = (1, 2, 8)


@pytest.fixture
def sharding_on(monkeypatch):
    monkeypatch.setenv(upd.ENV, "1")


def submesh(n):
    return create_mesh(devices=jax.devices()[:n])


@pytest.fixture
def use_default_mesh():
    """Set-and-restore seam for tests that fit through default_mesh()."""
    try:
        yield mesh_mod.set_default_mesh
    finally:
        mesh_mod.set_default_mesh(None)


# -- primitives ---------------------------------------------------------------

def test_reduce_scatter_sums_and_slices(mesh8):
    # every shard holds the same (16,) partial; each gets its 8x'd slice
    g = np.arange(16, dtype=np.float32)
    prog = mr.map_shards(lambda a: mr.reduce_scatter(a),
                         mesh8, in_specs=P(), out_specs=P(DATA_AXIS))
    got = np.asarray(prog(g))
    np.testing.assert_allclose(got, 8.0 * g)


def test_reduce_scatter_all_gather_roundtrip_one_device():
    mesh1 = submesh(1)
    g = np.arange(4, dtype=np.float32)
    prog = mr.map_shards(
        lambda a: mr.all_gather(mr.reduce_scatter(a)),
        mesh1, in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(prog(g)), g)


def test_reduce_scatter_hybrid_axes_matches_flat():
    from flink_ml_tpu.parallel import DCN_AXIS, create_hybrid_mesh

    g = np.arange(16, dtype=np.float32)
    flat = mr.map_shards(
        lambda a: mr.all_gather(mr.reduce_scatter(a)),
        create_mesh(), in_specs=P(), out_specs=P())
    hybrid_mesh = create_hybrid_mesh(ici_shape=(4,), dcn_shape=(2,))
    axes = (DCN_AXIS, DATA_AXIS)
    hybrid = mr.map_shards(
        lambda a: mr.all_gather(mr.reduce_scatter(a, axes), axes),
        hybrid_mesh, in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(hybrid(g)), np.asarray(flat(g)))


def test_owned_slice_pairs_with_reduce_scatter(mesh8):
    """The slice order contract: reduce_scatter's shard-i portion must be
    exactly shard i's owned_slice — the pairing the sharded update rests
    on. Checked by reconstructing: gather(scatter(g) - 8*owned(g)) == 0."""
    g = np.arange(16, dtype=np.float32)

    def body(a):
        return mr.all_gather(mr.reduce_scatter(a) - 8.0 * upd.owned_slice(a))

    prog = mr.map_shards(body, mesh8, in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(prog(g)), np.zeros(16))


def test_broadcast_takes_src_shard(mesh8):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    prog = mr.map_shards(lambda a: mr.broadcast(a, src=5),
                         mesh8, in_specs=P(DATA_AXIS, None),
                         out_specs=P(DATA_AXIS, None))
    np.testing.assert_allclose(np.asarray(prog(x)), np.full((8, 1), 5.0))


def test_shard_count_and_index(mesh8):
    prog = mr.map_shards(
        lambda: (jnp.asarray(mr.shard_count()),
                 mr.shard_index()[None]),
        mesh8, in_specs=(), out_specs=(P(), P(DATA_AXIS)))
    count, idx = prog()
    assert int(count) == 8
    np.testing.assert_array_equal(np.asarray(idx), np.arange(8))


def test_padding_helpers():
    assert upd.padded_len(10, 8) == 16
    assert upd.padded_len(16, 8) == 16
    assert upd.padded_len(5, 1) == 5
    x = jnp.ones((3, 2))
    assert upd.pad_leading(x, 5).shape == (5, 2)
    assert float(upd.pad_leading(x, 5)[3:].sum()) == 0.0
    assert upd.pad_leading(x, 3) is x


def test_collective_accounting_records_new_ops(mesh8):
    from flink_ml_tpu.common.metrics import metrics

    def totals():
        snap = metrics.snapshot().get("ml.collective", {})
        return {k: v for k, v in snap.get("counters", {}).items()
                if "psum_scatter" in k}

    before = sum(totals().values())
    # a FRESH body each call → re-traces → trace-time accounting fires
    prog = mr.map_shards(lambda a: mr.reduce_scatter(a + 0.0),
                         mesh8, in_specs=P(), out_specs=P(DATA_AXIS))
    prog(np.arange(16, dtype=np.float32))
    assert sum(totals().values()) > before


# -- MapReduceProgram ---------------------------------------------------------

def _mean_program(mesh):
    prog = mr.MapReduceProgram(mesh)

    def map_fn(xl, wl):
        return {"sx": jnp.sum(xl * wl[:, None], axis=0),
                "sw": jnp.sum(wl)}

    def update_fn(red, xl, wl):
        return red["sx"] / jnp.maximum(red["sw"], 1e-30)

    return prog.build(map_fn, update_fn,
                      in_specs=(prog.data_spec(2), prog.data_spec(1)),
                      out_specs=P())


@pytest.mark.parametrize("n_dev", MESH_SIZES)
def test_program_builder_identical_across_mesh_sizes(rng, n_dev):
    """The composed partition→map→reduce→update step returns the same
    result on a 1-device and an N-device mesh."""
    x = rng.normal(size=(64, 3)).astype(np.float32)
    w = (rng.random(64) + 0.5).astype(np.float32)
    got = np.asarray(_mean_program(submesh(n_dev))(x, w))
    want = (x * w[:, None]).sum(0) / w.sum()
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_program_builder_mixed_reducers(mesh8):
    """Per-leaf reducers: the gradient leaf reduce-scatters while the
    scalar leaf all-reduces — the sharded-update composition."""
    prog = mr.MapReduceProgram(mesh8)

    def map_fn(g, s):
        return {"grad": g, "scalar": s}

    def update_fn(red, g, s):
        return mr.all_gather(red["grad"]), red["scalar"]

    step = prog.build(map_fn, update_fn, in_specs=(P(), P()),
                      out_specs=(P(), P()),
                      reduce={"grad": mr.reduce_scatter,
                              "scalar": mr.reduce_sum})
    g = np.arange(16, dtype=np.float32)
    full, scalar = step(g, np.float32(2.0))
    np.testing.assert_allclose(np.asarray(full), 8.0 * g)
    assert float(scalar) == 16.0


def test_map_shards_donation_consumes_buffer(mesh8):
    """donate_argnums through the instrumented seam: the donated input
    buffer is really consumed (in-place update), with no 'not usable'
    warning."""
    sharding = NamedSharding(mesh8, P(DATA_AXIS))
    z = jax.device_put(np.zeros(16, np.float32), sharding)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        prog = mr.map_shards(lambda a: a + 1.0, mesh8,
                             in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
                             donate_argnums=(0,), name="donate-test")
        out = prog(z)
        jax.block_until_ready(out)
    assert not [w for w in caught if "donat" in str(w.message).lower()]
    assert z.is_deleted()
    np.testing.assert_allclose(np.asarray(out), 1.0)


def test_sharded_apply_matches_replicated_apply(mesh8):
    """The generic sharded_apply: scatter → slice-update → gather equals
    the replicated reduce → full update, with opt-state slices carried
    sharded."""
    d = 16

    def apply_rule(g, p, s):
        return p - 0.5 * g, (None if s is None else s + g * g)

    def replicated(g_local, params, state):
        g = mr.reduce_sum(g_local)
        new_p, new_s = apply_rule(g, params, state)
        return new_p, new_s

    def sharded(g_local, params, state):
        new_p, new_s = upd.sharded_apply(
            DATA_AXIS, g_local, params, state,
            lambda g, p, s: apply_rule(g, p, s))
        return new_p, mr.all_gather(new_s)

    g = np.linspace(-1, 1, d).astype(np.float32)
    p0 = np.ones(d, np.float32)
    s0 = np.full(d, 0.25, np.float32)
    rep = mr.map_shards(replicated, mesh8, in_specs=(P(), P(), P()),
                        out_specs=(P(), P()))
    sh = mr.map_shards(sharded, mesh8,
                       in_specs=(P(), P(), P(DATA_AXIS)),
                       out_specs=(P(), P()))
    s0_dev = jax.device_put(s0, NamedSharding(mesh8, P(DATA_AXIS)))
    p_r, s_r = rep(g, p0, s0)
    p_s, s_s = sh(g, p0, s0_dev)
    np.testing.assert_allclose(np.asarray(p_s), np.asarray(p_r),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_r),
                               rtol=1e-5)


# -- sharded-vs-replicated fit parity (the ISSUE 9 acceptance matrix) --------

def _sgd_fit(mesh, rng, **kw):
    from flink_ml_tpu.ops.losses import BinaryLogisticLoss
    from flink_ml_tpu.ops.optimizer import SGD, SGDParams

    x = rng.normal(size=(400, 10))
    y = (x @ rng.normal(size=10) > 0).astype(np.float64)
    prm = SGDParams(learning_rate=0.1, global_batch_size=80, max_iter=5,
                    tol=0.0, reg=0.02, elastic_net=0.4)
    coeffs, loss = SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(10),
                                     x, y, mesh=mesh, **kw)
    return coeffs, loss


@pytest.mark.parametrize("n_dev", MESH_SIZES)
def test_sgd_parity_sharded_vs_replicated(monkeypatch, rng, n_dev):
    mesh = submesh(n_dev)
    monkeypatch.delenv(upd.ENV, raising=False)
    c_rep, l_rep = _sgd_fit(mesh, np.random.default_rng(0))
    monkeypatch.setenv(upd.ENV, "1")
    c_sh, l_sh = _sgd_fit(mesh, np.random.default_rng(0))
    assert c_sh.shape == c_rep.shape  # padding trimmed
    np.testing.assert_allclose(c_sh, c_rep, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(l_sh, l_rep, rtol=1e-5)


@pytest.mark.parametrize("n_dev", MESH_SIZES)
def test_kmeans_parity_sharded_vs_replicated(monkeypatch, rng, n_dev,
                                             use_default_mesh):
    from flink_ml_tpu.models.clustering import KMeans

    x = rng.normal(size=(240, 6)).astype(np.float32)
    t = Table.from_columns(features=x)
    use_default_mesh(submesh(n_dev))

    def fit():
        m = KMeans(k=4, seed=7, max_iter=6).fit(t)
        return m.centroids, m.weights

    monkeypatch.delenv(upd.ENV, raising=False)
    c_rep, w_rep = fit()
    monkeypatch.setenv(upd.ENV, "1")
    c_sh, w_sh = fit()
    np.testing.assert_allclose(c_sh, c_rep, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(w_sh, w_rep)


def _ftrl_fit(rng, d=6, batches=6, bs=64):
    from flink_ml_tpu.iteration.streaming import StreamTable
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    x = rng.normal(size=(batches * bs, d)).astype(np.float32)
    y = (x @ rng.normal(size=d) > 0).astype(float)
    t = Table.from_columns(features=x, label=y)
    est = OnlineLogisticRegression(global_batch_size=bs, reg=0.01,
                                   elastic_net=0.3)
    est.set_initial_model_data(Table.from_columns(
        coefficient=np.zeros((1, d)), modelVersion=np.asarray([0])))
    return est.fit(StreamTable.from_table(t, bs))


@pytest.mark.parametrize("n_dev", MESH_SIZES)
def test_ftrl_parity_sharded_vs_replicated(monkeypatch, n_dev,
                                           use_default_mesh):
    use_default_mesh(submesh(n_dev))
    monkeypatch.delenv(upd.ENV, raising=False)
    m_rep = _ftrl_fit(np.random.default_rng(3))
    monkeypatch.setenv(upd.ENV, "1")
    m_sh = _ftrl_fit(np.random.default_rng(3))
    np.testing.assert_allclose(m_sh.coefficients, m_rep.coefficients,
                               rtol=1e-5, atol=1e-7)
    assert m_sh.model_version == m_rep.model_version
    # history snapshots carry the TRIMMED (d,) shape in both modes
    assert all(c.shape == m_rep.history[0][1].shape
               for _, c in m_sh.history)


def test_ftrl_sparse_device_parity(monkeypatch, rng):
    """The device CSR path under sharding: per-coordinate grad/weight
    sums reduce-scattered, z/n slices sharded."""
    from flink_ml_tpu.iteration.streaming import StreamTable
    from flink_ml_tpu.linalg.vectors import SparseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    monkeypatch.setenv("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ", "1")
    n, d = 300, 7
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    sv = np.empty(n, object)
    for i in range(n):
        idx = np.nonzero(rng.random(d) < 0.6)[0]
        sv[i] = SparseVector(d, idx, x[i, idx])
    t = Table.from_columns(features=sv, label=y)

    def fit():
        est = OnlineLogisticRegression(global_batch_size=100)
        est.set_initial_model_data(
            Table.from_columns(coefficient=np.zeros((1, d))))
        m = est.fit(StreamTable.from_table(t, 100))
        assert est.last_execution_path == "device-csr-batches"
        return m

    monkeypatch.delenv(upd.ENV, raising=False)
    m_rep = fit()
    monkeypatch.setenv(upd.ENV, "1")
    m_sh = fit()
    np.testing.assert_allclose(m_sh.coefficients, m_rep.coefficients,
                               rtol=1e-5, atol=1e-7)


# -- restart-from-checkpoint mid-fit (sharded state through v2 manifests) ----

def test_sgd_segmented_restart_resumes_sharded_state(monkeypatch, rng,
                                                     tmp_path):
    """A sharded segmented fit killed at a segment boundary resumes from
    the v2-manifest checkpoint — the padded, sharded carry round-trips —
    and finishes bit-identical to the uninterrupted sharded fit."""
    from flink_ml_tpu.iteration.checkpoint import CheckpointManager
    from flink_ml_tpu.iteration.iteration import IterationConfig
    from flink_ml_tpu.resilience import InjectedFault, faults

    monkeypatch.setenv(upd.ENV, "1")
    mesh = submesh(8)
    data_rng = np.random.default_rng(4)
    clean, _ = _sgd_fit(mesh, np.random.default_rng(4))

    mgr = CheckpointManager(str(tmp_path / "ck"))
    cfg = IterationConfig(mode="device", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    with faults.chaos(at={"epoch-boundary": [2]}):
        with pytest.raises(InjectedFault):
            _sgd_fit(mesh, np.random.default_rng(4), config=cfg)
    assert mgr.list_checkpoints()  # a mid-fit snapshot survived the crash

    resumed, _ = _sgd_fit(mesh, np.random.default_rng(4), config=cfg)
    np.testing.assert_allclose(resumed, clean, rtol=1e-6, atol=1e-12)
    assert not mgr.list_checkpoints()  # success cleared them


def test_ftrl_checkpoint_resume_across_sharding_modes(monkeypatch, rng,
                                                      tmp_path,
                                                      use_default_mesh):
    """The host checkpoint view is the trimmed (d,) state in BOTH modes,
    so a replicated fit's mid-stream snapshot resumes under the sharded
    update (and the result matches the uninterrupted replicated fit)."""
    from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
    from flink_ml_tpu.iteration.iteration import IterationListener
    from flink_ml_tpu.iteration.streaming import StreamTable
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    use_default_mesh(submesh(8))
    x = np.random.default_rng(5).normal(size=(600, 6))
    y = (x @ [1, -1, 2, 0.5, -0.3, 1] > 0).astype(float)
    t = Table.from_columns(features=x, label=y)
    init = Table.from_columns(coefficient=np.zeros((1, 6)),
                              modelVersion=np.asarray([0]))

    def est(cfg=None, listeners=()):
        e = OnlineLogisticRegression(global_batch_size=100)
        e.set_initial_model_data(init)
        if cfg is not None:
            e.set_iteration_config(cfg, listeners=listeners)
        return e

    monkeypatch.delenv(upd.ENV, raising=False)
    expected = est().fit(StreamTable.from_table(t, 100))

    class DieAfter(IterationListener):
        def on_epoch_watermark_incremented(self, batch_idx, state):
            if batch_idx + 1 == 3:
                raise RuntimeError("injected crash")

    mgr = CheckpointManager(str(tmp_path / "ck"))
    cfg = IterationConfig(mode="host", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    with pytest.raises(RuntimeError):
        est(cfg, [DieAfter()]).fit(StreamTable.from_table(t, 100))
    assert mgr.list_checkpoints()

    # resume the tail (batches 3..6) with the SHARDED update armed: the
    # snapshot restores into padded sharded device state transparently
    monkeypatch.setenv(upd.ENV, "1")
    tail = t.take(np.arange(200, 600))
    resumed = est(cfg).fit(StreamTable.from_table(tail, 100))
    assert resumed.model_version == expected.model_version
    np.testing.assert_allclose(resumed.coefficients,
                               expected.coefficients,
                               rtol=1e-5, atol=1e-7)


def test_checkpoint_manager_roundtrips_sharded_carry(tmp_path):
    """CheckpointManager.save/restore on a carry holding dim-0-sharded
    optimizer-state leaves: values AND shardings survive the v2
    manifest."""
    from flink_ml_tpu.iteration.checkpoint import CheckpointManager

    mesh = submesh(8)
    w = jax.device_put(np.arange(16, dtype=np.float32),
                       NamedSharding(mesh, P()))
    z, n = upd.place_opt_state(
        mesh, (np.linspace(0, 1, 16, dtype=np.float32),
               np.full(16, 2.0, np.float32)))
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save((w, z, n), epoch=3)

    template = (jax.device_put(np.zeros(16, np.float32),
                               NamedSharding(mesh, P())),
                *upd.place_opt_state(mesh, (np.zeros(16, np.float32),
                                            np.zeros(16, np.float32))))
    (w2, z2, n2), epoch = mgr.restore(template)
    assert epoch == 3
    np.testing.assert_allclose(np.asarray(z2), np.asarray(z))
    np.testing.assert_allclose(np.asarray(n2), np.asarray(n))
    assert z2.sharding == template[1].sharding
    assert len(z2.addressable_shards) == 8


# -- accounting & provenance --------------------------------------------------

def test_state_bytes_accounting(monkeypatch, use_default_mesh):
    use_default_mesh(submesh(8))
    monkeypatch.setenv(upd.ENV, "1")
    _ftrl_fit(np.random.default_rng(6), d=10)
    # z + n at d=10 padded to 16: 2*16*4 bytes over 8 replicas
    assert upd.last_state_bytes("OnlineLogisticRegression") == \
        2 * 16 * 4 // 8
    monkeypatch.delenv(upd.ENV)
    _ftrl_fit(np.random.default_rng(6), d=10)
    assert upd.last_state_bytes("OnlineLogisticRegression") == 2 * 10 * 4


def test_benchmark_provenance_fields(monkeypatch, use_default_mesh):
    from flink_ml_tpu.benchmark.runner import _mesh_provenance

    use_default_mesh(submesh(8))
    monkeypatch.setenv(upd.ENV, "1")
    _ftrl_fit(np.random.default_rng(7))
    prov = _mesh_provenance()
    assert prov["updateSharding"] is True
    assert prov["deviceCount"] == 8
    assert prov["optStateBytesPerReplica"] == upd.last_state_bytes()


def test_sharded_fits_run_without_donation_warnings(monkeypatch,
                                                    use_default_mesh):
    """The donation satellite's bar: sharded SGD + FTRL fits must not
    emit a single 'donated buffers were not usable' warning."""
    use_default_mesh(submesh(8))
    monkeypatch.setenv(upd.ENV, "1")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _sgd_fit(submesh(8), np.random.default_rng(8))
        _ftrl_fit(np.random.default_rng(8))
    assert not [w for w in caught
                if "donat" in str(w.message).lower()], \
        [str(w.message) for w in caught]
