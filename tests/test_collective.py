"""Collective tests on the 8-device CPU mesh (ref: AllReduceImplTest.java,
BroadcastUtilsTest.java run on MiniCluster)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.parallel import (
    DATA_AXIS,
    all_gather,
    all_reduce_sum,
    broadcast_from,
    create_mesh,
    replicate,
    shard_batch,
    termination_vote,
)


def shard_map_over(mesh, fn, in_specs, out_specs):
    from flink_ml_tpu.parallel.shardmap import shard_map

    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


def test_all_reduce_sum(mesh8, rng):
    x = rng.normal(size=(8, 16)).astype(np.float32)
    fn = shard_map_over(mesh8, lambda a: all_reduce_sum(a), P(DATA_AXIS, None),
                        P(None, None))
    # each shard holds one row; psum over axis = the column sums
    got = np.asarray(fn(x))
    np.testing.assert_allclose(got, x.sum(axis=0, keepdims=True), rtol=1e-5)


def test_all_gather(mesh8, rng):
    x = rng.normal(size=(8, 4)).astype(np.float32)
    fn = shard_map_over(mesh8, lambda a: all_gather(a), P(DATA_AXIS, None),
                        P(None, None))
    got = np.asarray(fn(x))
    np.testing.assert_allclose(got, x, rtol=1e-6)


def test_broadcast_from(mesh8):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    fn = shard_map_over(mesh8, lambda a: broadcast_from(a, src=3),
                        P(DATA_AXIS, None), P(DATA_AXIS, None))
    got = np.asarray(fn(x))
    np.testing.assert_allclose(got, np.full((8, 1), 3.0))


def test_termination_vote(mesh8):
    counts = np.zeros((8, 1), dtype=np.int32)
    fn = shard_map_over(mesh8, lambda c: termination_vote(c),
                        P(DATA_AXIS, None), P(None))
    assert bool(np.asarray(fn(counts)).all())
    counts[5] = 1
    assert not bool(np.asarray(fn(counts)).any())


def test_shard_batch_pads(mesh8):
    arr = np.ones((13, 4), dtype=np.float32)
    device_arr, n = shard_batch(mesh8, arr)
    assert n == 13
    assert device_arr.shape == (16, 4)  # padded to multiple of 8
    assert np.asarray(device_arr).sum() == 13 * 4  # padding is zeros
    # actually sharded over the data axis
    assert device_arr.sharding.spec == P(DATA_AXIS, None)


def test_replicate(mesh8):
    tree = {"w": np.ones((4,), np.float32), "b": np.float32(2.0)}
    rep = replicate(mesh8, tree)
    assert rep["w"].sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(rep["w"]), 1.0)


# -- hybrid multi-slice mesh (DCN axis outermost) ---------------------------

def test_hybrid_mesh_layout_and_hierarchical_psum():
    from flink_ml_tpu.parallel import DCN_AXIS, create_hybrid_mesh

    mesh = create_hybrid_mesh(ici_shape=(4,), dcn_shape=(2,))
    assert mesh.axis_names == (DCN_AXIS, DATA_AXIS)
    assert mesh.shape[DCN_AXIS] == 2 and mesh.shape[DATA_AXIS] == 4

    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    # global hierarchical all-reduce over both axes
    fn = shard_map_over(
        mesh, lambda a: all_reduce_sum(a, (DCN_AXIS, DATA_AXIS)),
        P((DCN_AXIS, DATA_AXIS), None), P(None, None))
    np.testing.assert_allclose(np.asarray(fn(x)), [[28.0]])
    # in-slice-only reduce: each dcn group sums its own 4 shards
    fn_ici = shard_map_over(
        mesh, lambda a: all_reduce_sum(a, DATA_AXIS),
        P((DCN_AXIS, DATA_AXIS), None), P(DCN_AXIS, None))
    np.testing.assert_allclose(np.asarray(fn_ici(x)), [[6.0], [22.0]])


def test_shard_batch_over_hybrid_axes():
    from flink_ml_tpu.parallel import DCN_AXIS, create_hybrid_mesh

    mesh = create_hybrid_mesh(ici_shape=(4,), dcn_shape=(2,))
    arr = np.ones((10, 3), np.float32)
    dev, n = shard_batch(mesh, arr, axis_name=(DCN_AXIS, DATA_AXIS))
    assert n == 10
    assert dev.shape == (16, 3)  # padded to a multiple of 8
    assert dev.sharding.spec == P((DCN_AXIS, DATA_AXIS), None)


def test_fit_on_hybrid_mesh():
    """A full LogisticRegression fit must produce identical coefficients on
    a flat 8-way data mesh and a (2, 4) dcn x data hybrid mesh."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.parallel import create_hybrid_mesh, mesh as mesh_mod

    rng = np.random.default_rng(3)
    x = rng.normal(size=(200, 6)).astype(np.float32)
    y = (x @ rng.normal(size=6) > 0).astype(np.float32)
    t = Table.from_columns(features=x, label=y)

    def fit():
        return LogisticRegression(
            max_iter=5, global_batch_size=100).fit(t).coefficients

    flat = fit()
    mesh_mod.set_default_mesh(create_hybrid_mesh(ici_shape=(4,),
                                                 dcn_shape=(2,)))
    try:
        hybrid = fit()
    finally:
        mesh_mod.set_default_mesh(None)
    np.testing.assert_allclose(hybrid, flat, rtol=1e-6)


def test_create_mesh_raises_when_backend_init_raises(monkeypatch):
    """A backend that fails to initialize makes jax.devices() RAISE; mesh
    construction must propagate that — never rebuild the mesh on host
    devices, which would let a run without its accelerator look like
    success."""
    from flink_ml_tpu.parallel import mesh as mesh_mod

    def dead_devices(*args, **kwargs):
        raise RuntimeError("Unable to initialize backend 'tpu': UNAVAILABLE")

    monkeypatch.setattr(jax, "devices", dead_devices)
    platforms_before = jax.config.jax_platforms
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        mesh_mod.create_mesh()
    assert jax.config.jax_platforms == platforms_before  # no CPU pin


def test_init_distributed_single_process_noop():
    from flink_ml_tpu.parallel import init_distributed

    assert init_distributed(num_processes=1) is False


def test_fit_on_tensor_parallel_mesh():
    """LogisticRegression on a (data=2, model=4) mesh: coefficients sharded
    over the model axis must reproduce the flat data-parallel result, and a
    feature dim that doesn't divide the model axis must pad transparently."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.parallel import MODEL_AXIS, mesh as mesh_mod

    rng = np.random.default_rng(5)
    x = rng.normal(size=(128, 6)).astype(np.float32)  # 6 % 4 != 0 → pads
    y = (x @ rng.normal(size=6) > 0).astype(np.float32)
    t = Table.from_columns(features=x, label=y)

    def fit():
        return LogisticRegression(
            max_iter=6, global_batch_size=64).fit(t).coefficients

    # the 2-way flat data mesh is the numerics oracle: the TP mesh has the
    # same data parallelism (2) and only adds the model-axis split
    mesh_mod.set_default_mesh(mesh_mod.create_mesh(
        (2,), devices=jax.devices()[:2]))
    try:
        flat = fit()
    finally:
        mesh_mod.set_default_mesh(None)

    mesh_mod.set_default_mesh(
        mesh_mod.create_mesh((2, 4), (DATA_AXIS, MODEL_AXIS)))
    try:
        tp = fit()
    finally:
        mesh_mod.set_default_mesh(None)
    assert tp.shape == (6,)
    np.testing.assert_allclose(tp, flat, rtol=1e-5)
