"""KMeans tests (ref: clustering/KMeansTest.java)."""

import numpy as np
import pytest

from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.models.clustering import KMeans, KMeansModel


def make_blobs(rng, centers, n_per=100, scale=0.1):
    pts, labels = [], []
    for i, c in enumerate(centers):
        pts.append(rng.normal(scale=scale, size=(n_per, len(c))) + c)
        labels += [i] * n_per
    x = np.concatenate(pts).astype(np.float32)
    perm = rng.permutation(len(x))
    return x[perm], np.asarray(labels)[perm]


def test_kmeans_default_params():
    km = KMeans()
    assert km.k == 2
    assert km.max_iter == 20
    assert km.distance_measure == "euclidean"
    assert km.init_mode == "random"
    assert km.features_col == "features"
    assert km.prediction_col == "prediction"


def test_kmeans_fit_predict(rng):
    centers = np.array([[0.0, 0.0], [5.0, 5.0], [-5.0, 5.0]])
    x, true_labels = make_blobs(rng, centers)
    table = Table.from_columns(features=as_dense_vector_column(x))
    model = KMeans(k=3, max_iter=30, seed=7).fit(table)
    # learned centroids close to true centers (in some order)
    got = np.asarray(sorted(model.centroids.tolist()))
    want = np.asarray(sorted(centers.tolist()))
    np.testing.assert_allclose(got, want, atol=0.2)
    # weights = cluster sizes
    np.testing.assert_allclose(sorted(model.weights), [100, 100, 100])
    # predictions perfectly separate the blobs
    out = model.transform(table)[0]
    pred = out["prediction"]
    for i in range(3):
        assert len(np.unique(pred[true_labels == i])) == 1


def test_kmeans_matches_sklearn_inertia(rng):
    from sklearn.cluster import KMeans as SkKMeans
    x, _ = make_blobs(rng, np.array([[0, 0], [4, 0], [0, 4], [4, 4]]),
                      n_per=50, scale=0.5)
    table = Table.from_columns(features=as_dense_vector_column(x))

    def inertia(centroids):
        d = ((x[:, None, :] - centroids[None]) ** 2).sum(-1)
        return d.min(1).sum()

    # the reference algorithm is single-random-init Lloyd's, which can land
    # in a local optimum; emulate sklearn's n_init restarts across seeds
    best = min((KMeans(k=4, max_iter=50, seed=s).fit(table)
                for s in range(5)),
               key=lambda m: inertia(m.centroids))
    sk = SkKMeans(n_clusters=4, n_init=10, random_state=0).fit(x)
    assert inertia(best.centroids) <= 1.05 * inertia(sk.cluster_centers_)


def test_kmeans_save_load(rng, tmp_path):
    x, _ = make_blobs(rng, np.array([[0.0, 0.0], [8.0, 8.0]]), n_per=30)
    table = Table.from_columns(features=as_dense_vector_column(x))
    model = KMeans(k=2, seed=1).fit(table)
    model.save(str(tmp_path / "km"))
    reloaded = KMeansModel.load(str(tmp_path / "km"))
    np.testing.assert_array_equal(reloaded.centroids, model.centroids)
    p1 = model.transform(table)[0]["prediction"]
    p2 = reloaded.transform(table)[0]["prediction"]
    np.testing.assert_array_equal(p1, p2)


def test_kmeans_model_data_round_trip(rng):
    x, _ = make_blobs(rng, np.array([[0.0, 0.0], [8.0, 8.0]]), n_per=30)
    table = Table.from_columns(features=as_dense_vector_column(x))
    model = KMeans(k=2, seed=1).fit(table)
    (md,) = model.get_model_data()
    assert set(md.column_names) == {"centroid", "weight"}
    fresh = KMeansModel().set_model_data(md)
    np.testing.assert_allclose(fresh.centroids, model.centroids)
    np.testing.assert_allclose(fresh.weights, model.weights)


def test_kmeans_cosine_distance(rng):
    # two directions, different magnitudes — cosine clusters by angle
    a = rng.uniform(1, 10, size=(50, 1)) * np.array([[1.0, 0.02]])
    b = rng.uniform(1, 10, size=(50, 1)) * np.array([[0.02, 1.0]])
    x = np.concatenate([a, b]).astype(np.float32)
    table = Table.from_columns(features=as_dense_vector_column(x))
    model = KMeans(k=2, distance_measure="cosine", seed=3,
                   max_iter=20).fit(table)
    pred = model.transform(table)[0]["prediction"]
    assert len(np.unique(pred[:50])) == 1
    assert len(np.unique(pred[50:])) == 1
    assert pred[0] != pred[-1]


def test_kmeans_k_greater_than_points():
    x = np.array([[0.0, 0.0], [1.0, 1.0], [2.0, 2.0]], np.float32)
    table = Table.from_columns(features=as_dense_vector_column(x))
    model = KMeans(k=2, seed=0, max_iter=5).fit(table)
    assert model.centroids.shape == (2, 2)


def test_pipeline_with_kmeans(rng, tmp_path):
    """Quickstart parity (ref: KMeansExample.java): pipeline fit→transform."""
    from flink_ml_tpu.api import Pipeline, PipelineModel
    x, _ = make_blobs(rng, np.array([[0.0, 0.0], [9.0, 9.0]]), n_per=20)
    table = Table.from_columns(features=as_dense_vector_column(x))
    pipe = Pipeline([KMeans(k=2, seed=5)])
    pm = pipe.fit(table)
    out = pm.transform(table)[0]
    assert "prediction" in out.column_names
    pm.save(str(tmp_path / "pipe"))
    out2 = PipelineModel.load(str(tmp_path / "pipe")).transform(table)[0]
    np.testing.assert_array_equal(out["prediction"], out2["prediction"])


def test_unrolled_lloyd_matches_while_program(rng):
    """The unrolled fit program (static round count) must equal the
    while-loop program — same round_step, same order. The (c0, counts0)
    carry is donated, so every call gets fresh carry buffers."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.clustering.kmeans import _build_lloyd_program
    from flink_ml_tpu.parallel.collective import ensure_on_mesh
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    mesh = default_mesh()
    x = rng.random((500, 6)).astype(np.float32)
    xs, _ = ensure_on_mesh(mesh, x, data_axes(mesh), jnp.float32)

    def run(measure, unroll):
        prog = _build_lloyd_program(mesh, measure, 5, unroll=unroll)
        c, cnt = prog(xs, jnp.int32(500), jnp.asarray(x[:4]),
                      jnp.zeros((4,), jnp.float32))
        return np.asarray(c), np.asarray(cnt)

    for measure in ("euclidean", "manhattan", "cosine"):
        ca, cnta = run(measure, True)
        cb, cntb = run(measure, False)
        np.testing.assert_allclose(ca, cb, rtol=1e-6, atol=1e-12)
        np.testing.assert_allclose(cnta, cntb, rtol=1e-6, atol=1e-12)


def test_lloyd_program_donates_carry(rng):
    """The donation satellite's bar for KMeans: the fit program's
    (c0, counts0) carry must be CONSUMED (in-place update) without a
    single 'donated buffers were not usable' warning."""
    import warnings

    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.clustering.kmeans import _build_lloyd_program
    from flink_ml_tpu.parallel.collective import ensure_on_mesh
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    mesh = default_mesh()
    x = rng.random((256, 4)).astype(np.float32)
    xs, _ = ensure_on_mesh(mesh, x, data_axes(mesh), jnp.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        # while program: both carry leaves flow through the loop carry
        c0 = jax.device_put(jnp.asarray(x[:3]))
        counts0 = jax.device_put(jnp.zeros((3,), jnp.float32))
        prog = _build_lloyd_program(mesh, "euclidean", 4, unroll=False)
        jax.block_until_ready(prog(xs, jnp.int32(256), c0, counts0))
        assert c0.is_deleted()
        assert counts0.is_deleted()
        # unrolled program: the centroid carry donates; counts0 is a
        # dead input there (counts are recomputed every straight-line
        # round) which jit drops before donation — no warning either way
        c0u = jax.device_put(jnp.asarray(x[:3]))
        prog_u = _build_lloyd_program(mesh, "euclidean", 4, unroll=True)
        jax.block_until_ready(prog_u(xs, jnp.int32(256), c0u,
                                     jnp.zeros((3,), jnp.float32)))
        assert c0u.is_deleted()
    assert not [w for w in caught
                if "donat" in str(w.message).lower()], \
        [str(w.message) for w in caught]


def test_kmeans_fit_emits_no_donation_warnings(rng):
    """Public-API bar: a KMeans.fit through the donated-carry program
    must stay warning-free (matching the PR 9 SGD/FTRL satellite)."""
    import warnings

    x, _ = make_blobs(rng, np.array([[0.0, 0.0], [6.0, 6.0]]), n_per=40)
    table = Table.from_columns(features=as_dense_vector_column(x))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        KMeans(k=2, seed=3, max_iter=8).fit(table)
    assert not [w for w in caught
                if "donat" in str(w.message).lower()], \
        [str(w.message) for w in caught]


def test_kmeans_kernel_failure_propagates(rng, monkeypatch):
    """A fit that selected the fused Lloyd kernel and whose kernel then
    fails must RAISE — no retry on the XLA partials: a run without the
    kernel it chose must not look like success."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.clustering import kmeans as km_mod

    x = rng.normal(size=(256, 4)).astype(np.float32)
    table = Table.from_columns(features=jnp.asarray(x))

    def fake_partials(xl, vl, c, interpret=False):
        raise RuntimeError("Mosaic lowering failed (synthetic)")

    from flink_ml_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    monkeypatch.setattr(pk, "lloyd_partial_sums", fake_partials)
    km_mod._build_lloyd_program.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="Mosaic lowering failed"):
            KMeans(k=3, seed=7, max_iter=5).fit(table)
    finally:
        km_mod._build_lloyd_program.cache_clear()
