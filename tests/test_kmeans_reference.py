"""Every Lloyd path against the plain reference the benchmark's ``correct``
is decided by (``benchmarks/harness/references/lloyd_kmeans.py``, the one
copy: direct squared differences in float32, sums in float64 on the host),
at the benchmark configuration's shapes (d 100, k 10, 10 rounds) and a row
count that divides over four devices and not into a kernel tile.

The seed is one at which no row lies within float32 rounding of a tie
between two centroids on any path: at 4,100 rows ONE row that goes the
other way moves a centroid by 1e-3 at once and by 2e-2 ten rounds later
(seed 5 has such a row on the four-device kernel paths; 1, 2, 3, 7 and 11
have none). The paths are float32 by different sums, so they may break a
tie differently; on the chip, at 12M rows, that is 60 rows in a fit.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.harness.references import lloyd_kmeans
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.parallel import create_mesh

N, D, K, ROUNDS, SEED = 4100, 100, 10, 10, 11
#: path -> (rounds unrolled, kernels interpreted, iteration config)
PATHS = {
    "xla-lloyd/while": (False, False, None),
    "xla-lloyd/unrolled": (True, False, None),
    "pallas-lloyd/while": (False, True, None),
    "pallas-lloyd/unrolled": (True, True, None),
    "xla-lloyd-segments": (True, False, "segments"),
    "pallas-lloyd-segments": (True, True, "segments"),
    "host-rounds": (True, False, "host"),
}


def resident_table(mesh):
    """The benchmark generator's table: uniform [0, 1) float32, resident
    and row-sharded over ``mesh``."""
    x = jax.jit(
        lambda key: jax.random.uniform(key, (N, D), jax.numpy.float32),
        out_shardings=NamedSharding(mesh, P("data", None)))(
            jax.random.key(SEED))
    return jax.block_until_ready(x)


def fit_path(path, x, mesh, monkeypatch, tmp_path, request):
    unroll, kernels, mode = PATHS[path]
    if kernels:
        request.getfixturevalue("interpreted_kernels")
    monkeypatch.setattr(km, "default_mesh", lambda: mesh)
    monkeypatch.setattr(km, "_UNROLL_MAX_ROUNDS", 64 if unroll else 0)
    est = KMeans(k=K, max_iter=ROUNDS, seed=SEED)
    if mode == "segments":
        est.set_iteration_config(IterationConfig(
            mode="device", checkpoint_interval=4,
            checkpoint_manager=CheckpointManager(str(tmp_path))))
    elif mode == "host":
        est.set_iteration_config(IterationConfig(mode="host"))
    model = est.fit(Table.from_columns(features=x))
    return model, est.last_execution_path


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("path", PATHS)
def test_every_lloyd_path_agrees_with_the_plain_reference(
        path, devices, monkeypatch, tmp_path, request):
    mesh = create_mesh(devices=jax.devices()[:devices])
    x = resident_table(mesh)
    model, reported = fit_path(path, x, mesh, monkeypatch, tmp_path,
                               request)
    assert reported == path.split("/")[0]
    reference = lloyd_kmeans.run(
        {"features": x}, {"k": K, "maxIter": ROUNDS, "seed": SEED}, devices)
    assert reference["_rounds"] == ROUNDS
    np.testing.assert_allclose(model.centroids, reference["centroid"],
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(model.weights, reference["weight"])
    assert model.weights.sum() == N
    gaps = lloyd_kmeans.compare(
        {"centroid": model.centroids, "weight": model.weights}, reference)
    assert gaps["centroid_gap"] < 1e-5 and gaps["weight_gap"] == 0.0
