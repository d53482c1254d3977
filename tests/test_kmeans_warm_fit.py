"""A warm KMeans fit builds and places nothing it built before (PERF.md
section 5): no ``jax.jit`` made, nothing traced, lowered or compiled, the
chosen rows taken by one cached program, the carry placed by ONE
``jax.device_put`` of host arrays — on every Lloyd path, one device and
eight, a resident column and a host one. Where the mathematics did not
change (the XLA paths: the CPU multiplies float32 exactly at any
precision) the answers are the parent tree's to the last bit
(``tests/fixtures/kmeans_warm_fit/golden.json``, written from commit
269f96a by running this file as a script there). And the fit's own spans:
one tree a fit, summing to the root, nothing recorded when nobody looks.
"""

import json
import os
import sys

if __name__ == "__main__":  # the golden writer: the mesh conftest.py gives
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())

import jax
import numpy as np
import pytest

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.observability import tracing
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.parallel import create_mesh
from test_optimizer_warm_fit import Watch

GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures",
                      "kmeans_warm_fit", "golden.json")
N, D, K, ROUNDS, SEGMENT, SEED = 2000, 6, 4, 6, 2, 3

#: (execution path, rounds unrolled, devices, where the column lives)
XLA_CASES = [
    ("xla-lloyd", False, 1, "device"), ("xla-lloyd", False, 8, "device"),
    ("xla-lloyd", True, 1, "host"), ("xla-lloyd", True, 8, "device"),
    ("xla-lloyd-segments", True, 1, "device"),
    ("xla-lloyd-segments", True, 8, "host"),
    ("host-rounds", True, 1, "host"), ("host-rounds", True, 8, "device"),
]
KERNEL_CASES = [
    ("pallas-lloyd", False, 1, "device"), ("pallas-lloyd", True, 8, "device"),
    ("pallas-lloyd-segments", True, 1, "host"),
    ("pallas-lloyd-segments", True, 8, "device"),
]


def case_id(case) -> str:
    return "-".join(map(str, case))


def make_table(where: str) -> Table:
    x = np.random.default_rng(7).random((N, D)).astype(np.float32)
    return Table.from_columns(
        features=jax.numpy.asarray(x) if where == "device" else x)


def fit(case, ckpt_dir):
    """One fit of ``case`` -> (centroids, weights, the path it reported)."""
    path, unroll, devices, where = case
    km.default_mesh = lambda: create_mesh(devices=jax.devices()[:devices])
    km._UNROLL_MAX_ROUNDS = 64 if unroll else 0
    est = KMeans(k=K, max_iter=ROUNDS, seed=SEED)
    if path.endswith("-segments"):
        est.set_iteration_config(IterationConfig(
            mode="device", checkpoint_interval=SEGMENT,
            checkpoint_manager=CheckpointManager(str(ckpt_dir))))
    elif path == "host-rounds":
        est.set_iteration_config(IterationConfig(mode="host"))
    model = est.fit(make_table(where))
    return model.centroids, model.weights, est.last_execution_path


@pytest.fixture(autouse=True)
def restore_module_state(monkeypatch):
    monkeypatch.setattr(km, "default_mesh", km.default_mesh)
    monkeypatch.setattr(km, "_UNROLL_MAX_ROUNDS", km._UNROLL_MAX_ROUNDS)
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    tracer.recent.clear()
    yield
    tracer.recent.clear()


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


#: tracing, lowering and compiling: a warm fit does none of them
BUILDS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


@pytest.fixture
def watch(monkeypatch):
    w = Watch(monkeypatch, module=km, events=BUILDS)
    yield w
    w.armed = False  # jax keeps the listener; it counts nothing from here


def assert_warm(case, tmp_path, watch):
    first = fit(case, tmp_path)
    assert first[2] == case[0]
    with watch():
        again = fit(case, tmp_path)
    assert watch.jits == []
    assert watch.requests == 0
    # the carry goes up in one call, and nothing else is placed between
    # the column and the fetch
    assert [span for span, _ in watch.puts
            if span != "lloyd.place_inputs"] == ["lloyd.init"]
    np.testing.assert_array_equal(first[0], again[0])
    np.testing.assert_array_equal(first[1], again[1])
    return first


@pytest.mark.parametrize("case", XLA_CASES, ids=case_id)
def test_a_warm_fit_builds_nothing_and_answers_as_the_parent_did(
        case, tmp_path, watch, golden):
    centroids, weights, _ = assert_warm(case, tmp_path, watch)
    want = golden["fits"][case_id(case)]
    assert centroids.dtype == np.float64 and weights.dtype == np.float64
    assert centroids.tolist() == want["centroids"]
    assert weights.tolist() == want["weights"]


@pytest.mark.parametrize("case", KERNEL_CASES, ids=case_id)
def test_a_warm_kernel_fit_builds_nothing(case, tmp_path, watch, golden,
                                          interpreted_kernels):
    """The kernel's mathematics changed (float32 products, sums a tile at
    a time in the table's own layout), so its answers are held to the XLA
    path's, which are the parent's: counts equal, centroids to rounding."""
    centroids, weights, _ = assert_warm(case, tmp_path, watch)
    twin = next(c for c in XLA_CASES
                if c[0] == case[0].replace("pallas", "xla")
                and c[2] == case[2])
    want = golden["fits"][case_id(twin)]
    assert weights.tolist() == want["weights"]
    np.testing.assert_allclose(centroids, want["centroids"], rtol=0,
                               atol=1e-6)


# -- the fit's own spans -----------------------------------------------------

#: span -> its parent; every Lloyd path records exactly these under the root
TREE = {"lloyd.place_inputs": "KMeans.fit", "lloyd.init": "KMeans.fit",
        "lloyd.build_program": "KMeans.fit", "lloyd.launch": "KMeans.fit",
        "lloyd.fetch": "KMeans.fit", "lloyd.health": "KMeans.fit",
        "fit.model": "KMeans.fit"}
#: what the iteration runtime records under ``lloyd.launch``, and how often
UNDER_LAUNCH = {"xla-lloyd": {}, "xla-lloyd-segments": {"segment": 3},
                "host-rounds": {"epoch": ROUNDS}, "pallas-lloyd": {},
                "pallas-lloyd-segments": {"segment": 3}}


@pytest.mark.parametrize("case", [XLA_CASES[1], XLA_CASES[4], XLA_CASES[7],
                                  KERNEL_CASES[0], KERNEL_CASES[3]],
                         ids=case_id)
def test_the_spans_of_a_fit_are_one_tree_that_sums_to_its_root(
        case, tmp_path, monkeypatch, request):
    if case in KERNEL_CASES:
        request.getfixturevalue("interpreted_kernels")
    fit(case, tmp_path)                      # warm, and nobody looking:
    assert len(tracer.recent) == 0           # nothing recorded
    before = metrics.group(ML_GROUP, "iteration").snapshot()["counters"]
    monkeypatch.setattr(tracer, "keep_recent", True)
    fit(case, tmp_path)
    records = list(tracer.recent)
    assert len({r["trace"] for r in records}) == 1
    root, = [r for r in records if r["parent"] is None]
    assert root["name"] == "KMeans.fit" and root["attrs"]["kind"] == "fit"
    by_id = {r["id"]: r for r in records}
    names = [r["name"] for r in records]
    for name, parent in TREE.items():
        assert names.count(name) == 1, name
        rec = next(r for r in records if r["name"] == name)
        assert by_id[rec["parent"]]["name"] == parent
    init = next(r for r in records if r["name"] == "lloyd.init")
    assert init["attrs"] == {**init["attrs"], "rounds": ROUNDS, "k": K,
                             "path": case[0]}
    launch = next(r for r in records if r["name"] == "lloyd.launch")
    inner = [r["name"] for r in records if r["parent"] == launch["id"]
             and r["name"] in ("segment", "epoch")]
    assert {n: inner.count(n) for n in set(inner)} == UNDER_LAUNCH[case[0]]
    assert all(r["parent"] == launch["id"] for r in records
               if r["name"] in ("segment", "epoch"))
    # the root's children lie inside it, one after another
    children = [r for r in records if r["parent"] == root["id"]]
    assert sum(r["dur_us"] for r in children) <= root["dur_us"]
    # the blocking reads are counted as the SGD fit's are: the final state
    # (two leaves under one wait), and one fused bundle a segment boundary
    after = metrics.group(ML_GROUP, "iteration").snapshot()["counters"]
    segments = UNDER_LAUNCH[case[0]].get("segment", 0)
    assert {name: after[name] - before.get(name, 0)
            for name in ("boundaryFetches", "boundaryWaits")} == {
        "boundaryFetches": 2 + segments, "boundaryWaits": 1 + segments}


def write_golden(path):
    fits = {}
    import tempfile
    for case in XLA_CASES:
        with tempfile.TemporaryDirectory() as tmp:
            centroids, weights, reported = fit(case, tmp)
        assert reported == case[0], (case, reported)
        fits[case_id(case)] = {"centroids": centroids.tolist(),
                               "weights": weights.tolist()}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"commit": "269f96a", "fits": fits}, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    write_golden(sys.argv[1])
