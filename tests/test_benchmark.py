"""Benchmark harness tests (ref: BenchmarkTest.java, DataGeneratorTest.java)."""

import json

import numpy as np
import pytest

from flink_ml_tpu.benchmark import (
    DenseVectorGenerator,
    LabeledPointWithWeightGenerator,
    RandomStringGenerator,
    resolve_generator,
)
from flink_ml_tpu.benchmark.runner import (
    load_config,
    main,
    resolve_stage,
    run_benchmark,
    run_benchmarks,
)


def test_generator_determinism():
    g1 = DenseVectorGenerator(seed=5, col_names=[["features"]],
                              num_values=20, vector_dim=3)
    g2 = DenseVectorGenerator(seed=5, col_names=[["features"]],
                              num_values=20, vector_dim=3)
    np.testing.assert_array_equal(g1.get_data().vectors("features"),
                                  g2.get_data().vectors("features"))


def test_device_datagen_path(monkeypatch):
    """Above the size threshold, numeric generators produce sharded device
    columns that flow into fit without a host round-trip."""
    import jax

    from flink_ml_tpu.benchmark import datagen

    monkeypatch.setattr(datagen, "_DEVICE_DATAGEN_MIN_BYTES", 0)
    g1 = DenseVectorGenerator(seed=5, col_names=[["features"]],
                              num_values=16, vector_dim=3)
    col = g1.get_data().column("features")
    assert isinstance(col, jax.Array) and col.dtype == "float32"
    g2 = DenseVectorGenerator(seed=5, col_names=[["features"]],
                              num_values=16, vector_dim=3)
    np.testing.assert_array_equal(np.asarray(col),
                                  np.asarray(g2.get_data().column("features")))

    g = LabeledPointWithWeightGenerator(
        seed=1, col_names=[["f", "l", "w"]], num_values=16, vector_dim=4,
        feature_arity=3, label_arity=2)
    t = g.get_data()
    assert isinstance(t.column("f"), jax.Array)
    assert set(np.unique(t.vectors("f"))) <= {0.0, 1.0, 2.0}
    assert set(np.unique(t["l"])) <= {0.0, 1.0}
    assert ((np.asarray(t["w"]) >= 0) & (np.asarray(t["w"]) < 1)).all()

    # device table → fit consumes it without densifying/off-ramping
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )
    model = LogisticRegression(
        features_col="f", label_col="l", weight_col="w",
        global_batch_size=8, max_iter=2).fit(t)
    assert model.coefficients.shape == (4,)


def test_labeled_point_generator_arities():
    g = LabeledPointWithWeightGenerator(
        seed=1, col_names=[["f", "l", "w"]], num_values=100, vector_dim=4,
        feature_arity=3, label_arity=2)
    t = g.get_data()
    f = t.vectors("f")
    assert set(np.unique(f)) <= {0.0, 1.0, 2.0}
    assert set(np.unique(t["l"])) <= {0.0, 1.0}
    assert ((t["w"] >= 0) & (t["w"] < 1)).all()


def test_string_generator_distinct():
    g = RandomStringGenerator(seed=2, col_names=[["s"]], num_values=200,
                              num_distinct_values=5)
    t = g.get_data()
    assert len(set(t["s"])) <= 5


def test_benchmark_rows_record_execution_path():
    """Kernel-capable stages must name the code path their number
    measured (VERDICT r3 ask: 'a note on which path ran'): the SGD
    fit runs the while-loop program and, on the CPU test backend, Lloyd's
    the XLA partials."""
    from flink_ml_tpu.benchmark.runner import run_benchmark

    lr_spec = {
        "stage": {"className": ("org.apache.flink.ml.classification."
                                "logisticregression.LogisticRegression"),
                  "paramMap": {"maxIter": 3, "globalBatchSize": 64}},
        "inputData": {
            "className": ("org.apache.flink.ml.benchmark.datagenerator."
                          "common.LabeledPointWithWeightGenerator"),
            "paramMap": {"colNames": [["features", "label", "weight"]],
                         "seed": 2, "numValues": 256, "vectorDim": 4,
                         "featureArity": 0, "labelArity": 2}}}
    assert run_benchmark("lr", lr_spec)["executionPath"] == "xla-while"

    km_spec = {
        "stage": {"className": "org.apache.flink.ml.clustering.kmeans."
                               "KMeans",
                  "paramMap": {"featuresCol": "features", "k": 2,
                               "maxIter": 3, "seed": 0}},
        "inputData": {
            "className": ("org.apache.flink.ml.benchmark.datagenerator."
                          "common.DenseVectorGenerator"),
            "paramMap": {"colNames": [["features"]], "seed": 2,
                         "numValues": 256, "vectorDim": 4}}}
    assert run_benchmark("km", km_spec)["executionPath"] == "xla-lloyd"


def test_codes_to_strings_matches_direct_gather():
    """The int-view string gather must be byte-identical to the plain
    tokens[ints] fancy-index across dense/sparse domains, widths whose
    '<U' itemsize is and isn't a multiple of 8, and empty input."""
    from flink_ml_tpu.benchmark.datagen import _codes_to_strings

    rng = np.random.default_rng(0)
    for k, shape in [(100, (1000, 7)), (3, (50,)), (100000, (20, 4)),
                     (1, (5,)), (1000, (0, 3))]:
        ints = rng.integers(0, k, shape)
        got = _codes_to_strings(ints, k)
        assert got.dtype.kind == "U"
        assert got.shape == shape
        if ints.size:
            want = np.array([str(v) for v in range(k)])[ints]
            assert np.array_equal(got, want)
            assert got.dtype == want.dtype


def test_resolve_java_class_names():
    assert resolve_generator(
        "org.apache.flink.ml.benchmark.datagenerator.common."
        "DenseVectorGenerator") is DenseVectorGenerator
    cls = resolve_stage(
        "org.apache.flink.ml.clustering.kmeans.KMeans")
    assert cls.__name__ == "KMeans"
    with pytest.raises(ValueError):
        resolve_stage("com.example.Bogus")


def test_run_benchmark_estimator_and_config(tmp_path):
    spec = {
        "stage": {"className": "KMeans", "paramMap": {"k": 2, "maxIter": 3}},
        "inputData": {"className": "DenseVectorGenerator",
                      "paramMap": {"seed": 2, "colNames": [["features"]],
                                   "numValues": 500, "vectorDim": 4}},
    }
    res = run_benchmark("km", spec)
    assert res["inputRecordNum"] == 500
    assert res["outputRecordNum"] == 2  # model data = k centroids
    assert res["inputThroughput"] > 0

    # end-to-end CLI with a reference-style config file incl. // comments
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text("// license header\n" + json.dumps(
        {"version": 1, "bench1": spec}))
    out_path = tmp_path / "out.json"
    assert main([str(cfg_path), "--output-file", str(out_path)]) == 0
    results = json.loads(out_path.read_text())
    assert "results" in results["bench1"]


def test_run_benchmarks_captures_failures():
    config = {
        "bad": {"stage": {"className": "Bogus"},
                "inputData": {"className": "DenseVectorGenerator"}},
    }
    results = run_benchmarks(config)
    assert "exception" in results["bad"]


def test_shipped_configs_parse():
    import glob
    import os
    cfg_dir = os.path.join(os.path.dirname(__file__), "..",
                           "flink_ml_tpu", "benchmark", "configs")
    files = glob.glob(os.path.join(cfg_dir, "*.json"))
    assert len(files) >= 4
    for f in files:
        config = load_config(f)
        for spec in config.values():
            resolve_stage(spec["stage"]["className"])
            resolve_generator(spec["inputData"]["className"])


def test_shipped_configs_execute_scaled_down():
    """Every shipped workload runs end-to-end (numValues cut to 1000; the
    demo's two deliberately-broken entries must fail, everything else must
    succeed — BenchmarkTest.java parity for the full config set)."""
    import glob
    import os
    cfg_dir = os.path.join(os.path.dirname(__file__), "..",
                           "flink_ml_tpu", "benchmark", "configs")
    expected_failures = {"Undefined-Parameter", "Unmatch-Input"}
    for f in sorted(glob.glob(os.path.join(cfg_dir, "*.json"))):
        config = load_config(f)
        for spec in config.values():
            spec["inputData"].setdefault("paramMap", {})["numValues"] = 1000
        results = run_benchmarks(config)
        for name, entry in results.items():
            if name in expected_failures:
                assert "exception" in entry, (f, name)
            else:
                assert "results" in entry, (f, name, entry.get("exception"))
                assert entry["results"]["inputRecordNum"] == 1000


def test_model_benchmark_with_model_data():
    spec = {
        "stage": {"className": "KMeansModel",
                  "paramMap": {"k": 2, "featuresCol": "features"}},
        "modelData": {"className": "KMeansModelDataGenerator",
                      "paramMap": {"seed": 1, "arraySize": 2,
                                   "vectorDim": 4}},
        "inputData": {"className": "DenseVectorGenerator",
                      "paramMap": {"seed": 2, "colNames": [["features"]],
                                   "numValues": 300, "vectorDim": 4}},
    }
    res = run_benchmark("kmm", spec)
    assert res["outputRecordNum"] == 300


def test_graft_entry_single_device():
    import jax

    from __graft_entry__ import entry
    fn, args = entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


def test_graft_entry_dryrun_multichip():
    from __graft_entry__ import dryrun_multichip
    dryrun_multichip(8)


def test_visualize_results(tmp_path):
    """Ref parity: bin/benchmark-results-visualize.py — chart from results."""
    import json

    from flink_ml_tpu.benchmark import visualize

    results = {
        "KMeans-1": {"stage": {}, "results": {
            "totalTimeMs": 100.0, "inputRecordNum": 1000,
            "inputThroughput": 10000.0, "outputRecordNum": 1000,
            "outputThroughput": 10000.0}},
        "Broken-1": {"exception": "ValueError: nope"},
    }
    p1 = tmp_path / "r1.json"
    p1.write_text(json.dumps(results))
    out = tmp_path / "chart.png"
    visualize.main([str(p1), str(p1), "--output-file", str(out)])
    assert out.exists() and out.stat().st_size > 0


def test_host_loop_round_metrics():
    """The host-mode iteration publishes per-round timing gauges."""
    import jax.numpy as jnp

    from flink_ml_tpu.common.metrics import metrics
    from flink_ml_tpu.iteration.iteration import (IterationConfig,
                                                  iterate_bounded)

    group = metrics.group("ml", "iteration")
    before = group.get_counter("rounds")
    iterate_bounded(jnp.float32(0.0), lambda c, e: c + 1.0, max_iter=3,
                    config=IterationConfig(mode="host"))
    assert group.get_counter("rounds") == before + 3
    assert group.get_gauge("lastRoundMs") is not None


def test_double_generator_device_path(monkeypatch):
    """Past the device-gen threshold DoubleGenerator emits device-resident
    f32 columns (same policy as DenseVectorGenerator); host consumers can
    still materialize them."""
    import jax

    from flink_ml_tpu.benchmark import datagen
    from flink_ml_tpu.benchmark.datagen import DoubleGenerator
    from flink_ml_tpu.ops import columnar

    monkeypatch.setattr(datagen, "_DEVICE_DATAGEN_MIN_BYTES", 0)
    gen = DoubleGenerator(seed=2, col_names=[["f0", "f1"]], num_values=64)
    t = gen.get_data()
    col = t.column("f0")
    assert isinstance(col, jax.Array) and columnar.is_device_array(col)
    vals = np.asarray(col)  # host off-ramp still works
    assert vals.shape == (64,)
    assert 0.0 <= vals.min() and vals.max() < 1.0
    assert not np.array_equal(vals, np.asarray(t.column("f1")))  # streams
    gen2 = DoubleGenerator(seed=2, col_names=[["f0"]], num_values=64,
                           arity=5)
    v2 = np.asarray(gen2.get_data().column("f0"))
    assert set(np.unique(v2)) <= set(range(5))


def test_string_gather_asserts_on_out_of_range_codes():
    """ADVICE r5 #5: mode='clip' would silently clamp a bad code to the
    last token — the one-time debug assert must fail loudly instead, for
    both too-large and negative codes; in-range codes still gather."""
    import pytest

    from flink_ml_tpu.benchmark.datagen import _string_gather

    tokens = np.array(["a", "bb", "ccc"])
    good = _string_gather(tokens, np.asarray([[0, 2], [1, 1]]))
    assert np.array_equal(good, [["a", "ccc"], ["bb", "bb"]])
    with pytest.raises(AssertionError, match="out of range"):
        _string_gather(tokens, np.asarray([0, 3]))
    with pytest.raises(AssertionError, match="out of range"):
        _string_gather(tokens, np.asarray([-1, 0]))
    # empty input stays fine (no max() on an empty array)
    assert _string_gather(tokens, np.zeros((0, 2), np.int64)).size == 0
