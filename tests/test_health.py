"""Model-health telemetry (ISSUE 5): convergence series, non-finite
sentinels, divergence classification, NonFiniteState fail-fast, serving
metrics, and the ``flink-ml-tpu-trace health`` CLI.

Acceptance bar: a LinearEstimatorBase fit under FLINK_ML_TPU_TRACE_DIR
yields per-epoch loss and update-norm series readable via
``flink-ml-tpu-trace health``, and a NaN-injected fit raises a terminal
NonFiniteState (no retries) with the ml.health divergence event in the
trace — all on CPU. The CSR host engine carries the ungated tests (it
runs everywhere); the compiled dense/KMeans program variants are gated
on shard_map availability like the rest of the suite.
"""

import json
import math
import os

import numpy as np
import pytest
import scipy.sparse  # noqa: F401  (sparse vectors need scipy present)

import jax

from flink_ml_tpu.common.metrics import metrics
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.linalg.vectors import SparseVector
from flink_ml_tpu.models.regression import LinearRegression
from flink_ml_tpu.observability import health
from flink_ml_tpu.observability.exporters import read_spans
from flink_ml_tpu.observability.health import main as health_cli
from flink_ml_tpu.observability.tracing import TRACE_DIR_ENV, tracer
from flink_ml_tpu.resilience import NonFiniteState, RetryPolicy


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(TRACE_DIR_ENV, raising=False)
    monkeypatch.delenv(health.HEALTH_ENV, raising=False)
    yield
    tracer.shutdown()


def _events(trace_dir, name):
    return [ev for sp in read_spans(str(trace_dir))
            for ev in sp.get("events", ()) if ev.get("name") == name]


def sparse_regression_table(rng, n=160, d=4):
    x = rng.normal(size=(n, d))
    w_true = np.arange(1.0, d + 1.0)
    y = x @ w_true
    feats = np.asarray(
        [SparseVector(d, np.arange(d), row) for row in x], object)
    return Table.from_columns(features=feats, label=y)


def dense_regression_table(rng, n=256, d=4):
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ np.arange(1.0, d + 1.0)).astype(np.float32)
    return Table.from_columns(features=x, label=y)


# -- device-side helpers ------------------------------------------------------

def test_finite_sentinel_single_scalar():
    """One boolean out of many leaves; NaN/Inf anywhere trips it — and
    it runs inside jit (the JL107-clean-by-design contract)."""
    import jax.numpy as jnp

    @jax.jit
    def probe(a, b):
        return health.finite_sentinel(a, b)

    ok = probe(jnp.ones(4), jnp.zeros((2, 2)))
    assert bool(ok) is True
    bad = probe(jnp.array([1.0, jnp.nan]), jnp.zeros((2, 2)))
    assert bool(bad) is False
    inf = probe(jnp.ones(4), jnp.array([[1.0, jnp.inf], [0.0, 0.0]]))
    assert bool(inf) is False


def test_convergence_row_values_and_finite_fold():
    import jax.numpy as jnp

    @jax.jit
    def probe(loss, prev, new):
        return health.convergence_row(loss, prev, new)

    row, fin = probe(jnp.float32(2.0), jnp.zeros(3),
                     jnp.array([3.0, 0.0, 4.0]))
    row = np.asarray(row)
    assert row[0] == pytest.approx(2.0)
    assert row[1] == pytest.approx(5.0)  # ||new - prev||
    assert row[2] == pytest.approx(5.0)  # ||new||
    assert bool(fin) is True
    _, fin = probe(jnp.float32(2.0), jnp.zeros(3),
                   jnp.array([jnp.nan, 0.0, 4.0]))
    assert bool(fin) is False  # a NaN parameter poisons the fold


# -- divergence classification ------------------------------------------------

def test_classify_divergence_non_finite_wins():
    kind, epoch = health.classify_divergence(
        {"loss": [1.0, 0.5, float("nan"), 0.1]})
    assert (kind, epoch) == ("non-finite", 2)
    # sentinel-only signal (series finite, parameters were not)
    kind, epoch = health.classify_divergence(
        {"loss": [1.0, 0.5]}, finite=False)
    assert (kind, epoch) == ("non-finite", 1)


def test_classify_divergence_exploding_norm_window():
    # epochs 2-3 grow fast but sit below the absolute floor (1e6);
    # epoch 4 is the first above it with window growth past the factor
    norms = [1.0, 10.0, 1e3, 1e5, 1e7, 1e10]
    assert health.classify_divergence(
        {"paramNorm": norms}, window=2, factor=1e3) == \
        ("exploding-norm", 4)
    # below the absolute floor, large ratios are normal early training
    assert health.classify_divergence(
        {"paramNorm": [1e-6, 1e-3, 1.0, 10.0]},
        window=1, factor=1e2) is None
    assert health.classify_divergence(
        {"loss": [5.0, 4.0, 3.0]}) is None


# -- acceptance: CSR LinearEstimatorBase fit ---------------------------------

def test_csr_fit_records_convergence_series(tmp_path, monkeypatch, rng):
    """A traced fit yields per-epoch loss + update-norm series: labeled
    ml.health histograms in the registry and ml.convergence span events
    the health CLI renders."""
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table = sparse_regression_table(rng)
    before = metrics.group("ml", "health").histogram(
        "loss", buckets=health.VALUE_BUCKETS,
        labels={"algo": "LinearRegression"}).snapshot()["count"]
    LinearRegression(max_iter=8, learning_rate=0.1,
                     global_batch_size=40).fit(table)
    after = metrics.group("ml", "health").histogram(
        "loss", buckets=health.VALUE_BUCKETS,
        labels={"algo": "LinearRegression"}).snapshot()["count"]
    assert after - before == 8
    tracer.shutdown()

    conv = _events(trace_dir, health.CONVERGENCE_EVENT)
    assert len(conv) == 8
    epochs = sorted(ev["attrs"]["epoch"] for ev in conv)
    assert epochs == list(range(8))
    for ev in conv:
        attrs = ev["attrs"]
        assert attrs["algo"] == "LinearRegression"
        assert math.isfinite(attrs["loss"])
        assert math.isfinite(attrs["updateNorm"])
        assert math.isfinite(attrs["paramNorm"])
    assert not _events(trace_dir, health.HEALTH_EVENT)

    # CLI: the convergence table renders from the artifacts alone
    rc = health_cli([str(trace_dir)])
    assert rc == 0
    rc = health_cli([str(trace_dir), "--check"])
    assert rc == 0  # healthy fit: no health event, check passes


def test_nan_injected_fit_raises_terminal_with_event(
        tmp_path, monkeypatch, rng, capsys):
    """Acceptance: an absurd learning rate overflows the fit; the fit
    raises NonFiniteState, the ml.health event lands in the trace, and
    ``health --check`` exits 3."""
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table = sparse_regression_table(rng)
    with np.errstate(over="ignore", invalid="ignore"):
        with pytest.raises(NonFiniteState) as exc:
            LinearRegression(max_iter=30, learning_rate=1e160,
                             global_batch_size=40).fit(table)
    assert exc.value.epoch is not None
    tracer.shutdown()

    events = _events(trace_dir, health.HEALTH_EVENT)
    assert len(events) == 1
    assert events[0]["attrs"]["kind"] == "non-finite"
    assert events[0]["attrs"]["algo"] == "LinearRegression"

    rc = health_cli([str(trace_dir), "--check"])
    assert rc == 3
    out = capsys.readouterr().out
    assert "non-finite" in out


def test_guard_without_trace_dir_still_raises(rng):
    """The always-on tier: no trace dir, no series — the cheap final-
    state guard still turns a NaN fit into the terminal failure."""
    table = sparse_regression_table(rng)
    with np.errstate(over="ignore", invalid="ignore"):
        with pytest.raises(NonFiniteState):
            LinearRegression(max_iter=30, learning_rate=1e160,
                             global_batch_size=40).fit(table)


def test_health_env_0_disables_layer(monkeypatch, rng):
    monkeypatch.setenv(health.HEALTH_ENV, "0")
    table = sparse_regression_table(rng)
    with np.errstate(over="ignore", invalid="ignore"):
        model = LinearRegression(max_iter=30, learning_rate=1e160,
                                 global_batch_size=40).fit(table)
    assert not np.isfinite(model.coefficients).all()


def test_nonfinite_is_terminal_no_retries(rng):
    """Acceptance: under a retry policy, NonFiniteState propagates on
    the FIRST attempt — run_supervised must not burn restarts on a
    deterministic NaN."""
    table = sparse_regression_table(rng)
    restarts_before = metrics.group("ml", "resilience").get_counter(
        "restarts")
    est = LinearRegression(max_iter=30, learning_rate=1e160,
                           global_batch_size=40)
    est.set_retry_policy(RetryPolicy(max_restarts=3, backoff_s=0.0))
    with np.errstate(over="ignore", invalid="ignore"):
        with pytest.raises(NonFiniteState):
            est.fit(table)
    assert metrics.group("ml", "resilience").get_counter(
        "restarts") == restarts_before


def test_exploding_norm_reports_without_raising(monkeypatch):
    """Exploding-but-finite norms classify as drift (event + counter),
    not as a terminal failure."""
    before = metrics.group("ml", "health").get_counter(
        "divergences", labels={"algo": "probe", "kind": "exploding-norm"})
    cls = health.check_fit(
        "probe",
        {"loss": [1.0, 2.0, 4.0, 8.0, 16.0, 32.0],
         "paramNorm": [1.0, 1e2, 1e4, 1e7, 1e9, 1e11]})
    assert cls == ("exploding-norm", 5)
    assert metrics.group("ml", "health").get_counter(
        "divergences",
        labels={"algo": "probe", "kind": "exploding-norm"}) == before + 1


# -- FTRL (online) ------------------------------------------------------------

def _ftrl_fixture(rng, coeffs):
    n, dim = 90, 5
    x = rng.normal(size=(n, dim))
    y = (x @ rng.normal(size=dim) > 0).astype(np.float64)
    feats = np.asarray(
        [SparseVector(dim, np.arange(dim), row) for row in x], object)
    table = Table.from_columns(features=feats, label=y)
    init = Table.from_columns(
        coefficient=as_dense_vector_column(np.asarray(coeffs)[None, :]),
        modelVersion=np.asarray([0], np.int64))
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    return table, OnlineLogisticRegression(
        global_batch_size=30).set_initial_model_data(init)


def test_ftrl_per_batch_loss_series(tmp_path, monkeypatch, rng):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table, est = _ftrl_fixture(rng, np.zeros(5))
    est.fit(table)
    tracer.shutdown()
    conv = _events(trace_dir, health.CONVERGENCE_EVENT)
    ftrl = [ev for ev in conv
            if ev["attrs"]["algo"] == "OnlineLogisticRegression"]
    assert len(ftrl) == 3  # one per global batch
    assert all(math.isfinite(ev["attrs"]["loss"]) for ev in ftrl)


def test_ftrl_nan_state_raises(tmp_path, monkeypatch, rng):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table, est = _ftrl_fixture(rng, np.full(5, np.nan))
    with np.errstate(all="ignore"):
        with pytest.raises(NonFiniteState):
            est.fit(table)
    tracer.shutdown()
    events = _events(trace_dir, health.HEALTH_EVENT)
    assert any(ev["attrs"]["kind"] == "non-finite" for ev in events)


# -- serving path -------------------------------------------------------------

def _lr_servable(coeffs):
    from flink_ml_tpu.servable.lr import (
        LogisticRegressionModelData,
        LogisticRegressionModelServable,
    )
    servable = LogisticRegressionModelServable()
    servable.model_data = LogisticRegressionModelData(np.asarray(coeffs))
    return servable


def _df(rows):
    from flink_ml_tpu.linalg.vectors import DenseVector
    from flink_ml_tpu.servable.api import DataFrame, DataTypes, Row
    return DataFrame(["features"], [DataTypes.vector()],
                     [Row([DenseVector(r)]) for r in rows])


def test_servable_transform_records_serving_metrics():
    labels = {"servable": "LogisticRegressionModelServable"}
    group = metrics.group("ml", "serving")
    t_before = group.get_counter("transforms", labels=labels)
    r_before = group.get_counter("rowsTotal", labels=labels)
    servable = _lr_servable([1.0, -1.0])
    servable.transform(_df([[1.0, 0.0], [0.0, 1.0], [2.0, 2.0]]))
    assert group.get_counter("transforms", labels=labels) == t_before + 1
    assert group.get_counter("rowsTotal", labels=labels) == r_before + 3
    assert group.histogram("transformMs",
                           labels=labels).snapshot()["count"] >= 1
    assert group.histogram("rows", buckets=health.COUNT_BUCKETS,
                           labels=labels).snapshot()["count"] >= 1
    # prediction-distribution drift baseline
    assert group.get_gauge("predictionFiniteFraction",
                           labels=labels) == 1.0
    assert 0.0 <= group.get_gauge("predictionMean", labels=labels) <= 1.0
    assert 0.0 < group.get_gauge("probabilityMean", labels=labels) < 1.0


def test_servable_nonfinite_probability_emits_health_event(
        tmp_path, monkeypatch):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    labels = {"servable": "LogisticRegressionModelServable"}
    before = metrics.group("ml", "health").get_counter(
        "divergences", labels={
            "algo": "LogisticRegressionModelServable",
            "kind": "non-finite-probability"})
    servable = _lr_servable([np.nan, 1.0])
    with np.errstate(invalid="ignore"):
        out = servable.transform(_df([[1.0, 0.0], [0.0, 1.0]]))
    # serving never fails on bad numerics — it reports them
    assert out.num_rows() == 2
    assert metrics.group("ml", "health").get_counter(
        "divergences", labels={
            "algo": "LogisticRegressionModelServable",
            "kind": "non-finite-probability"}) == before + 1
    # a NaN coefficient poisons every margin through the matmul
    frac = metrics.group("ml", "serving").get_gauge(
        "probabilityFiniteFraction", labels=labels)
    assert frac == pytest.approx(0.0)
    tracer.shutdown()
    events = _events(trace_dir, health.HEALTH_EVENT)
    assert any(ev["attrs"]["kind"] == "non-finite-probability"
               for ev in events)


def test_pipeline_servable_also_instrumented():
    """The _served wrapper applies to every TransformerServable subclass
    — the pipeline servable records its own transform envelope."""
    from flink_ml_tpu.servable.builder import PipelineModelServable
    labels = {"servable": "PipelineModelServable"}
    before = metrics.group("ml", "serving").get_counter(
        "transforms", labels=labels)
    pipe = PipelineModelServable([_lr_servable([1.0, -1.0])])
    pipe.transform(_df([[1.0, 0.0]]))
    assert metrics.group("ml", "serving").get_counter(
        "transforms", labels=labels) == before + 1


# -- health CLI ---------------------------------------------------------------

def test_health_cli_json_and_serving_summary(tmp_path, monkeypatch,
                                             rng, capsys):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    LinearRegression(max_iter=4, learning_rate=0.1,
                     global_batch_size=40).fit(
        sparse_regression_table(rng))
    _lr_servable([1.0, -1.0]).transform(_df([[1.0, 0.0], [0.0, 1.0]]))
    from flink_ml_tpu.observability.exporters import dump_metrics
    dump_metrics(str(trace_dir))
    tracer.shutdown()
    capsys.readouterr()
    rc = health_cli([str(trace_dir), "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    fits = [f for f in doc["fits"] if f["algo"] == "LinearRegression"]
    assert fits and fits[0]["epochs"] == 4
    assert "loss" in fits[0]["series"]
    assert "updateNorm" in fits[0]["series"]
    serving = doc["serving"]["LogisticRegressionModelServable"]
    assert serving["transforms"] >= 1
    assert "transformMs_p50" in serving


def test_health_cli_via_trace_entrypoint(tmp_path, monkeypatch, rng,
                                         capsys):
    """`flink-ml-tpu-trace health <dir>` dispatches to the health view."""
    from flink_ml_tpu.observability.cli import main as trace_cli
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    LinearRegression(max_iter=3, learning_rate=0.1,
                     global_batch_size=40).fit(
        sparse_regression_table(rng))
    tracer.shutdown()
    rc = trace_cli(["health", str(trace_dir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "LinearRegression" in out
    assert "loss" in out


def test_health_cli_check_empty_dir(tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    assert health_cli([str(empty), "--check"]) == 2


# -- compiled program variants (shard_map-gated, run in CI) -------------------

def test_dense_plain_fit_records_series(tmp_path, monkeypatch, rng):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table = dense_regression_table(rng)
    LinearRegression(max_iter=6, learning_rate=0.1,
                     global_batch_size=64).fit(table)
    tracer.shutdown()
    conv = [ev for ev in _events(trace_dir, health.CONVERGENCE_EVENT)
            if ev["attrs"]["algo"] == "LinearRegression"]
    assert len(conv) == 6
    assert all(math.isfinite(ev["attrs"]["loss"]) for ev in conv)


def test_dense_nan_fit_raises_with_sentinel(tmp_path, monkeypatch, rng):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table = dense_regression_table(rng)
    with pytest.raises(NonFiniteState):
        LinearRegression(max_iter=20, learning_rate=1e12,
                         global_batch_size=64).fit(table)
    tracer.shutdown()
    events = _events(trace_dir, health.HEALTH_EVENT)
    assert any(ev["attrs"]["kind"] == "non-finite" for ev in events)


def test_segmented_fit_fails_at_segment_boundary(tmp_path, monkeypatch,
                                                 rng):
    """Device-mode checkpointed fit: the sentinel is checked at the
    segment (epoch) boundary, so the fit dies there instead of running
    out the full round budget."""
    from flink_ml_tpu.iteration.checkpoint import CheckpointManager
    from flink_ml_tpu.iteration.iteration import IterationConfig
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    table = dense_regression_table(rng)
    cfg = IterationConfig(
        mode="device", checkpoint_interval=4,
        checkpoint_manager=CheckpointManager(str(tmp_path / "ckpt")))
    est = LinearRegression(max_iter=80, learning_rate=1e12,
                           global_batch_size=64)
    est.set_iteration_config(cfg)
    with pytest.raises(NonFiniteState):
        est.fit(table)
    tracer.shutdown()
    assert _events(trace_dir, health.HEALTH_EVENT)


def test_tensor_parallel_fit_records_series(tmp_path, monkeypatch, rng):
    """convergence_row's model-axis psum branch: a TP-mesh fit under
    trace yields the same global norms a DP fit would (the squared sums
    cross the model axis before the sqrt)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from flink_ml_tpu.ops.losses import LeastSquareLoss
    from flink_ml_tpu.ops.optimizer import SGD, SGDParams
    from flink_ml_tpu.parallel.mesh import create_mesh

    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    x = rng.normal(size=(800, 10))
    y = x @ rng.normal(size=10)
    prm = SGDParams(learning_rate=0.05, global_batch_size=200,
                    max_iter=5, tol=0.0)
    mesh = create_mesh((4, 2), ("data", "model"))
    coeffs_tp, _ = SGD(prm).optimize(LeastSquareLoss(), np.zeros(10),
                                     x, y, mesh=mesh, tag="TPFit")
    tracer.shutdown()
    tp = [ev for ev in _events(trace_dir, health.CONVERGENCE_EVENT)
          if ev["attrs"]["algo"] == "TPFit"]
    assert len(tp) == 5
    # cross-check one epoch's paramNorm against the host value
    dp_like = [ev["attrs"]["paramNorm"] for ev in tp]
    assert all(math.isfinite(v) and v > 0 for v in dp_like)
    assert dp_like[-1] == pytest.approx(
        float(np.linalg.norm(coeffs_tp)), rel=1e-4)


def test_kmeans_center_shift_series(tmp_path, monkeypatch, rng):
    from flink_ml_tpu.models.clustering.kmeans import KMeans
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    x = rng.normal(size=(240, 4)).astype(np.float32)
    KMeans(k=3, seed=7, max_iter=5).fit(Table.from_columns(features=x))
    tracer.shutdown()
    conv = [ev for ev in _events(trace_dir, health.CONVERGENCE_EVENT)
            if ev["attrs"]["algo"] == "KMeans"]
    assert len(conv) == 5
    assert all(math.isfinite(ev["attrs"]["centerShift"]) for ev in conv)
    assert not _events(trace_dir, health.HEALTH_EVENT)
