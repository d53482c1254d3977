"""Observability layer: tracer spans, labeled/histogram metrics, exporters,
the ``flink-ml-tpu-trace`` CLI, and the fork-boundary merge.

Acceptance bar (ISSUE 3): with FLINK_ML_TPU_TRACE_DIR set, a supervised
fit with one injected chaos fault emits a Perfetto-loadable Chrome trace
containing nested fit→epoch→checkpoint spans plus a restart event, the
CLI renders a per-epoch summary from the artifacts alone, and the
Prometheus text dump includes labeled epoch-duration histogram buckets —
all verified here, not by hand.
"""

import json
import os
import re
import threading

import numpy as np
import pytest

import jax

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.hostpool import map_row_shards
from flink_ml_tpu.common.metrics import (
    Histogram,
    MetricsRegistry,
    metrics,
)
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.iteration.checkpoint import CheckpointManager
from flink_ml_tpu.iteration.iteration import (
    IterationConfig,
    iterate_bounded,
)
from flink_ml_tpu.models.common import IterationRuntimeMixin
from flink_ml_tpu.observability import (
    TRACE_DIR_ENV,
    chrome_trace,
    prometheus_text,
    read_metrics,
    read_spans,
    tracer,
    write_chrome_trace,
)
from flink_ml_tpu.observability.cli import main as trace_cli
from flink_ml_tpu.observability.cli import render_summary, summarize
from flink_ml_tpu.resilience import RetryPolicy, faults


@pytest.fixture(autouse=True)
def _clean_tracer(monkeypatch):
    """Each test arms its own trace dir; the singleton tracer's sink must
    not leak across tests, and ambient chaos must not reshape schedules."""
    monkeypatch.delenv(TRACE_DIR_ENV, raising=False)
    for var in ("FLINK_ML_TPU_CHAOS", "FLINK_ML_TPU_CHAOS_SEED",
                "FLINK_ML_TPU_CHAOS_RATE", "FLINK_ML_TPU_CHAOS_SITES",
                "FLINK_ML_TPU_CHAOS_AT"):
        monkeypatch.delenv(var, raising=False)
    faults.reset_env_plan()
    yield
    tracer.shutdown()


# -- metrics: labels, histograms, thread safety, merge -----------------------

def test_labeled_metrics_round_trip():
    reg = MetricsRegistry()
    g = reg.group("ml", "test")
    g.counter("retries", labels={"site": "epoch"})
    g.counter("retries", 2, labels={"site": "epoch"})
    g.counter("retries")  # unlabeled is a distinct series
    g.gauge("lastMs", 5.0, labels={"mode": "host"})
    assert g.get_counter("retries", labels={"site": "epoch"}) == 3
    assert g.get_counter("retries") == 1
    assert g.get_gauge("lastMs", labels={"mode": "host"}) == 5.0
    snap = reg.snapshot()["ml.test"]
    assert snap["counters"]['retries{site="epoch"}'] == 3
    assert snap["counters"]["retries"] == 1
    assert snap["gauges"]['lastMs{mode="host"}'] == 5.0


def test_histogram_cumulative_buckets():
    h = Histogram(buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["counts"] == [1, 2, 3]  # cumulative per bucket
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(555.5)


def test_registry_merge_folds_counters_histograms_gauges():
    driver, child = MetricsRegistry(), MetricsRegistry()
    driver.group("ml").counter("rows", 5)
    driver.group("ml").histogram("ms", buckets=(1.0, 10.0)).observe(0.5)
    child.group("ml").counter("rows", 7)
    child.group("ml").histogram("ms", buckets=(1.0, 10.0)).observe(5.0)
    child.group("ml").gauge("last", 42.0)
    child.group("ml", "new").counter("only_child")
    driver.merge(child.snapshot())
    snap = driver.snapshot()
    assert snap["ml"]["counters"]["rows"] == 12
    assert snap["ml"]["gauges"]["last"] == 42.0
    assert snap["ml"]["histograms"]["ms"]["count"] == 2
    assert snap["ml"]["histograms"]["ms"]["counts"] == [1, 2]
    assert snap["ml.new"]["counters"]["only_child"] == 1


def test_registry_merge_rejects_bucket_drift_whole():
    """A snapshot whose histogram buckets drifted must be rejected whole
    — not half-merged (counters folded, histograms dropped)."""
    driver, child = MetricsRegistry(), MetricsRegistry()
    driver.group("ml").histogram("ms", buckets=(1.0,)).observe(0.5)
    child.group("ml").counter("rows", 7)
    child.group("ml").histogram("ms", buckets=(1.0, 2.0)).observe(0.5)
    with pytest.raises(ValueError):
        driver.merge(child.snapshot())
    assert driver.group("ml").get_counter("rows") == 0
    assert driver.group("ml").histogram(
        "ms", buckets=(1.0,)).snapshot()["count"] == 1


def test_registry_concurrent_stress():
    """Concurrent stages hammering one registry must lose no update —
    the race the unlocked seed registry had."""
    reg = MetricsRegistry()
    threads, per_thread = 8, 500
    barrier = threading.Barrier(threads)

    def worker(i):
        barrier.wait()
        for n in range(per_thread):
            # group() creation races with sibling threads on purpose
            g = reg.group("ml", f"shared{n % 3}")
            g.counter("hits")
            g.histogram("ms").observe(float(n % 50))
            g.gauge("last", n)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = reg.snapshot()
    total_hits = sum(snap[f"ml.shared{k}"]["counters"]["hits"]
                     for k in range(3))
    total_obs = sum(snap[f"ml.shared{k}"]["histograms"]["ms"]["count"]
                    for k in range(3))
    assert total_hits == threads * per_thread
    assert total_obs == threads * per_thread


# -- Prometheus exposition ----------------------------------------------------

#: text exposition grammar: name{label="value",...} value — label values
#: may contain \" \\ \n escapes, per the Prometheus text format
_LV = r'"(?:[^"\\\n]|\\.)*"'
_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*=' + _LV +
    r'(,[a-zA-Z_][a-zA-Z0-9_]*=' + _LV + r')*\})?'
    r' (-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN)$')
_PROM_TYPE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                        r"(gauge|counter|histogram)$")


def test_prometheus_exposition_parses():
    reg = MetricsRegistry()
    g = reg.group("ml", "iteration")
    for ms in (0.5, 3.0, 700.0):
        g.histogram("epochMs", labels={"mode": "host"}).observe(ms)
    g.counter("rounds", 3)
    g.gauge("lastRoundMs", 700.0)
    text = prometheus_text(reg.snapshot())
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line) or _PROM_TYPE.match(line), line
    # labeled histogram series, cumulative, +Inf == _count
    assert ('flink_ml_tpu_ml_iteration_epochMs_bucket'
            '{mode="host",le="+Inf"} 3') in text
    assert 'flink_ml_tpu_ml_iteration_epochMs_count{mode="host"} 3' in text
    assert 'flink_ml_tpu_ml_iteration_rounds_total 3' in text


def test_prometheus_one_type_line_per_metric_name():
    """Two labeled series of one metric (op=save / op=restore) must share
    a single '# TYPE' header — duplicates violate the exposition format
    and strict scrapers reject the whole dump."""
    reg = MetricsRegistry()
    g = reg.group("ml", "checkpoint")
    g.histogram("opMs", labels={"op": "save"}).observe(1.0)
    g.histogram("opMs", labels={"op": "restore"}).observe(2.0)
    g.counter("ops", labels={"op": "save"})
    g.counter("ops", labels={"op": "restore"})
    text = prometheus_text(reg.snapshot())
    type_lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines)), type_lines
    assert 'opMs_bucket{op="save"' in text
    assert 'opMs_bucket{op="restore"' in text


def test_label_values_escaped():
    """Quotes/backslashes/newlines in label values must render escaped —
    unbalanced quotes would break the exposition grammar and the key
    round-trip."""
    reg = MetricsRegistry()
    g = reg.group("ml")
    hairy = 'ValueError("x")\\n'
    g.counter("errs", labels={"cls": hairy})
    assert g.get_counter("errs", labels={"cls": hairy}) == 1
    text = prometheus_text(reg.snapshot())
    line = next(ln for ln in text.splitlines() if "errs" in ln
                and not ln.startswith("#"))
    assert _PROM_LINE.match(line), line


# -- tracer ------------------------------------------------------------------

def test_span_nesting_parent_links(tmp_path):
    tracer.configure(str(tmp_path))
    with tracer.span("outer", job="j1") as outer:
        with tracer.span("inner") as inner:
            tracer.event("tick", n=1)
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id
    tracer.configure(None)
    spans = read_spans(str(tmp_path))
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["events"][0]["name"] == "tick"
    assert by_name["outer"]["attrs"]["job"] == "j1"
    assert by_name["outer"]["dur_us"] >= by_name["inner"]["dur_us"]


def test_chrome_instant_events_carry_owning_span_id(tmp_path):
    """Satellite (ISSUE 4): instants must name their owning span (and
    its parent) in args, or Perfetto shows floating events nobody can
    correlate back to a span."""
    from flink_ml_tpu.observability.exporters import chrome_trace_events

    tracer.configure(str(tmp_path))
    with tracer.span("outer"):
        with tracer.span("inner"):
            tracer.event("tick", n=1)
    tracer.configure(None)
    spans = read_spans(str(tmp_path))
    by_name = {s["name"]: s for s in spans}
    instants = [e for e in chrome_trace_events(spans) if e["ph"] == "i"]
    assert instants, "no instant events exported"
    tick = next(e for e in instants if e["name"] == "tick")
    assert tick["args"]["span_id"] == by_name["inner"]["id"]
    assert tick["args"]["parent_id"] == by_name["outer"]["id"]
    assert tick["args"]["n"] == 1  # event attrs still ride along


def test_disarmed_tracer_is_noop(tmp_path):
    with tracer.span("ghost") as sp:
        sp.set_attribute("x", 1)
        tracer.event("never")
    assert read_spans(str(tmp_path)) == []
    assert tracer.current() is None


# -- the supervised traced fit (acceptance criterion) -------------------------

class _ToyModel(Model):
    def transform(self, *inputs):
        return inputs


class _ToyEstimator(Estimator, IterationRuntimeMixin):
    """Minimal checkpoint-aware estimator: a pure-host GD iteration, so
    the whole fit→epoch→checkpoint→restart chain runs on any jax build
    (no shard_map dependency)."""

    def fit(self, table):
        return self._supervised_fit(lambda: self._fit_once(table))

    def _fit_once(self, table):
        A = np.diag([1.0, 2.0, 3.0])
        b = np.array([1.0, -2.0, 0.5])

        def body(carry, epoch):
            return carry - 0.1 * (A @ carry - b)

        w = iterate_bounded(np.zeros(3), body, max_iter=6,
                            jit_round=False,
                            config=self._iteration_config,
                            listeners=self._iteration_listeners)
        model = _ToyModel()
        model.coefficients = w
        return model


@pytest.fixture
def traced_supervised_fit(tmp_path, monkeypatch):
    """One supervised fit with one injected epoch fault, traced end to
    end; yields (trace_dir, model)."""
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    cfg = IterationConfig(mode="host", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    est = (_ToyEstimator()
           .set_iteration_config(cfg)
           .set_retry_policy(RetryPolicy(max_restarts=3, backoff_s=0.0)))
    with faults.chaos(at={"epoch-boundary": [4]}):
        model = est.fit(None)
    return str(trace_dir), model


def test_traced_fit_emits_nested_chrome_trace(traced_supervised_fit,
                                              tmp_path):
    trace_dir, model = traced_supervised_fit
    spans = read_spans(trace_dir)
    by_id = {s["id"]: s for s in spans}

    fits = [s for s in spans if s["name"] == "_ToyEstimator.fit"]
    assert len(fits) == 1
    epochs = [s for s in spans if s["name"] == "epoch"]
    assert epochs, "no epoch spans"
    # nested fit → epoch → checkpoint.save
    assert all(e["parent"] == fits[0]["id"] for e in epochs)
    saves = [s for s in spans if s["name"] == "checkpoint.save"]
    assert saves, "no checkpoint spans"
    assert all(by_id[s["parent"]]["name"] == "epoch" for s in saves)
    assert all(s["attrs"]["bytes"] > 0 for s in saves)
    # the injected fault produced a restart event + a restore span
    restarts = [ev for s in spans for ev in s["events"]
                if ev["name"] == "supervisor.restart"]
    assert len(restarts) == 1
    assert restarts[0]["attrs"]["error"] == "InjectedFault"
    assert any(s["name"] == "checkpoint.restore" for s in spans)

    # Chrome trace-event JSON: loadable, complete+instant phases present
    out = tmp_path / "chrome.json"
    n = write_chrome_trace(trace_dir, str(out))
    doc = json.loads(out.read_text())
    assert n == len(spans)
    events = doc["traceEvents"]
    assert {"X", "i"} <= {e["ph"] for e in events}
    for e in events:
        assert set(e) >= {"name", "ph", "ts", "pid", "tid"}
    assert any(e["ph"] == "i" and e["name"] == "supervisor.restart"
               for e in events)
    # the fit produced the correct model despite the fault
    expected = _ToyEstimator()._fit_once(None).coefficients
    np.testing.assert_allclose(model.coefficients, expected)


def test_trace_cli_summary_and_prometheus(traced_supervised_fit, tmp_path,
                                          capsys):
    trace_dir, _ = traced_supervised_fit

    assert trace_cli([trace_dir, "--check"]) == 0
    out = capsys.readouterr().out
    assert "per-epoch breakdown:" in out
    assert re.search(r"epoch 0: .*checkpoints=", out)
    assert "checkpoint/retry timeline:" in out
    assert "supervisor.restart" in out
    assert "top spans by self-time:" in out

    # machine-readable summary agrees
    summary = summarize(read_spans(trace_dir))
    assert summary["spans"] > 0
    assert any(r["what"] == "supervisor.restart"
               for r in summary["timeline"])
    epochs_seen = {r["epoch"] for r in summary["epochs"]}
    assert 0 in epochs_seen
    assert render_summary(summary)  # renders without throwing

    # the registry snapshot became an artifact; Prometheus dump carries
    # the labeled epoch-duration histogram
    assert trace_cli([trace_dir, "--prometheus"]) == 0
    prom = capsys.readouterr().out
    assert 'epochMs_bucket{mode="host",le="' in prom
    assert "checkpoint_opMs_bucket" in prom
    merged = read_metrics(trace_dir)
    assert merged["ml.iteration"]["histograms"][
        'epochMs{mode="host"}']["count"] >= 6


def test_trace_cli_check_fails_on_empty(tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    assert trace_cli([str(empty), "--check"]) == 2
    assert trace_cli([str(empty)]) == 0  # without --check: benign summary


def test_epoch_histogram_survives_fit(tmp_path):
    """Satellite: per-epoch timings used to collapse into a last-value
    gauge; the labeled histogram must keep every epoch."""
    before = metrics.group("ml", "iteration").histogram(
        "epochMs", labels={"mode": "host"}).snapshot()["count"]
    iterate_bounded(np.float64(0.0), lambda c, e: c + 1, max_iter=5,
                    jit_round=False, config=IterationConfig(mode="host"))
    after = metrics.group("ml", "iteration").histogram(
        "epochMs", labels={"mode": "host"}).snapshot()["count"]
    assert after - before == 5


# -- host-pool fork boundary --------------------------------------------------

def test_hostpool_child_spans_merge(tmp_path, monkeypatch):
    if not hasattr(os, "fork"):
        pytest.skip("no fork on this platform")
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))

    def fn(lo, hi):
        metrics.group("ml", "hostpool_test").counter("shards")
        metrics.group("ml", "hostpool_test").histogram(
            "rows", buckets=(10.0, 1000.0)).observe(hi - lo)
        return hi - lo

    before = metrics.group("ml", "hostpool_test").get_counter("shards")
    out = map_row_shards(fn, 8, workers=2, min_rows=2, shard_cap=4)
    assert out == [4, 4]
    tracer.shutdown()

    spans = read_spans(str(trace_dir))
    parent = [s for s in spans if s["name"] == "hostpool.map"]
    children = [s for s in spans if s["name"] == "hostpool.child"]
    assert len(parent) == 1 and parent[0]["attrs"]["mode"] == "fork"
    assert len(children) == 2
    # child spans live in per-pid files, re-seeded to parent at fork
    assert all(c["parent"] == parent[0]["id"] for c in children)
    assert all(c["trace"] == parent[0]["trace"] for c in children)
    assert all(c["pid"] != parent[0]["pid"] for c in children)
    span_files = [f for f in os.listdir(trace_dir)
                  if f.startswith("spans-")]
    assert len(span_files) == 3  # driver + 2 children

    # child registry snapshots folded into the driver registry
    after = metrics.group("ml", "hostpool_test").get_counter("shards")
    assert after - before == 2
    hist = metrics.group("ml", "hostpool_test").histogram(
        "rows", buckets=(10.0, 1000.0)).snapshot()
    assert hist["count"] >= 2


def test_prometheus_labeled_histograms_across_fork(tmp_path, monkeypatch):
    """Satellite (ISSUE 4): the composition the separate merge and
    grammar tests skip — LABELED histograms observed in forked host-pool
    children must fold into the driver registry and render as one valid
    Prometheus exposition family with the merged counts."""
    if not hasattr(os, "fork"):
        pytest.skip("no fork on this platform")
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    buckets = (1.0, 10.0, 100.0)

    def fn(lo, hi):
        metrics.group("ml", "forkprom").histogram(
            "shardRows", buckets=buckets,
            labels={"site": "child"}).observe(float(hi - lo))
        return hi - lo

    base = metrics.group("ml", "forkprom").histogram(
        "shardRows", buckets=buckets,
        labels={"site": "child"}).snapshot()["count"]
    out = map_row_shards(fn, 8, workers=2, min_rows=2, shard_cap=4)
    assert out == [4, 4]
    tracer.shutdown()

    merged = metrics.group("ml", "forkprom").histogram(
        "shardRows", buckets=buckets,
        labels={"site": "child"}).snapshot()
    assert merged["count"] - base == 2  # both children folded in

    text = prometheus_text(metrics.snapshot())
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line) or _PROM_TYPE.match(line), line
    # the labeled series rendered cumulative under one family, with the
    # +Inf bucket equal to the merged observation count
    inf_line = next(
        ln for ln in text.splitlines()
        if ln.startswith('flink_ml_tpu_ml_forkprom_shardRows_bucket'
                         '{site="child",le="+Inf"}'))
    assert int(inf_line.rsplit(" ", 1)[1]) == merged["count"]
    type_lines = [ln for ln in text.splitlines()
                  if "forkprom_shardRows" in ln and ln.startswith("# TYPE")]
    assert len(type_lines) == 1


def test_hostpool_inline_path_still_counts(monkeypatch):
    def fn(lo, hi):
        metrics.group("ml", "hostpool_inline").counter("shards")
        return hi - lo

    before = metrics.group("ml", "hostpool_inline").get_counter("shards")
    out = map_row_shards(fn, 8, workers=1, min_rows=2)
    assert sum(out) == 8
    after = metrics.group("ml", "hostpool_inline").get_counter("shards")
    assert after > before


# -- model-level golden trace (needs shard_map) -------------------------------

def test_kmeans_supervised_traced_fit_golden(tmp_path, monkeypatch, rng):
    """The ISSUE acceptance run verbatim: KMeans under run_supervised
    with one injected fault, trace armed — nested fit→epoch→checkpoint
    spans, a restart event, and a CLI-renderable per-epoch summary."""
    from flink_ml_tpu.models.clustering import KMeans

    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    x = rng.normal(size=(240, 4)).astype(np.float32)
    table = Table.from_columns(features=x)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    cfg = IterationConfig(mode="host", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    km = (KMeans(k=3, seed=7, max_iter=6)
          .set_iteration_config(cfg)
          .set_retry_policy(RetryPolicy(max_restarts=3, backoff_s=0.0)))
    with faults.chaos(at={"epoch-boundary": [4]}):
        model = km.fit(table)
    assert model.centroids.shape == (3, 4)
    tracer.shutdown()

    spans = read_spans(str(trace_dir))
    by_id = {s["id"]: s for s in spans}
    fit = next(s for s in spans if s["name"] == "KMeans.fit")
    epochs = [s for s in spans if s["name"] == "epoch"]
    saves = [s for s in spans if s["name"] == "checkpoint.save"]
    assert epochs and saves
    # the rounds are driven under the fit's ``lloyd.launch`` span (PR 29)
    launches = {e["parent"] for e in epochs}
    assert all(by_id[l]["name"] == "lloyd.launch"
               and by_id[l]["parent"] == fit["id"] for l in launches)
    assert all(by_id[s["parent"]]["name"] == "epoch" for s in saves)
    assert any(ev["name"] == "supervisor.restart"
               for s in spans for ev in s["events"])
    doc = chrome_trace(str(trace_dir))
    assert any(e["ph"] == "X" and e["name"] == "epoch"
               for e in doc["traceEvents"])


# -- summary subcommand + --json (ISSUE 5 satellite) --------------------------

def test_summary_subcommand_json(traced_supervised_fit, capsys):
    """`flink-ml-tpu-trace summary <dir> --json` — machine-readable
    output for unattended sweeps, no text scraping."""
    trace_dir, _ = traced_supervised_fit
    assert trace_cli(["summary", trace_dir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["spans"] > 0
    assert any(r["what"] == "supervisor.restart" for r in doc["timeline"])
    # the bare-positional legacy spellings keep working
    assert trace_cli([trace_dir, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["spans"] == doc["spans"]
    assert trace_cli(["summary", trace_dir]) == 0
    assert "top spans by self-time:" in capsys.readouterr().out


# -- histogram_quantile edge contracts (ISSUE 5 satellite) --------------------

def test_histogram_quantile_rejects_invalid_q():
    from flink_ml_tpu.common.metrics import histogram_quantile
    snap = {"buckets": [1.0, 10.0], "counts": [1, 2], "sum": 7.0,
            "count": 2}
    for bad in (-0.1, 1.5, float("nan")):
        with pytest.raises(ValueError):
            histogram_quantile(snap, bad)


def test_histogram_quantile_empty_and_bucketless():
    import math as _math

    from flink_ml_tpu.common.metrics import Histogram, histogram_quantile
    assert _math.isnan(histogram_quantile({"count": 0}, 0.5))
    assert _math.isnan(Histogram(buckets=(1.0, 2.0)).quantile(0.5))
    # count present but no buckets: still NaN, never IndexError
    assert _math.isnan(histogram_quantile({"count": 3}, 0.5))


def test_histogram_quantile_q0_q1_and_single_bucket():
    from flink_ml_tpu.common.metrics import Histogram
    h = Histogram(buckets=(5.0,))
    h.observe(3.0)
    h.observe(7.0)  # lands past the last finite bound (+Inf bucket)
    assert h.quantile(0.0) == 0.0  # implicit lower bound
    assert 0.0 < h.quantile(0.5) <= 5.0
    assert h.quantile(1.0) == 5.0  # clamps to the last finite bound
    # q=1 with every observation inside the finite buckets interpolates
    # to the winning bucket's upper bound
    h2 = Histogram(buckets=(1.0, 10.0))
    h2.observe(0.5)
    h2.observe(5.0)
    assert h2.quantile(1.0) == 10.0


# -- Prometheus label-value escaping (ISSUE 5 satellite) ----------------------

def test_prometheus_label_value_escaping():
    r"""Text-format spec: label values escape backslash (\\), newline
    (\n) and double-quote (\") — round-tripped through metric_key and
    rendered verbatim by the exposition."""
    from flink_ml_tpu.common.metrics import metric_key

    assert metric_key("m", {"p": "a\\b"}) == 'm{p="a\\\\b"}'
    assert metric_key("m", {"p": 'say "hi"'}) == 'm{p="say \\"hi\\""}'
    assert metric_key("m", {"p": "l1\nl2"}) == 'm{p="l1\\nl2"}'

    reg = MetricsRegistry()
    g = reg.group("ml", "esc")
    g.counter("hits", labels={"path": 'a\\b"c'})
    g.gauge("v", 1.5, labels={"note": "line1\nline2"})
    g.histogram("h", buckets=(1.0,), labels={"q": '"'}).observe(0.5)
    text = prometheus_text(reg.snapshot())
    assert 'flink_ml_tpu_ml_esc_hits_total{path="a\\\\b\\"c"} 1' in text
    assert 'flink_ml_tpu_ml_esc_v{note="line1\\nline2"} 1.5' in text
    assert 'flink_ml_tpu_ml_esc_h_bucket{q="\\"",le="1"} 1' in text
    # the raw newline never reaches the exposition body (it would split
    # the sample line and break the line-oriented grammar)
    assert "line1\nline2" not in text


# -- health metrics across the host-pool fork (ISSUE 5 satellite) -------------

def test_hostpool_child_health_metrics_merge(tmp_path, monkeypatch):
    """Model-health series recorded in forked host-pool children
    (ml.health histograms, ml.serving envelopes) must fold into the
    driver registry exactly like the systems metrics do."""
    if not hasattr(os, "fork"):
        pytest.skip("no fork on this platform")
    from flink_ml_tpu.observability import health

    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    algo_labels = {"algo": "ForkFit"}
    serve_labels = {"servable": "ForkServable"}
    h_before = metrics.group("ml", "health").histogram(
        "loss", buckets=health.VALUE_BUCKETS,
        labels=algo_labels).snapshot()["count"]
    t_before = metrics.group("ml", "serving").get_counter(
        "transforms", labels=serve_labels)

    def fn(lo, hi):
        health.record_fit_series("ForkFit",
                                 {"loss": [1.0, 0.5], "paramNorm": [1.0, 2.0]})
        health.observe_serving("ForkServable", hi - lo, 1.25,
                               predictions=[0.0, 1.0])
        return hi - lo

    out = map_row_shards(fn, 8, workers=2, min_rows=2, shard_cap=4)
    assert out == [4, 4]
    tracer.shutdown()

    merged = metrics.group("ml", "health").histogram(
        "loss", buckets=health.VALUE_BUCKETS,
        labels=algo_labels).snapshot()
    assert merged["count"] - h_before == 4  # 2 children x 2 epochs
    assert metrics.group("ml", "serving").get_counter(
        "transforms", labels=serve_labels) - t_before == 2
    # gauges last-write-win across the merge; fractions stay sane
    assert metrics.group("ml", "serving").get_gauge(
        "predictionFiniteFraction", labels=serve_labels) == 1.0
    # the children's convergence events reached the trace files too
    spans = read_spans(str(trace_dir))
    conv = [ev for sp in spans for ev in sp.get("events", ())
            if ev.get("name") == health.CONVERGENCE_EVENT]
    assert len(conv) == 4
