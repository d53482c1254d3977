"""Live serving telemetry (ISSUE 7): windowed metrics, the SLO engine,
the embedded HTTP endpoint, request-scoped trace sampling, and the
``flink-ml-tpu-trace slo`` / ``--latest`` CLI surface.

Acceptance bar: windowed p99 must diverge from the cumulative quantile
after a latency shift inside one horizon; ``/metrics`` must serve valid
Prometheus text and ``/slo`` JSON verdicts from a *running* process;
``mltrace slo --check`` exits 4 on a violated spec and 0 on a satisfied
one; child serving metrics must merge into the driver's windowed view.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flink_ml_tpu.common.hostpool import map_row_shards
from flink_ml_tpu.common.metrics import (
    MetricsRegistry,
    WindowedHistogram,
    metrics,
)
from flink_ml_tpu.observability import health, server, slo, tracing
from flink_ml_tpu.observability.cli import main as trace_cli
from flink_ml_tpu.observability.exporters import (
    dump_metrics,
    latest_trace_dir,
    prometheus_text,
    resolve_trace_dir,
)
from flink_ml_tpu.observability.tracing import TRACE_DIR_ENV, tracer
from flink_ml_tpu.servable.api import (
    DataFrame,
    DataTypes,
    Row,
    TransformerServable,
)

# grammar regexes shared with test_observability's Prometheus checks
import re

_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*='
    r'"(?:[^"\\]|\\.)*"(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
    r'(?:[+-]?(?:\d+(?:\.\d+)?(?:e[+-]?\d+)?|Inf)|NaN)$')
_PROM_TYPE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                        r"(gauge|counter|histogram)$")


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    """Tracer, endpoint, and sampling env must not leak across tests —
    the singletons are process-wide."""
    for var in (TRACE_DIR_ENV, health.SAMPLE_ENV,
                server.METRICS_PORT_ENV, slo.SLO_SPEC_ENV):
        monkeypatch.delenv(var, raising=False)
    server.stop()
    tracer.recent.clear()
    yield
    server.stop()
    tracer.shutdown()
    tracer.recent.clear()


class _FakeClock:
    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now


class _EchoServable(TransformerServable):
    """Minimal servable: echoes the frame, adds a prediction column;
    ``fail`` raises instead (the error-path fixture)."""

    prediction_col = "pred"

    def __init__(self, fail=False):
        self.fail = fail

    def transform(self, df):
        if self.fail:
            raise RuntimeError("injected serving failure")
        df.add_column("pred", DataTypes.DOUBLE,
                      [0.5] * df.num_rows())
        return df


def _frame(rows=4):
    return DataFrame(["x"], [DataTypes.DOUBLE],
                     [Row([float(i)]) for i in range(rows)])


# -- windowed metrics ---------------------------------------------------------

def test_windowed_p99_diverges_from_cumulative_after_latency_shift():
    """The ISSUE acceptance demonstration: 10k fast observations age
    out of the horizon, 50 slow ones land inside it — the cumulative
    p99 stays fast while the windowed p99 reports the shift."""
    clock = _FakeClock()
    h = WindowedHistogram(buckets=(5.0, 50.0, 500.0), horizon_s=60.0,
                          slices=12, clock=clock)
    for _ in range(10000):
        h.observe(2.0)
    clock.now = 100.0  # > horizon: the fast traffic is out of window
    for _ in range(50):
        h.observe(400.0)
    cumulative_p99 = h.quantile(0.99)
    windowed_p99 = h.window_quantile(0.99)
    assert cumulative_p99 <= 5.0  # dominated by the 10k fast samples
    assert windowed_p99 > 50.0    # the window holds only the slow ones
    win = h.window_snapshot()
    assert win["count"] == 50
    # the cumulative view is untouched by the window machinery
    assert h.snapshot()["count"] == 10050


def test_windowed_histogram_dormant_observations_age_out():
    clock = _FakeClock()
    h = WindowedHistogram(buckets=(5.0,), horizon_s=60.0, slices=12,
                          clock=clock)
    h.observe(1.0)
    clock.now = 1000.0
    assert h.window_snapshot()["count"] == 0
    assert h.snapshot()["count"] == 1
    assert h.window_rate() == 0.0


def test_windowed_histogram_merge_lands_in_current_window():
    clock = _FakeClock()
    h = WindowedHistogram(buckets=(5.0, 50.0), horizon_s=60.0,
                          slices=12, clock=clock)
    h.observe(1.0)
    clock.now = 120.0  # the live observation ages out ...
    h.merge_snapshot({"buckets": [5.0, 50.0], "counts": [0, 3],
                      "sum": 60.0, "count": 3})
    # ... but the merged child counts are window-visible at merge time
    assert h.window_snapshot()["count"] == 3
    assert h.snapshot()["count"] == 4


def test_windowed_counter_window_delta_and_rate():
    reg = MetricsRegistry()
    g = reg.group("ml", "wc")
    clock = _FakeClock(1000.0)
    wc = g.windowed_counter("reqs", horizon_s=60.0, slices=12)
    wc._clock = clock
    wc._t0 = wc._last_slice = 1000.0
    for _ in range(6):
        wc.inc()
    clock.now = 1030.0
    assert wc.value == 6
    assert wc.window_delta(60.0) == 6
    assert wc.window_rate(60.0) > 0.0
    # the plain counter is the single cumulative source of truth
    assert g.get_counter("reqs") == 6
    clock.now = 2000.0
    assert wc.window_delta(60.0) == 0
    assert wc.value == 6


def test_windowed_histogram_concurrent_observe_snapshot_stress():
    """Satellite: 8 threads hammering observe + window/cumulative reads
    with live slice rotation must neither crash nor lose counts."""
    h = WindowedHistogram(buckets=(1.0, 10.0, 100.0), horizon_s=0.4,
                          slices=8)
    errors = []
    n_writers, per_writer = 4, 2000

    def writer():
        try:
            for i in range(per_writer):
                h.observe(float(i % 120))
        except Exception as e:  # noqa: BLE001 — collected for assert
            errors.append(e)

    def reader():
        try:
            for _ in range(400):
                win = h.window_snapshot()
                assert all(c >= 0 for c in win["counts"])
                assert win["count"] >= 0
                h.window_quantile(0.99)
                snap = h.snapshot()
                assert snap["count"] <= n_writers * per_writer
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = ([threading.Thread(target=writer) for _ in range(4)]
               + [threading.Thread(target=reader) for _ in range(4)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert h.snapshot()["count"] == n_writers * per_writer


def test_windowed_metrics_prometheus_exposition():
    """Satellite: windowed metrics must render as plain cumulative
    families — same grammar, same values — so scrapers cannot tell the
    difference."""
    reg = MetricsRegistry()
    g = reg.group("ml", "winprom")
    g.windowed_histogram("latencyMs", buckets=(1.0, 10.0),
                         labels={"servable": "X"}).observe(5.0)
    g.windowed_counter("requests", labels={"servable": "X"}).inc(3)
    text = prometheus_text(reg.snapshot())
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line) or _PROM_TYPE.match(line), line
    assert ('flink_ml_tpu_ml_winprom_latencyMs_bucket'
            '{servable="X",le="10"} 1') in text
    assert 'flink_ml_tpu_ml_winprom_requests_total{servable="X"} 3' \
        in text


# -- merge validation (satellite bugfix) --------------------------------------

def test_merge_rejects_short_counts_whole():
    """Regression: matching bucket bounds with a short counts array
    used to fold PARTIALLY and silently; now the whole snapshot is
    rejected and the registry is untouched."""
    driver = MetricsRegistry()
    driver.group("ml").histogram("ms", buckets=(1.0, 2.0, 3.0)) \
        .observe(0.5)
    driver.group("ml").counter("rows", 1)
    snap = {"ml": {"counters": {"rows": 7},
                   "histograms": {"ms": {"buckets": [1.0, 2.0, 3.0],
                                         "counts": [1],
                                         "sum": 1.0, "count": 1}}}}
    with pytest.raises(ValueError, match="bucket layout mismatch"):
        driver.merge(snap)
    assert driver.group("ml").get_counter("rows") == 1
    assert driver.group("ml").histogram(
        "ms", buckets=(1.0, 2.0, 3.0)).snapshot()["counts"] == [1, 1, 1]


def test_merge_rejects_junk_counts_values_whole():
    """Review regression: a count value that only int() can reject must
    fail validation BEFORE the fold (it used to blow up mid-merge,
    leaving the histogram partially folded), and a snapshot missing
    sum/count merges as zeros instead of escaping with a KeyError."""
    driver = MetricsRegistry()
    driver.group("ml").histogram("ms", buckets=(1.0, 2.0, 3.0)) \
        .observe(0.5)
    junk = {"ml": {"histograms": {"ms": {
        "buckets": [1.0, 2.0, 3.0], "counts": [1, "x", 3],
        "sum": 1.0, "count": 1}}}}
    with pytest.raises(ValueError, match="non-numeric"):
        driver.merge(junk)
    assert driver.group("ml").histogram(
        "ms", buckets=(1.0, 2.0, 3.0)).snapshot()["counts"] == [1, 1, 1]
    no_sum = {"ml": {"histograms": {"ms": {
        "buckets": [1.0, 2.0, 3.0], "counts": [0, 1, 1]}}}}
    driver.merge(no_sum)  # tolerated: sum/count default to zero
    snap = driver.group("ml").histogram(
        "ms", buckets=(1.0, 2.0, 3.0)).snapshot()
    assert snap["counts"] == [1, 2, 2]


def test_windowed_counter_excludes_preexisting_counts():
    """Review regression: a counter that already holds counts when its
    windowed view is created (e.g. a child snapshot merged before the
    driver's first request) must NOT report them as in-window."""
    reg = MetricsRegistry()
    g = reg.group("ml", "serving")
    g.counter("errors", 5, labels={"servable": "X"})
    wc = g.windowed_counter("errors", horizon_s=60.0,
                            labels={"servable": "X"})
    assert wc.window_delta(60.0) == 0
    assert wc.window_rate(60.0) == 0.0
    wc.inc()
    assert wc.window_delta(60.0) == 1
    assert wc.value == 6


def test_merge_rejects_long_counts_and_unsorted_buckets():
    driver = MetricsRegistry()
    driver.group("ml").histogram("ms", buckets=(1.0, 2.0)).observe(0.5)
    long_counts = {"ml": {"histograms": {
        "ms": {"buckets": [1.0, 2.0], "counts": [1, 1, 9],
               "sum": 1.0, "count": 1}}}}
    with pytest.raises(ValueError, match="bucket layout mismatch"):
        driver.merge(long_counts)
    # a NEW histogram with unsorted bounds must be rejected before it
    # is created (Histogram would silently re-sort, misaligning counts)
    unsorted = {"ml": {"histograms": {
        "fresh": {"buckets": [5.0, 1.0], "counts": [1, 2],
                  "sum": 6.0, "count": 3}}}}
    with pytest.raises(ValueError, match="unsorted"):
        driver.merge(unsorted)
    assert "fresh" not in driver.snapshot()["ml"]["histograms"]


# -- fork boundary: windowed view ---------------------------------------------

def test_child_serving_metrics_merge_into_driver_windowed_view():
    """Satellite: serving metrics recorded in forked host-pool children
    must fold into the DRIVER's windowed view — window quantiles and
    counter deltas include the children right after the map returns."""
    if not hasattr(os, "fork"):
        pytest.skip("no fork on this platform")
    name = "ForkWindowServable"
    labels = {"servable": name}
    health.observe_serving(name, 4, 1.0)
    group = metrics.group("ml", "serving")
    wh = group.windowed_histogram("transformMs", labels=labels)
    assert isinstance(wh, WindowedHistogram)
    before = wh.window_snapshot()["count"]
    wc = group.windowed_counter("transforms", labels=labels)
    delta_before = wc.window_delta()

    def fn(lo, hi):
        health.observe_serving(name, hi - lo, 2.0)
        return hi - lo

    out = map_row_shards(fn, 8, workers=2, min_rows=2, shard_cap=4)
    assert out == [4, 4]
    after = wh.window_snapshot()
    assert after["count"] - before == 2
    assert wc.window_delta() - delta_before == 2
    # cumulative view folded identically
    assert wh.snapshot()["count"] >= after["count"]


# -- SLO engine ---------------------------------------------------------------

def test_slo_spec_json_round_trip(tmp_path):
    spec = tmp_path / "slo.json"
    spec.write_text(json.dumps({"slos": [
        {"name": "lat", "kind": "latency", "quantile": 0.9,
         "threshold_ms": 50.0, "labels": {"servable": "X"}},
        {"name": "err", "kind": "error-rate",
         "max_error_ratio": 0.05}]}))
    specs = slo.load_specs(str(spec))
    assert [s.name for s in specs] == ["lat", "err"]
    assert specs[0].labels == {"servable": "X"}
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"slos": [
        {"name": "x", "kind": "latency", "nope": 1}]}))
    with pytest.raises(ValueError, match="unknown spec key"):
        slo.load_specs(str(bad))
    with pytest.raises(ValueError, match="unknown kind"):
        slo.SLO(name="x", kind="availability")


def test_slo_spec_toml(tmp_path):
    spec = tmp_path / "slo.toml"
    spec.write_text('[[slos]]\nname = "lat"\nkind = "latency"\n'
                    'threshold_ms = 50.0\n')
    specs = slo.load_specs(str(spec))
    assert specs[0].name == "lat"
    assert specs[0].threshold_ms == 50.0


def test_slo_latency_violation_and_burn_rate():
    reg = MetricsRegistry()
    wh = reg.group("ml", "serving").windowed_histogram(
        "transformMs", labels={"servable": "S"})
    for _ in range(100):
        wh.observe(400.0)
    spec = slo.SLO(name="lat", kind="latency", quantile=0.99,
                   threshold_ms=100.0)
    (verdict,) = slo.evaluate_slos([spec], registry=reg)
    assert not verdict["ok"]
    primary = verdict["objectives"][0]
    assert primary["objective"] == "latency-quantile"
    assert primary["source"] == "windowed"
    assert primary["samples"] == 100
    assert primary["value_ms"] > 100.0
    burns = [o for o in verdict["objectives"]
             if o["objective"] == "latency-burn"]
    assert burns
    # every request blows the budget: burn = 1.0 / 0.01 = 100x
    assert all(b["burn_rate"] > b["max_burn_rate"] for b in burns)
    assert all(not b["ok"] for b in burns)

    ok_spec = slo.SLO(name="lat-ok", kind="latency", quantile=0.99,
                      threshold_ms=1e9)
    (ok_verdict,) = slo.evaluate_slos([ok_spec], registry=reg)
    assert ok_verdict["ok"]


def test_slo_error_rate_windowed():
    reg = MetricsRegistry()
    g = reg.group("ml", "serving")
    g.windowed_counter("transforms", labels={"servable": "S"}).inc(90)
    g.windowed_counter("errors", labels={"servable": "S"}).inc(10)
    tight = slo.SLO(name="err", kind="error-rate",
                    max_error_ratio=0.05)
    loose = slo.SLO(name="err-ok", kind="error-rate",
                    max_error_ratio=0.5)
    bad, good = slo.evaluate_slos([tight, loose], registry=reg)
    assert not bad["ok"] and good["ok"]
    primary = bad["objectives"][0]
    assert primary["objective"] == "error-ratio"
    assert primary["value"] == pytest.approx(0.1)
    assert primary["source"] == "windowed"
    burns = [o for o in bad["objectives"]
             if o["objective"] == "error-burn"]
    # burn = 0.1 / 0.05 = 2x: under the default 14.4x/6x gates
    assert burns and all(b["ok"] for b in burns)


def test_slo_empty_series_passes_vacuously():
    reg = MetricsRegistry()
    verdicts = slo.evaluate_slos(slo.default_slos(), registry=reg)
    assert all(v["ok"] for v in verdicts)
    assert verdicts[0]["objectives"][0]["samples"] == 0


def test_slo_emit_counters_and_event(tmp_path, monkeypatch):
    trace_dir = tmp_path / "trace"
    monkeypatch.setenv(TRACE_DIR_ENV, str(trace_dir))
    reg = MetricsRegistry()
    wh = reg.group("ml", "serving").windowed_histogram(
        "transformMs", labels={"servable": "S"})
    wh.observe(500.0)
    spec = slo.SLO(name="emit-me", kind="latency", quantile=0.5,
                   threshold_ms=1.0)
    before = metrics.group("ml", "slo").get_counter(
        "slo_violations", labels={"slo": "emit-me"})
    slo.evaluate_slos([spec], registry=reg, emit=True)
    assert metrics.group("ml", "slo").get_counter(
        "slo_violations", labels={"slo": "emit-me"}) == before + 1
    tracer.shutdown()
    from flink_ml_tpu.observability.exporters import read_spans

    events = [ev for sp in read_spans(str(trace_dir))
              for ev in sp.get("events", ())
              if ev.get("name") == slo.SLO_EVENT]
    assert events and events[0]["attrs"]["slo"] == "emit-me"


def test_slo_cli_exit_codes(tmp_path, capsys):
    """Acceptance: `mltrace slo --check` exits 4 on a violated spec, 0
    on a satisfied one, 2 on broken artifacts or a broken spec."""
    reg = MetricsRegistry()
    g = reg.group("ml", "serving")
    h = g.histogram("transformMs", labels={"servable": "S"})
    for _ in range(50):
        h.observe(100.0)
    g.counter("transforms", 50, labels={"servable": "S"})
    trace = tmp_path / "trace"
    dump_metrics(str(trace), reg)

    tight = tmp_path / "tight.json"
    tight.write_text(json.dumps({"slos": [
        {"name": "tight", "kind": "latency", "quantile": 0.5,
         "threshold_ms": 0.001}]}))
    loose = tmp_path / "loose.json"
    loose.write_text(json.dumps({"slos": [
        {"name": "loose", "kind": "latency", "quantile": 0.99,
         "threshold_ms": 1e9},
        {"name": "errs", "kind": "error-rate",
         "max_error_ratio": 0.99}]}))

    assert slo.main([str(trace), "--spec", str(tight),
                     "--check"]) == 4
    assert slo.main([str(trace), "--spec", str(loose),
                     "--check"]) == 0
    # report-only never gates
    assert slo.main([str(trace), "--spec", str(tight)]) == 0
    capsys.readouterr()
    assert slo.main([str(trace), "--spec", str(loose), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["source"] == "cumulative"
    assert {v["slo"] for v in doc["verdicts"]} == {"loose", "errs"}
    # artifact evaluation is tagged cumulative on every objective
    assert all(o["source"] == "cumulative"
               for v in doc["verdicts"] for o in v["objectives"])

    empty = tmp_path / "empty"
    empty.mkdir()
    assert slo.main([str(empty), "--check"]) == 2
    badspec = tmp_path / "bad.json"
    badspec.write_text("{not json")
    assert slo.main([str(trace), "--spec", str(badspec)]) == 2
    # the cli dispatcher reaches the subcommand
    assert trace_cli(["slo", str(trace), "--spec", str(loose),
                      "--check"]) == 0


# -- serving seam: sampling, errors, in-flight --------------------------------

def test_trace_sampling_controls_request_spans(monkeypatch):
    tracer.keep_recent = True
    try:
        monkeypatch.setenv(health.SAMPLE_ENV, "0")
        _EchoServable().transform(_frame())
        assert not any(r["name"] == "serving.request"
                       for r in tracer.recent)
        monkeypatch.setenv(health.SAMPLE_ENV, "1")
        _EchoServable().transform(_frame(3))
        reqs = [r for r in tracer.recent
                if r["name"] == "serving.request"]
        assert reqs and reqs[-1]["attrs"]["rows_in"] == 3
        assert reqs[-1]["attrs"]["servable"] == "_EchoServable"
    finally:
        tracer.keep_recent = False
        tracer.recent.clear()


def test_trace_sample_rate_parsing(monkeypatch):
    assert health.trace_sample_rate() == 1.0
    monkeypatch.setenv(health.SAMPLE_ENV, "0.25")
    assert health.trace_sample_rate() == 0.25
    monkeypatch.setenv(health.SAMPLE_ENV, "7")
    assert health.trace_sample_rate() == 1.0
    monkeypatch.setenv(health.SAMPLE_ENV, "junk")
    assert health.trace_sample_rate() == 1.0


def test_serving_errors_counted_and_inflight_returns_to_zero():
    group = metrics.group("ml", "serving")
    labels = {"servable": "_EchoServable"}
    errors_before = group.get_counter("errors", labels=labels)
    by_class_before = group.get_counter(
        "errorsByClass", labels={"servable": "_EchoServable",
                                 "exception": "RuntimeError"})
    with pytest.raises(RuntimeError, match="injected"):
        _EchoServable(fail=True).transform(_frame())
    assert group.get_counter("errors", labels=labels) \
        == errors_before + 1
    assert group.get_counter(
        "errorsByClass", labels={"servable": "_EchoServable",
                                 "exception": "RuntimeError"}) \
        == by_class_before + 1
    assert group.get_gauge("inFlight", labels=labels) == 0
    # the windowed error counter feeds the SLO engine immediately
    wc = group.windowed_counter("errors", labels=labels)
    assert wc.window_delta() >= 1


def test_serving_success_records_windowed_series():
    _EchoServable().transform(_frame(5))
    group = metrics.group("ml", "serving")
    labels = {"servable": "_EchoServable"}
    wh = group.windowed_histogram("transformMs", labels=labels)
    assert isinstance(wh, WindowedHistogram)
    assert wh.window_snapshot()["count"] >= 1
    assert group.windowed_counter(
        "transforms", labels=labels).window_delta() >= 1
    assert group.get_gauge("predictionMean", labels=labels) == 0.5


# -- the live endpoint --------------------------------------------------------

def _fetch(port, route):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{route}", timeout=10) as resp:
        return resp.read().decode("utf-8"), resp.headers

def test_endpoint_serves_metrics_slo_health_spans(monkeypatch):
    monkeypatch.setenv(server.METRICS_PORT_ENV, "0")
    srv = server.maybe_start()
    assert srv is not None and srv.port > 0
    # idempotent: the second call returns the same server
    assert server.maybe_start() is srv
    _EchoServable().transform(_frame(4))

    text, headers = _fetch(srv.port, "/metrics")
    assert headers["Content-Type"].startswith("text/plain")
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line) or _PROM_TYPE.match(line), line
    assert "flink_ml_tpu_ml_serving_transformMs_bucket" in text

    body, _ = _fetch(srv.port, "/healthz")
    hz = json.loads(body)
    assert hz["status"] == "ok" and hz["pid"] == os.getpid()

    body, _ = _fetch(srv.port, "/slo")
    live = json.loads(body)
    assert live["source"] == "windowed"
    assert {v["slo"] for v in live["verdicts"]} \
        == {s.name for s in slo.default_slos()}

    body, _ = _fetch(srv.port, "/spans/recent")
    spans = json.loads(body)["spans"]
    assert any(s["name"] == "serving.request" for s in spans)

    with pytest.raises(urllib.error.HTTPError) as exc:
        _fetch(srv.port, "/nope")
    assert exc.value.code == 404


def test_endpoint_bad_port_latches_off_without_raising(monkeypatch):
    """Review regression: an out-of-range port (OverflowError, not
    OSError) must latch the endpoint off — the stage/servable seams
    call maybe_start unguarded on every fit."""
    monkeypatch.setenv(server.METRICS_PORT_ENV, "70000")
    assert server.maybe_start() is None
    assert server.maybe_start() is None  # latched: no retry, no raise
    _EchoServable().transform(_frame())  # the seam survives too
    server.stop()
    monkeypatch.setenv(server.METRICS_PORT_ENV, "not-a-port")
    assert server.maybe_start() is None


def test_endpoint_unarmed_and_driver_only(monkeypatch):
    assert server.maybe_start() is None  # no env, no port argument
    monkeypatch.setenv(server.METRICS_PORT_ENV, "0")
    # a forked child (different pid than the module owner) must refuse
    monkeypatch.setattr(server, "_owner_pid", os.getpid() + 1)
    assert server.maybe_start() is None
    monkeypatch.setattr(server, "_owner_pid", os.getpid())
    srv = server.maybe_start()
    assert srv is not None
    # reseed_child latches the endpoint shut (the hostpool fork path)
    monkeypatch.setattr(server, "_owner_pid", os.getpid())
    server.reseed_child()
    assert server.maybe_start() is None
    server.stop()  # un-latch for the next test


def test_endpoint_slo_env_spec(monkeypatch, tmp_path):
    spec = tmp_path / "slo.json"
    spec.write_text(json.dumps({"slos": [
        {"name": "custom", "kind": "latency", "quantile": 0.5,
         "threshold_ms": 1e9}]}))
    monkeypatch.setenv(slo.SLO_SPEC_ENV, str(spec))
    monkeypatch.setenv(server.METRICS_PORT_ENV, "0")
    srv = server.maybe_start()
    body, _ = _fetch(srv.port, "/slo")
    verdicts = json.loads(body)["verdicts"]
    assert [v["slo"] for v in verdicts] == ["custom"]


# -- --latest resolver --------------------------------------------------------

_SPAN_LINE = json.dumps({"type": "span", "name": "fit", "trace": "t",
                         "id": "1", "parent": None, "ts_us": 1,
                         "dur_us": 5, "pid": 1, "tid": 1, "attrs": {},
                         "events": []}) + "\n"


def test_latest_trace_dir_picks_newest(tmp_path):
    old = tmp_path / "trace-old"
    new = tmp_path / "trace-new"
    for d in (old, new):
        d.mkdir()
        (d / "spans-1.jsonl").write_text(_SPAN_LINE)
    past = time.time() - 3600
    os.utime(old / "spans-1.jsonl", (past, past))
    assert latest_trace_dir(str(tmp_path)) == str(new)
    assert resolve_trace_dir(str(tmp_path), latest=True) == str(new)
    # without --latest the path passes through untouched
    assert resolve_trace_dir(str(tmp_path)) == str(tmp_path)
    # a root with artifacts of its own can win too
    (tmp_path / "metrics-1.json").write_text("{}")
    assert latest_trace_dir(str(tmp_path)) == str(tmp_path)
    empty = tmp_path / "nothing"
    empty.mkdir()
    with pytest.raises(FileNotFoundError):
        resolve_trace_dir(str(empty), latest=True)


def test_cli_subcommands_accept_latest(tmp_path, capsys):
    root = tmp_path / "runs"
    trace = root / "trace-1"
    trace.mkdir(parents=True)
    (trace / "spans-1.jsonl").write_text(_SPAN_LINE)
    assert trace_cli([str(root), "--latest", "--json", "--check"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["spans"] == 1
    # an artifact-less root exits 2, the broken-artifacts class
    empty = tmp_path / "empty"
    empty.mkdir()
    assert trace_cli([str(empty), "--latest"]) == 2
    capsys.readouterr()
    reg = MetricsRegistry()
    reg.group("ml", "serving").counter("transforms", 1,
                                       labels={"servable": "S"})
    dump_metrics(str(trace), reg)
    assert slo.main([str(root), "--latest"]) == 0
    from flink_ml_tpu.observability.health import main as health_main

    assert health_main([str(root), "--latest"]) == 0
