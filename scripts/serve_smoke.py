"""CI smoke: live serving telemetry end-to-end (docs/observability.md
"Live telemetry & SLOs").

CPU-only CI harness: pins ``JAX_PLATFORMS=cpu`` (and may start child
processes) — never a chip check. A chip belongs to one process;
``python chip_smoke.py`` is the check that runs there.

Flow: arm the embedded endpoint (``FLINK_ML_TPU_METRICS_PORT=0`` — an
ephemeral port read back from the server) and a trace dir, build a
logistic-regression servable, drive N requests through the serving
load generator (serving/loadgen.py — the one request-driving code
path shared with scripts/serve_bench.py) — a second loadgen run issues
malformed frames so the error path runs — while scraping ``/metrics``
(must be valid Prometheus text with the windowed serving families),
``/slo`` (must be JSON verdicts evaluated over sliding windows),
``/healthz`` and ``/spans/recent`` (must hold sampled
``serving.request`` spans) from the RUNNING process. Then gate the
dumped artifacts the way CI consumes them: ``flink-ml-tpu-trace slo
--check`` must exit 4 against a deliberately tight spec and 0 against a
satisfied one, and ``--latest`` must resolve the trace dir from its
parent root.

Exit codes: 0 all good; 1 an assertion failed; 2 environment broken
(endpoint would not arm).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = tempfile.mkdtemp(prefix="serve-smoke-")
TRACE_DIR = os.path.join(ROOT, "trace-1")
os.environ["FLINK_ML_TPU_TRACE_DIR"] = TRACE_DIR
os.environ["FLINK_ML_TPU_METRICS_PORT"] = "0"
os.environ.setdefault("FLINK_ML_TPU_TRACE_SAMPLE", "1.0")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from flink_ml_tpu.linalg.vectors import DenseVector  # noqa: E402
from flink_ml_tpu.observability import server, slo, tracing  # noqa: E402
from flink_ml_tpu.observability.exporters import dump_metrics  # noqa: E402
from flink_ml_tpu.servable.api import (  # noqa: E402
    DataFrame,
    DataTypes,
    Row,
)
from flink_ml_tpu.servable.lr import (  # noqa: E402
    LogisticRegressionModelData,
    LogisticRegressionModelServable,
)

N_OK = 40
N_ERR = 6
ROWS = 16


def fail(code: int, message: str) -> "NoReturn":  # noqa: F821
    print(f"serve_smoke: FAIL — {message}", file=sys.stderr)
    raise SystemExit(code)


def fetch(port: int, route: str) -> bytes:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{route}", timeout=10) as resp:
        return resp.read()


def main() -> int:
    from flink_ml_tpu.utils import compile_cache

    compile_cache.configure()
    # a small traced fit first: the stage seam must arm the endpoint
    # and the scraped /metrics must carry fit telemetry beside serving
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.iteration.iteration import IterationConfig
    from flink_ml_tpu.models.clustering import KMeans

    x = np.random.default_rng(0).normal(size=(240, 4)).astype(np.float32)
    KMeans(k=3, seed=7, max_iter=4).set_iteration_config(
        IterationConfig(mode="host")).fit(Table.from_columns(features=x))

    servable = LogisticRegressionModelServable().set_model_data(
        LogisticRegressionModelData(
            np.array([0.5, -0.25, 0.1])).encode())
    seed = [0]

    def frame() -> DataFrame:
        # fresh Generator per frame: built on concurrent loadgen workers
        seed[0] += 1
        rng = np.random.default_rng(seed[0])
        return DataFrame(
            ["features"], [DataTypes.vector()],
            [Row([DenseVector(rng.normal(size=3))])
             for _ in range(ROWS)])

    # the first transform lazily arms the endpoint; the remaining
    # requests drive through the serving loadgen, scraping WHILE it
    # serves via the per-completion tick hook
    servable.transform(frame())
    srv = server.maybe_start()
    if srv is None:
        fail(2, "telemetry endpoint did not arm "
                "(FLINK_ML_TPU_METRICS_PORT=0)")
    port = srv.port

    # ticks run on loadgen worker threads, where a raised SystemExit
    # would be silently swallowed — collect, assert after the run
    scrape_failures = []

    def scrape_tick(i: int) -> None:
        if i % 10 == 5:
            text = fetch(port, "/metrics").decode("utf-8")
            if "flink_ml_tpu_ml_serving_transformMs_bucket" not in text:
                scrape_failures.append(
                    f"/metrics missing the serving latency histogram "
                    f"at request {i}")

    from flink_ml_tpu.serving import LoadGenConfig, run_loadgen

    res = run_loadgen(servable.transform, lambda i: frame(),
                      LoadGenConfig(mode="closed", requests=N_OK - 1,
                                    concurrency=4),
                      tick=scrape_tick)
    if scrape_failures:
        fail(1, scrape_failures[0])
    if res["ok"] != N_OK - 1 or res["errors"] or res["rejected"]:
        fail(1, f"loadgen run not clean: {res}")
    print(f"serve_smoke: endpoint on 127.0.0.1:{port}, {N_OK} requests "
          f"served at {res['throughput_rps']} rps "
          f"(p99 {res['latency_ms']['p99']} ms)")

    def bad_frame(i: int) -> DataFrame:
        return DataFrame(["wrong"], [DataTypes.vector()],
                         [Row([DenseVector([1.0, 2.0, 3.0])])])

    res_bad = run_loadgen(servable.transform, bad_frame,
                          LoadGenConfig(mode="closed", requests=N_ERR,
                                        concurrency=2))
    if res_bad["errors"] != N_ERR \
            or res_bad["errorsByClass"] != {"ValueError": N_ERR}:
        fail(1, f"malformed requests were not all counted as "
                f"ValueError: {res_bad}")

    text = fetch(port, "/metrics").decode("utf-8")
    for needle in (
            "flink_ml_tpu_ml_serving_transformMs_bucket",
            "flink_ml_tpu_ml_serving_transforms_total",
            "flink_ml_tpu_ml_serving_errors_total",
            'exception="ValueError"',
            "flink_ml_tpu_ml_serving_inFlight",
            "flink_ml_tpu_ml_iteration_epochMs_bucket"):
        if needle not in text:
            fail(1, f"/metrics is missing {needle!r}")

    live = json.loads(fetch(port, "/slo"))
    if live.get("source") != "windowed" or not live.get("verdicts"):
        fail(1, f"/slo returned no windowed verdicts: {live}")
    print("serve_smoke: /slo verdicts "
          + ", ".join(f"{v['slo']}={'ok' if v['ok'] else 'VIOLATED'}"
                      for v in live["verdicts"]))

    hz = json.loads(fetch(port, "/healthz"))
    if hz.get("status") != "ok" or hz.get("pid") != os.getpid():
        fail(1, f"/healthz looks wrong: {hz}")

    # no serving runtime in this smoke: the route must say so, not 404
    # (the populated form is exercised by scripts/serve_bench.py)
    sv = json.loads(fetch(port, "/serving"))
    if sv != {"serving": None}:
        fail(1, f"/serving without a runtime should be null: {sv}")

    spans = json.loads(fetch(port, "/spans/recent"))["spans"]
    if not any(s.get("name") == "serving.request" for s in spans):
        fail(1, "no sampled serving.request spans in /spans/recent")

    # -- artifact gate: the way CI consumes a finished run ------------------
    tracing.tracer.shutdown()
    dump_metrics(TRACE_DIR)
    tight_spec = os.path.join(ROOT, "tight.json")
    with open(tight_spec, "w", encoding="utf-8") as f:
        json.dump({"slos": [
            {"name": "impossible-latency", "kind": "latency",
             "quantile": 0.5, "threshold_ms": 1e-7}]}, f)
    loose_spec = os.path.join(ROOT, "loose.json")
    with open(loose_spec, "w", encoding="utf-8") as f:
        json.dump({"slos": [
            {"name": "satisfied-latency", "kind": "latency",
             "quantile": 0.99, "threshold_ms": 1e9},
            {"name": "tolerated-errors", "kind": "error-rate",
             "max_error_ratio": 0.99}]}, f)

    rc_tight = slo.main([TRACE_DIR, "--spec", tight_spec, "--check"])
    if rc_tight != 4:
        fail(1, f"slo --check on a violated spec exited {rc_tight}, "
                "expected 4")
    rc_loose = slo.main([ROOT, "--latest", "--spec", loose_spec,
                         "--check"])
    if rc_loose != 0:
        fail(1, f"slo --check --latest on a satisfied spec exited "
                f"{rc_loose}, expected 0")

    # when CI arms the lock watchdog, the smoke self-gates its own lock
    # discipline: dump_metrics above left locks-*.json beside the spans,
    # and a cycle or long hold in the serving path must fail here
    if os.environ.get("FLINK_ML_TPU_LOCKCHECK"):
        from flink_ml_tpu.observability import lockstats

        rc_locks = lockstats.main([TRACE_DIR, "--check"])
        if rc_locks != 0:
            fail(1, f"locks --check exited {rc_locks}, expected 0 "
                    "(lock-order cycle, long hold, or missing lock "
                    "telemetry in the smoke)")

    print("serve_smoke: OK — /metrics + /slo live, error path counted, "
          "slo --check gates 4/0")
    return 0


if __name__ == "__main__":
    sys.exit(main())
