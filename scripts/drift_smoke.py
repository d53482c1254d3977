"""CI smoke: drift detection end-to-end (docs/observability.md "Drift
detection").

CPU-only CI harness: pins ``JAX_PLATFORMS=cpu`` (and may start child
processes) — never a chip check. A chip belongs to one process;
``python chip_smoke.py`` is the check that runs there.

Flow: train an LR model with the FTRL online path under a trace dir
(the traced-fit seam captures the training-time drift baseline),
publish it WITH the baseline into a model-registry watch dir, build the
serving runtime (registry → micro-batcher → AOT warmup), then drive two
loadgen phases through the batcher:

1. **clean** — requests drawn from the training distribution against
   ``lr@v1``; the artifacts dumped after this phase must pass
   ``flink-ml-tpu-trace drift --check`` (exit 0);
2. **shifted** — hot-swap to ``lr@v2`` (proving the per-version
   baseline install), then requests with a mean-shifted feature
   distribution; the artifacts dumped after this phase must FAIL the
   gate (exit 4), the ``ml.drift`` events must be in the trace, and the
   clean ``lr@v1`` series must still read ok — the drifted verdict is
   pinned to the version that saw the shifted traffic.

Also scrapes the live ``/drift`` route mid-run (must report the same
verdicts the artifacts later gate on).

Exit codes: 0 all good; 1 an assertion failed; 2 environment broken.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def fail(code: int, message: str):
    print(f"drift_smoke: FAIL — {message}", file=sys.stderr)
    raise SystemExit(code)


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=None,
                        help="artifact root (default: a temp dir; CI "
                             "points this at an uploadable path)")
    parser.add_argument("--requests", type=int, default=240)
    parser.add_argument("--dim", type=int, default=8)
    args = parser.parse_args(argv)

    root = args.root or tempfile.mkdtemp(prefix="drift-smoke-")
    trace_dir = os.path.join(root, "trace")
    clean_dir = os.path.join(root, "clean")
    shifted_dir = os.path.join(root, "shifted")
    os.environ["FLINK_ML_TPU_TRACE_DIR"] = trace_dir
    os.environ.setdefault("FLINK_ML_TPU_METRICS_PORT", "0")
    # evaluate on every observation and render verdicts from modest
    # sample counts — a smoke, not a production cadence
    os.environ["FLINK_ML_TPU_DRIFT_INTERVAL_S"] = "0"
    os.environ["FLINK_ML_TPU_DRIFT_MIN_COUNT"] = "60"

    import numpy as np

    from flink_ml_tpu.common.table import Table, as_dense_vector_column
    from flink_ml_tpu.linalg.vectors import DenseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    from flink_ml_tpu.observability import drift, server, tracing
    from flink_ml_tpu.observability.exporters import (
        dump_metrics,
        read_spans,
    )
    from flink_ml_tpu.servable.api import DataFrame, DataTypes, Row
    from flink_ml_tpu.servable.lr import (
        LogisticRegressionModelData,
        LogisticRegressionModelServable,
    )
    from flink_ml_tpu.serving import (
        BatcherConfig,
        LoadGenConfig,
        MicroBatcher,
        ModelRegistry,
        publish_model,
        run_loadgen,
        warm,
    )

    dim = args.dim
    rng = np.random.default_rng(11)

    def frame_factory(shift):
        def frame(rows: int) -> DataFrame:
            return DataFrame(
                ["features"], [DataTypes.vector()],
                [Row([DenseVector(rng.normal(size=dim) + shift)])
                 for _ in range(rows)])
        return frame

    # -- train (baseline captured by the traced-fit seam) --------------------
    w_true = rng.normal(size=dim)
    x = rng.normal(size=(4000, dim))
    y = (x @ w_true > 0).astype(np.float64)
    init = Table.from_columns(
        coefficient=as_dense_vector_column(np.zeros((1, dim))),
        modelVersion=np.asarray([0], np.int64))
    model = (OnlineLogisticRegression(global_batch_size=500,
                                      alpha=0.5, beta=0.5)
             .set_initial_model_data(init)
             .fit(Table.from_columns(features=x, label=y)))
    baseline = getattr(model, "drift_baseline", None)
    if baseline is None:
        fail(2, "traced FTRL fit did not capture a drift baseline")
    coef = np.asarray(model.coefficients, np.float64)

    # -- publish v1 with the baseline, build the runtime ---------------------
    watch_dir = os.path.join(root, "models")
    publish_model(watch_dir, [coef], 1, baseline=baseline)

    def loader(leaves, version):
        servable = LogisticRegressionModelServable().set_device_predict(
            True)
        servable.model_data = LogisticRegressionModelData(
            np.asarray(leaves[0], np.float64), version)
        return servable

    clean_frame = frame_factory(0.0)
    registry = ModelRegistry(watch_dir, loader, model="lr",
                             probe=lambda: clean_frame(4))
    if not registry.poll() or registry.version != 1:
        fail(2, "registry did not adopt the published v1 model")
    if drift.baseline_for("lr@v1") is None:
        fail(1, "hot-swap did not install v1's baseline")

    batcher = MicroBatcher(registry, BatcherConfig(
        buckets=(8, 32), window_ms=1.0)).start()
    warm(batcher, frame_factory=clean_frame)

    def drive(frame):
        r = run_loadgen(
            batcher.submit, lambda i: frame(1 + (i % 4)),
            LoadGenConfig(mode="closed", requests=args.requests,
                          concurrency=16))
        if r["errors"]:
            fail(1, f"loadgen errors: {r['errorsByClass']}")
        return r

    # -- phase 1: clean traffic against v1 → gate must pass ------------------
    drive(clean_frame)
    verdict = drift.evaluate("lr@v1")
    if verdict["drifted"]:
        fail(1, f"clean traffic flagged as drifted: {verdict['drifted']}")
    dump_metrics(clean_dir)
    rc = drift.main([clean_dir, "--check"])
    if rc != 0:
        fail(1, f"drift --check exited {rc} on CLEAN artifacts "
                f"({clean_dir})")
    print("drift_smoke: clean phase ok (drift --check exit 0)")

    # -- phase 2: hot-swap v2 (its own baseline), shifted traffic ------------
    publish_model(watch_dir, [coef * 1.01], 2, baseline=baseline)
    if not registry.poll() or registry.version != 2:
        fail(2, "registry did not adopt the published v2 model")
    if drift.baseline_for("lr@v2") is None:
        fail(1, "hot-swap did not install v2's baseline")
    if drift.baseline_for("lr@v1") is None:
        fail(1, "v2 swap evicted v1's baseline (in-flight v1 requests "
                "must keep their own comparison)")
    drive(frame_factory(3.0))
    verdict = drift.evaluate("lr@v2")
    if "f0" not in verdict["drifted"]:
        fail(1, f"shifted traffic not flagged on lr@v2: {verdict}")

    # the live /drift route must agree mid-run
    srv = server.maybe_start()
    if srv is not None:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/drift", timeout=10) as r:
            live = json.loads(r.read())
        if "lr@v2" not in live.get("drifted", []):
            fail(1, f"/drift route does not report the shift: {live}")
        print(f"drift_smoke: /drift route reports drifted="
              f"{live['drifted']}")

    batcher.stop()
    tracing.tracer.shutdown()
    dump_metrics(shifted_dir)

    rc = drift.main([shifted_dir, "--check"])
    if rc != 4:
        fail(1, f"drift --check exited {rc} (wanted 4) on SHIFTED "
                f"artifacts ({shifted_dir})")
    print("drift_smoke: shifted phase ok (drift --check exit 4)")

    # the drifted verdict must be pinned to v2; v1's series stayed clean
    out = json.loads(_capture_json(shifted_dir))
    by_name = {v["servable"]: v for v in out["verdicts"]}
    if by_name["lr@v1"]["drifted"]:
        fail(1, f"v1 series flagged by v2's shifted traffic: "
                f"{by_name['lr@v1']}")
    if not by_name["lr@v2"]["drifted"]:
        fail(1, f"v2 series not flagged: {by_name['lr@v2']}")

    # ml.drift events must be in the trace artifacts
    events = [ev for sp in read_spans(trace_dir)
              for ev in sp.get("events", ())
              if ev.get("name") == drift.DRIFT_EVENT]
    if not events:
        fail(1, f"no {drift.DRIFT_EVENT} events in {trace_dir}")
    print(f"drift_smoke: OK — {len(events)} {drift.DRIFT_EVENT} "
          f"event(s), v2 drifted / v1 clean, gates 0 and 4 as "
          f"expected")
    return 0


def _capture_json(trace_dir: str) -> str:
    """Run the drift CLI's --json rendering and capture stdout."""
    import contextlib
    import io

    from flink_ml_tpu.observability import drift

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        drift.main([trace_dir, "--json"])
    return buf.getvalue()


if __name__ == "__main__":
    sys.exit(main())
