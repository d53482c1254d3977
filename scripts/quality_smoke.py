"""CI smoke: the continuous evaluation plane end to end
(docs/observability.md "Continuous evaluation").

CPU-only CI harness: pins ``JAX_PLATFORMS=cpu`` (and may start child
processes) — never a chip check. A chip belongs to one process;
``python chip_smoke.py`` is the check that runs there.

One scenario proves the quality plane catches what drift cannot:

1. **clean labeled serving**: FTRL-train v1 (the traced fit captures
   BOTH fit-time baselines), publish it with ``quality-baseline.json``
   beside the manifest, drive a labeled closed loop (the loadgen's
   ``feedback`` hook joins ground truth back through the prediction
   ring) — live AUC tracks the baseline, ``flink-ml-tpu-trace quality
   --check`` exits 0 over the dumped artifacts.
2. **label-flip degradation, drift-clean**: hot-swap a degraded model —
   the SAME coefficients with flipped signs — and keep the INPUT
   distribution identical. Feature and prediction sketches stay under
   every drift threshold (the distributions did not move), but the
   joined labels say live AUC collapsed to ~(1 - baseline AUC):
   ``ml.quality`` fires, the quality SLO kind reads VIOLATED, and
   ``quality --check`` exits 4 over the degraded artifacts.
3. **quality-triggered self-healing**: the ops controller's watcher
   triggers on the ACTIVE version's quality verdict (no drift, no
   error-rate, no latency signal — quality alone), an honest
   warm-started refit on the recent labeled traffic publishes
   v(N+1) WITH a fresh quality baseline, and the canary verdict's
   quality stage passes it through to the swap.
4. **quality-gated rollback**: the next trigger's retrain is rigged to
   return sign-flipped coefficients beside HONEST baselines — finite,
   probe-clean, drift-clean, latency-clean. The bake stage's quality
   verdict sees live AUC collapse vs the published baseline, the
   controller rolls back to v(N-1) and the demoted version's quality
   state is forgotten.

Exit codes: 0 all good; 1 an assertion failed; 2 environment broken.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def fail(code: int, message: str):
    print(f"quality_smoke: FAIL — {message}", file=sys.stderr)
    raise SystemExit(code)


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=None,
                        help="artifact root (default: a temp dir; CI "
                             "points this at an uploadable path)")
    parser.add_argument("--dim", type=int, default=6)
    parser.add_argument("--requests-per-step", type=int, default=64)
    args = parser.parse_args(argv)
    if args.dim < 2 or args.dim % 2:
        parser.error("--dim must be an even integer >= 2 (w_true is "
                     "built as +/- pairs so labels stay ~50/50)")

    root = args.root or tempfile.mkdtemp(prefix="quality-smoke-")
    trace_dir = os.path.join(root, "trace")
    os.environ["FLINK_ML_TPU_TRACE_DIR"] = trace_dir
    os.environ.setdefault("FLINK_ML_TPU_METRICS_PORT", "0")
    # drift stays armed at its CI thresholds: the POINT of phase 2 is
    # that the drift verdict reads clean while quality fires
    os.environ["FLINK_ML_TPU_DRIFT"] = "1"
    os.environ["FLINK_ML_TPU_DRIFT_INTERVAL_S"] = "0"
    os.environ["FLINK_ML_TPU_DRIFT_MIN_COUNT"] = "150"
    # quality: evaluate on every joined label; the CI label floor is
    # sized so one drive batch (requests_per_step 2-row requests) makes
    # a window fresh — binned AUC at n=64 on a near-separable stream is
    # far from both the 0.6 floor and the 0.1 delta band
    os.environ["FLINK_ML_TPU_QUALITY"] = "1"
    os.environ["FLINK_ML_TPU_QUALITY_INTERVAL_S"] = "0"
    os.environ["FLINK_ML_TPU_QUALITY_MIN_LABELS"] = "64"

    import numpy as np

    from flink_ml_tpu.common.table import Table, as_dense_vector_column
    from flink_ml_tpu.linalg.vectors import DenseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    from flink_ml_tpu.observability import (
        drift,
        evaluation,
        server,
        slo,
        tracing,
    )
    from flink_ml_tpu.observability.exporters import dump_metrics
    from flink_ml_tpu.resilience import RetryPolicy
    from flink_ml_tpu.servable.api import DataFrame, DataTypes, Row
    from flink_ml_tpu.servable.lr import (
        LogisticRegressionModelData,
        LogisticRegressionModelServable,
    )
    from flink_ml_tpu.serving import (
        BatcherConfig,
        ControllerConfig,
        LoadGenConfig,
        MicroBatcher,
        ModelRegistry,
        OpsController,
        publish_model,
        run_loadgen,
        warm,
    )
    from flink_ml_tpu.serving.controller import WATCHING

    dim = args.dim
    # sum(w_true) == 0 keeps labels ~50/50, so the flipped model's
    # PREDICTION distribution is statistically identical to the honest
    # one — only the per-row assignment is wrong, which is exactly the
    # regression only joined ground truth can see
    mags = np.resize([1.0, 2.0, 1.5], dim // 2)
    w_true = np.stack([mags, -mags], axis=1).ravel()
    rng = np.random.default_rng(11)
    watch_dir = os.path.join(root, "models")
    buffer: collections.deque = collections.deque(
        maxlen=args.requests_per_step * 2 * 2)
    # the live concept the feedback hook labels with (phase 3 flips it:
    # concept drift — features unchanged, meanings inverted)
    concept = {"flip": False}

    def true_labels(x: np.ndarray) -> np.ndarray:
        y = (x @ w_true > 0).astype(np.float64)
        return 1.0 - y if concept["flip"] else y

    def make_rows(n: int):
        x = rng.normal(size=(n, dim))
        y = true_labels(x)
        for i in range(n):
            buffer.append((x[i], y[i]))
        return x

    def frames_for(x):
        return [DataFrame(["features"], [DataTypes.vector()],
                          [Row([DenseVector(x[i])]),
                           Row([DenseVector(x[i + 1])])])
                for i in range(0, len(x) - 1, 2)]

    def loader(leaves, version):
        servable = LogisticRegressionModelServable() \
            .set_device_predict(True)
        servable.model_data = LogisticRegressionModelData(
            np.asarray(leaves[0], np.float64), version)
        return servable

    def probe_frame():
        x = rng.normal(size=(4, dim))
        return DataFrame(["features"], [DataTypes.vector()],
                         [Row([DenseVector(row)]) for row in x])

    # the labeled half of the loadgen: join ground truth back through
    # the evaluation plane's prediction ring by the request id the
    # batcher stamped on the future
    def feedback(i, frame, fut):
        rid = getattr(fut, "request_id", None)
        if rid is None:
            return
        feats = np.asarray([r.values[0].to_array()
                            for r in frame.collect()])
        evaluation.record_feedback(rid, true_labels(feats))

    # -- train + publish v1 (BOTH fit-time baselines ride the manifest) -----
    x0 = rng.normal(size=(2000, dim))
    y0 = (x0 @ w_true > 0).astype(np.float64)
    init = Table.from_columns(
        coefficient=as_dense_vector_column(np.zeros((1, dim))),
        modelVersion=np.asarray([0], np.int64))
    m1 = (OnlineLogisticRegression(global_batch_size=500,
                                   alpha=0.5, beta=0.5)
          .set_initial_model_data(init)
          .fit(Table.from_columns(features=x0, label=y0)))
    drift_base = getattr(m1, "drift_baseline", None)
    quality_base = getattr(m1, "quality_baseline", None)
    if drift_base is None:
        fail(2, "traced FTRL fit did not capture a drift baseline")
    if quality_base is None:
        fail(2, "traced FTRL fit did not capture a quality baseline")
    coef1 = np.asarray(m1.coefficients, np.float64)
    publish_model(watch_dir, [coef1], 1, baseline=drift_base,
                  quality_baseline=quality_base)
    ckpt_extras = os.path.join(watch_dir, "ckpt-00000001",
                               evaluation.BASELINE_FILENAME)
    if not os.path.exists(ckpt_extras):
        fail(1, f"publish_model did not ship "
                f"{evaluation.BASELINE_FILENAME} beside the manifest "
                f"({ckpt_extras} missing)")

    registry = ModelRegistry(watch_dir, loader, model="lr",
                             probe=probe_frame)
    if not registry.poll() or registry.version != 1:
        fail(2, "registry did not adopt the published v1 model")
    if evaluation.baseline_for("lr@v1") is None:
        fail(1, "hot-swap did not install the published quality "
                "baseline for lr@v1")

    batcher = MicroBatcher(registry, BatcherConfig(
        buckets=(8, 32), window_ms=1.0)).start()
    warm(batcher, frame_factory=lambda rows: DataFrame(
        ["features"], [DataTypes.vector()],
        [Row([DenseVector(rng.normal(size=dim))])
         for _ in range(rows)]))

    drives = {"errors": 0, "rejected": 0, "requests": 0}

    def drive(n_rows=None):
        n = n_rows or (args.requests_per_step * 2)
        frames = frames_for(make_rows(n))
        r = run_loadgen(
            batcher.submit, lambda i: frames[i],
            LoadGenConfig(mode="closed", requests=len(frames),
                          concurrency=8),
            feedback=feedback)
        drives["errors"] += r["errors"]
        drives["rejected"] += r["rejected"]
        drives["requests"] += r["requests"]
        return r

    # -- phase 1: clean labeled serving — quality tracks the baseline -------
    drive()
    drive()
    v1 = evaluation.evaluate("lr@v1")
    if v1["thin"]:
        fail(1, f"labeled loadgen left the v1 window thin: {v1}")
    if v1["degraded"]:
        fail(1, f"clean serving reads degraded: {v1}")
    if (v1["coverage"] or {}).get("joined", 0) <= 0:
        fail(1, f"no labels joined through the prediction ring: {v1}")
    clean_dir = os.path.join(root, "clean")
    evaluation.dump_state(clean_dir)
    rc = evaluation.main([clean_dir, "--check"])
    if rc != 0:
        fail(1, f"`mltrace quality --check` exited {rc} on the CLEAN "
                f"artifacts ({clean_dir})")
    print(f"quality_smoke: phase 1 ok — live auc "
          f"{v1['live']['auc']:.4f} vs baseline "
          f"{v1['baseline']['auc']:.4f}, coverage "
          f"{v1['coverage']['coverage']:.2f}, quality --check exit 0")

    # -- phase 2: label-flip hot-swap — drift clean, quality fires -----------
    # the degraded model: the SAME coefficients, flipped signs. Inputs
    # never move, the prediction histogram stays ~50/50 — but every
    # per-row assignment inverts, so live AUC collapses to
    # ~(1 - baseline AUC). Published beside the HONEST baselines: the
    # quality plane must convict it on evidence, not on missingness.
    publish_model(watch_dir, [-coef1], 2, baseline=drift_base,
                  quality_baseline=quality_base)
    if not registry.poll() or registry.version != 2:
        fail(2, "registry did not adopt the flipped v2 model")
    drive()
    drive()
    v2 = evaluation.evaluate("lr@v2")
    if not v2["degraded"]:
        fail(1, f"label-flipped v2 did not read degraded: {v2}")
    if "auc-delta" not in v2["over"] and "min-auc" not in v2["over"]:
        fail(1, f"degraded v2 crossed no quality threshold: {v2}")
    drift_v2 = drift.evaluate("lr@v2")
    if drift_v2["drifted"]:
        fail(1, f"the label flip must be invisible to drift (inputs "
                f"unchanged), but drift fired: {drift_v2}")
    # the quality SLO kind over the live gauges reads VIOLATED
    quality_slo = slo.SLO.from_dict(
        {"name": "live-auc-floor", "kind": "quality",
         "min_quality": 0.6})
    verdicts = slo.evaluate_slos([quality_slo], emit=False)
    if verdicts[0]["ok"]:
        fail(1, f"quality SLO did not read VIOLATED on the flipped "
                f"model: {verdicts[0]}")
    degraded_dir = os.path.join(root, "degraded")
    evaluation.dump_state(degraded_dir)
    rc = evaluation.main([degraded_dir, "--check"])
    if rc != 4:
        fail(1, f"`mltrace quality --check` exited {rc} (want 4) on "
                f"the DEGRADED artifacts ({degraded_dir})")
    print(f"quality_smoke: phase 2 ok — flipped v2 live auc "
          f"{v2['live']['auc']:.4f} (baseline "
          f"{v2['baseline']['auc']:.4f}), drift clean, quality "
          f"--check exit 4")

    # -- phase 3: quality-triggered retrain → canary → swap ------------------
    rigged = {"on": False}

    def retrain(trigger):
        active = registry.active
        # batch 32, NOT 500: the buffer holds ~256 rows and the warm
        # start may be an inverted model (phase 3 retrains out of a
        # label flip) — the refit needs several FTRL updates to cross
        # back through zero, and a batch larger than the buffer makes
        # none at all
        est = (OnlineLogisticRegression(global_batch_size=32,
                                        alpha=0.5, beta=0.5)
               .warm_start(
                   np.asarray(active.model_data.coefficient,
                              np.float64),
                   model_version=registry.version or 0))
        rows = list(buffer)
        x = np.stack([r for r, _ in rows])
        y = np.asarray([label for _, label in rows])
        model = est.fit(Table.from_columns(features=x, label=y))
        coef = np.asarray(model.coefficients, np.float64)
        if rigged["on"]:
            rigged["on"] = False
            # the quality-gated rollback's candidate: flipped signs
            # beside HONEST baselines — finite, probe-clean,
            # drift-clean; only the bake stage's quality verdict can
            # convict it
            coef = -coef
        return ([coef], getattr(model, "drift_baseline", None),
                getattr(model, "quality_baseline", None))

    controller = OpsController(
        registry, retrain,
        ControllerConfig(
            ramp_stages=(),  # promote after probe; the bake stage's
            # quality verdict is the one under test
            stage_min_requests=8, bake_min_requests=8,
            stage_timeout_s=600.0, cooldown_s=0.0,
            max_error_ratio=0.02,
            policy=RetryPolicy(max_restarts=8, backoff_s=0.01,
                               max_backoff_s=0.05)))

    def run_cycle(max_steps: int = 80) -> str:
        before = dict(controller._outcomes)
        state = controller.state
        for _ in range(max_steps):
            drive()
            state = controller.step()
            if state == WATCHING and controller._outcomes != before:
                return [k for k in controller._outcomes
                        if controller._outcomes[k] > before.get(k, 0)][0]
        fail(1, f"controller did not complete a cycle within "
                f"{max_steps} steps (state {state}, transitions "
                f"{controller.transitions[-5:]})")

    outcome = run_cycle()
    if outcome != "swapped":
        fail(1, f"phase 3 expected outcome 'swapped', got {outcome!r}")
    if registry.version != 3:
        fail(1, f"phase 3 should serve v3, serving "
                f"v{registry.version}")
    trigger_reason = next(
        (t["reason"] for t in controller.transitions
         if t["to"] == "retraining"), "")
    if not trigger_reason.startswith("quality:"):
        fail(1, f"the cycle was not quality-triggered: "
                f"{trigger_reason!r}")
    drive()
    v3 = evaluation.evaluate("lr@v3")
    if v3["degraded"] or drift.evaluate("lr@v3")["drifted"]:
        fail(1, f"retrained v3 not clean on the traffic that "
                f"condemned v2: {v3}")
    print(f"quality_smoke: phase 3 ok — quality trigger "
          f"({trigger_reason}) → retrain → canary → swap, v3 live "
          f"auc {v3['live']['auc']:.4f}")

    # -- phase 4: quality-gated rollback -------------------------------------
    # the world changes (concept flip: same features, inverted labels)
    # and the rigged retrain answers with a flipped-coefficient
    # candidate. Probe, drift and latency all pass; the bake stage's
    # quality verdict must be the one that rolls it back.
    concept["flip"] = True
    rigged["on"] = True
    outcome = run_cycle()
    if outcome != "rolled-back":
        fail(1, f"phase 4 expected outcome 'rolled-back', got "
                f"{outcome!r}")
    if registry.version != 3:
        fail(1, f"rollback should restore v3, serving "
                f"v{registry.version}")
    rollback_reason = next(
        (t["reason"] for t in reversed(controller.transitions)
         if t["to"] == "rolling-back"), "")
    if "quality" not in rollback_reason:
        fail(1, f"the rollback was not quality-judged: "
                f"{rollback_reason!r}")
    if evaluation.baseline_for("lr@v4") is not None:
        fail(1, "rollback did not forget the demoted version's "
                "quality state")
    # and the loop converges: the next honest cycle learns the flipped
    # concept and swaps a healthy v5 in
    outcome = run_cycle()
    if outcome != "swapped":
        fail(1, f"post-rollback cycle expected 'swapped', got "
                f"{outcome!r}")
    if registry.version != 5:
        fail(1, f"converged loop should serve v5, serving "
                f"v{registry.version}")
    print(f"quality_smoke: phase 4 ok — rigged candidate baked, "
          f"quality verdict rolled back to v3 "
          f"({rollback_reason.split(':', 1)[-1].strip()}), loop "
          f"converged to v5")

    # the /quality route must reflect the live plane
    srv = server.maybe_start()
    if srv is not None:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/quality",
                timeout=10) as r:
            live = json.loads(r.read())
        names = set((live.get("servables") or {}))
        if "lr@v5" not in names:
            fail(1, f"/quality route does not show the serving "
                    f"version: {sorted(names)}")

    if drives["errors"] or drives["rejected"]:
        fail(1, f"in-flight requests were harmed: "
                f"{drives['errors']} error(s), "
                f"{drives['rejected']} rejection(s) across "
                f"{drives['requests']} request(s)")
    batcher.stop()
    controller.stop()

    # -- artifact gates -------------------------------------------------------
    tracing.tracer.shutdown()
    server.stop()
    dump_metrics(trace_dir)
    from flink_ml_tpu.serving import controller as controller_cli

    rc = controller_cli.main([trace_dir, "--check"])
    if rc != 0:
        fail(1, f"`mltrace controller --check` exited {rc} on the "
                f"smoke artifacts ({trace_dir})")
    print(f"quality_smoke: OK — clean exit 0, label-flip exit 4, "
          f"quality-triggered swap + quality-gated rollback, "
          f"controller --check exit 0 over {trace_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
