"""CI smoke: the self-healing ops loop end to end, under seeded chaos
(docs/ops.md).

CPU-only CI harness: pins ``JAX_PLATFORMS=cpu`` (and may start child
processes) — never a chip check. A chip belongs to one process;
``python chip_smoke.py`` is the check that runs there.

One scenario run proves the closed loop twice:

1. **drift → retrain → canary → swap**: traffic mean-shifts away from
   the serving model's training distribution; the controller's drift
   trigger fires, a warm-started FTRL refit on the recent (labeled)
   traffic publishes v(N+1) WITH a fresh baseline, the candidate is
   canary-probed, promoted and baked — and the new version's drift
   gauges read UNDER threshold on the very traffic that condemned its
   predecessor.
2. **bad candidate → automatic rollback**: the next trigger's retrain
   is rigged to return finite-but-garbage coefficients (they pass the
   NaN probe; their predictions collapse to one class). The bake stage
   sees the prediction-distribution drift regress, the controller rolls
   back to v(N-1) WITHOUT re-probe, the bad version is remembered — and
   the loop then converges: the following (honest) cycle swaps a
   healthy version in. In-flight requests are unharmed throughout
   (every loadgen phase must finish with 0 errors / 0 rejections).

The WHOLE scenario runs under a seeded chaos plan armed at exactly the
five controller fault sites (``controller-retrain``,
``controller-publish``, ``canary-probe``, ``model-swap``,
``model-rollback`` — resilience/faults.py), and runs TWICE at the same
seed: the normalized controller transition logs and cycle outcomes must
be identical — recovery is deterministic, not lucky. Artifacts are then
gated with ``flink-ml-tpu-trace controller --check`` (exit 4 unless the
loop ended healthy).

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
    print(f"ops_loop_smoke: FAIL — {message}", file=sys.stderr)
    raise SystemExit(code)


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=None,
                        help="artifact root (default: a temp dir; CI "
                             "points this at an uploadable path)")
    parser.add_argument("--chaos-seed", type=int, default=20260804)
    parser.add_argument("--chaos-rate", type=float, default=0.2)
    parser.add_argument("--dim", type=int, default=6)
    parser.add_argument("--requests-per-step", type=int, default=64)
    args = parser.parse_args(argv)
    if args.dim < 2 or args.dim % 2:
        parser.error("--dim must be an even integer >= 2 (w_true is "
                     "built as +/- pairs so labels stay ~50/50 under "
                     "any mean shift)")

    root = args.root or tempfile.mkdtemp(prefix="ops-loop-smoke-")
    trace_dir = os.path.join(root, "trace")
    os.environ["FLINK_ML_TPU_TRACE_DIR"] = trace_dir
    os.environ.setdefault("FLINK_ML_TPU_METRICS_PORT", "0")
    # evaluate drift on every observation; the sample floor is sized
    # for BINARY prediction sketches — at n=60 a 50/50 predictor's
    # KS estimate wanders within ~0.2 of truth and a healthy bake can
    # fire a rare false rollback; at n=150 the 0.25 threshold sits
    # >5 sigma from an honest candidate while the rigged all-one-class
    # candidate (KS 0.5, PSI >> 1) still fires at any floor
    os.environ["FLINK_ML_TPU_DRIFT"] = "1"
    os.environ["FLINK_ML_TPU_DRIFT_INTERVAL_S"] = "0"
    os.environ["FLINK_ML_TPU_DRIFT_MIN_COUNT"] = "150"

    import numpy as np

    from flink_ml_tpu.common.metrics import metrics
    from flink_ml_tpu.common.table import Table, as_dense_vector_column
    from flink_ml_tpu.linalg.vectors import DenseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    from flink_ml_tpu.observability import drift, server, tracing
    from flink_ml_tpu.observability.exporters import dump_metrics
    from flink_ml_tpu.resilience import RetryPolicy, faults
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
    # sum(w_true) == 0 keeps the honest label balance ~50/50 under ANY
    # feature mean shift — so the rigged candidate's one-class
    # predictions are unambiguous prediction drift, and an honest refit
    # never is
    mags = np.resize([1.0, 2.0, 1.5], dim // 2)
    w_true = np.stack([mags, -mags], axis=1).ravel()

    def scenario(run_idx: int) -> dict:
        """One full self-healing scenario; returns its normalized
        transition log + outcomes for the determinism comparison."""
        rng = np.random.default_rng(7)
        watch_dir = os.path.join(root, f"models-{run_idx}")
        # recent labeled traffic — what the warm-start refit trains on.
        # Sized to TWO drive batches: by the time a trigger's retrain
        # runs (one step after the trigger), the window holds only the
        # CURRENT distribution, so the fresh baseline matches the
        # traffic the new version will be judged against
        buffer: collections.deque = collections.deque(
            maxlen=args.requests_per_step * 2 * 2)

        def make_rows(n: int, shift: float):
            x = rng.normal(size=(n, dim)) + shift
            y = (x @ w_true > 0).astype(np.float64)
            for i in range(n):
                buffer.append((x[i], y[i]))
            return x

        def frames_for(x):
            # 2-row requests: small enough to exercise padding, large
            # enough to keep the tick count low
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

        # -- train + publish v1 on the clean distribution (shift 0);
        # the initial fit does NOT feed the traffic buffer — it is not
        # traffic
        x0 = rng.normal(size=(2000, dim))
        y0 = (x0 @ w_true > 0).astype(np.float64)
        init = Table.from_columns(
            coefficient=as_dense_vector_column(np.zeros((1, dim))),
            modelVersion=np.asarray([0], np.int64))
        m1 = (OnlineLogisticRegression(global_batch_size=500,
                                       alpha=0.5, beta=0.5)
              .set_initial_model_data(init)
              .fit(Table.from_columns(features=x0, label=y0)))
        baseline = getattr(m1, "drift_baseline", None)
        if baseline is None:
            fail(2, "traced FTRL fit did not capture a drift baseline")
        publish_model(watch_dir, [np.asarray(m1.coefficients,
                                             np.float64)],
                      1, baseline=baseline)

        registry = ModelRegistry(watch_dir, loader, model="lr",
                                 probe=probe_frame)
        rigged = {"on": False}

        def retrain(trigger):
            active = registry.active
            est = (OnlineLogisticRegression(global_batch_size=500,
                                            alpha=0.5, beta=0.5)
                   .warm_start(
                       np.asarray(active.model_data.coefficient,
                                  np.float64),
                       model_version=registry.version or 0))
            rows = list(buffer)
            x = np.stack([r for r, _ in rows])
            y = np.asarray([l for _, l in rows])
            model = est.fit(Table.from_columns(features=x, label=y))
            fresh = getattr(model, "drift_baseline", None)
            coef = np.asarray(model.coefficients, np.float64)
            if rigged["on"]:
                rigged["on"] = False
                # finite garbage: passes the NaN probe, predicts ONE
                # class on any mean-shifted traffic — the canary's
                # prediction distribution regresses vs the honest
                # baseline published beside it
                coef = np.abs(coef) * 10.0 + 1.0
            return [coef], fresh

        controller = OpsController(
            registry, retrain,
            ControllerConfig(
                ramp_stages=(),  # promote after probe; bake judges —
                # the post-swap rollback path is the one under test
                stage_min_requests=8, bake_min_requests=8,
                stage_timeout_s=600.0, cooldown_s=0.0,
                max_error_ratio=0.02,
                policy=RetryPolicy(max_restarts=8, backoff_s=0.01,
                                   max_backoff_s=0.05)))

        # the WHOLE loop runs under the seeded plan, armed at exactly
        # the five controller fault sites
        with faults.chaos(seed=args.chaos_seed, rate=args.chaos_rate,
                          sites=faults.CONTROLLER_SITES):
            for _ in range(50):
                if registry.poll():
                    break
            if registry.version != 1:
                fail(2, "registry did not adopt the published v1 "
                        "model under chaos")

            batcher = MicroBatcher(registry, BatcherConfig(
                buckets=(8, 32), window_ms=1.0)).start()
            with faults.suppressed():
                warm(batcher, frame_factory=lambda rows: DataFrame(
                    ["features"], [DataTypes.vector()],
                    [Row([DenseVector(rng.normal(size=dim))])
                     for _ in range(rows)]))

            drives = {"errors": 0, "rejected": 0, "requests": 0}

            def drive(shift: float, n_rows: int = None):
                n = n_rows or (args.requests_per_step * 2)
                frames = frames_for(make_rows(n, shift))
                r = run_loadgen(
                    batcher.submit, lambda i: frames[i],
                    LoadGenConfig(mode="closed", requests=len(frames),
                                  concurrency=8))
                drives["errors"] += r["errors"]
                drives["rejected"] += r["rejected"]
                drives["requests"] += r["requests"]
                return r

            def run_cycle(shift: float, max_steps: int = 80) -> str:
                """Drive traffic + step the controller until ONE cycle
                completes; returns its outcome."""
                before = dict(controller._outcomes)
                for _ in range(max_steps):
                    drive(shift)
                    state = controller.step()
                    if (state == WATCHING
                            and controller._outcomes != before):
                        new = [k for k in controller._outcomes
                               if controller._outcomes[k]
                               > before.get(k, 0)]
                        return new[0]
                fail(1, f"controller did not complete a cycle within "
                        f"{max_steps} steps (state {state}, "
                        f"transitions {controller.transitions[-5:]})")

            # -- phase 1: drift-shifted traffic heals via retrain+swap -------
            outcome = run_cycle(shift=3.0)
            if outcome != "swapped":
                fail(1, f"phase 1 expected outcome 'swapped', got "
                        f"{outcome!r}")
            if registry.version != 2:
                fail(1, f"phase 1 should serve v2, serving "
                        f"v{registry.version}")
            drive(3.0)
            verdict = drift.evaluate("lr@v2")
            if verdict["drifted"]:
                fail(1, f"v2 drift gauges not under threshold on the "
                        f"traffic it was retrained for: {verdict}")
            print(f"ops_loop_smoke[{run_idx}]: phase 1 ok — drift "
                  f"trigger → retrain → canary → swap, v2 clean")

            # -- phase 2: rigged candidate → automatic rollback --------------
            rigged["on"] = True
            outcome = run_cycle(shift=-3.0)
            if outcome != "rolled-back":
                fail(1, f"phase 2 expected outcome 'rolled-back', got "
                        f"{outcome!r}")
            if registry.version != 2:
                fail(1, f"rollback should restore v2, serving "
                        f"v{registry.version}")
            if 3 not in registry._rejected:
                fail(1, "rolled-back v3 was not remembered as "
                        "rejected")
            if drift.baseline_for("lr@v3") is not None:
                fail(1, "rollback did not forget the demoted "
                        "version's drift state")
            print(f"ops_loop_smoke[{run_idx}]: phase 2 ok — rigged "
                  f"candidate baked, rolled back to v2, v3 condemned")

            # -- phase 3: the loop converges after the failure ---------------
            outcome = run_cycle(shift=-3.0)
            if outcome != "swapped":
                fail(1, f"phase 3 expected outcome 'swapped', got "
                        f"{outcome!r}")
            if registry.version != 4:
                fail(1, f"phase 3 should serve v4, serving "
                        f"v{registry.version}")
            drive(-3.0)
            verdict = drift.evaluate("lr@v4")
            if verdict["drifted"]:
                fail(1, f"v4 not healthy after convergence: {verdict}")
            print(f"ops_loop_smoke[{run_idx}]: phase 3 ok — loop "
                  f"converged to healthy v4 after the rollback")

            # the /controller route must reflect the live machine
            srv = server.maybe_start()
            if srv is not None:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/controller",
                        timeout=10) as r:
                    live = json.loads(r.read())
                status = live.get("controller") or {}
                if status.get("state") != WATCHING or \
                        status.get("active_version") != 4:
                    fail(1, f"/controller route out of sync: {live}")

            if drives["errors"] or drives["rejected"]:
                fail(1, f"in-flight requests were harmed: "
                        f"{drives['errors']} error(s), "
                        f"{drives['rejected']} rejection(s) across "
                        f"{drives['requests']} request(s)")
            batcher.stop()
        controller.stop()

        return {
            # counts (ticks, ms) vary run to run; the SHAPE of the loop
            # must not — compare states + cycles, not free-text reasons
            "transitions": [(t["from"], t["to"], t["cycle"])
                            for t in controller.transitions],
            "outcomes": dict(controller._outcomes),
            "final_version": registry.version,
            "rejected": sorted(registry._rejected),
        }

    # -- two runs, same seed: the loop must be deterministic -----------------
    result_a = scenario(1)
    # reset cross-run process state (metrics, drift windows) so run 2
    # starts from the same blank slate — the chaos plan is re-seeded by
    # the fresh `with faults.chaos(...)` block
    metrics.clear()
    drift.clear()
    result_b = scenario(2)
    if result_a != result_b:
        fail(1, "chaos runs at the same seed diverged:\n"
                f"  run 1: {json.dumps(result_a, indent=2)}\n"
                f"  run 2: {json.dumps(result_b, indent=2)}")
    print(f"ops_loop_smoke: deterministic — "
          f"{len(result_a['transitions'])} transition(s), outcomes "
          f"{result_a['outcomes']}, identical across both runs at "
          f"seed {args.chaos_seed}")

    # -- artifact gate: the CLI must read the loop as healthy ----------------
    tracing.tracer.shutdown()
    server.stop()
    dump_metrics(trace_dir)
    from flink_ml_tpu.serving import controller as controller_cli

    rc = controller_cli.main([trace_dir, "--check"])
    if rc != 0:
        fail(1, f"`mltrace controller --check` exited {rc} on the "
                f"smoke artifacts ({trace_dir})")
    print(f"ops_loop_smoke: OK — controller --check exit 0 over "
          f"{trace_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
