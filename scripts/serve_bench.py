"""Serving benchmark: micro-batched vs per-request throughput, SLO-gated
(docs/serving.md).

CPU-only CI harness: pins ``JAX_PLATFORMS=cpu`` (and may start child
processes) — never a chip check. A chip belongs to one process;
``python chip_smoke.py`` is the check that runs there.

Flow: train a logistic-regression model with the FTRL online path
(OnlineLogisticRegression — the train-while-serve producer), publish it
into a model-registry watch dir (v2 checkpoint manifests), build the
serving runtime (registry → micro-batcher → AOT warmup), then drive the
SAME closed-loop request mix (serving/loadgen.py) through

1. the **per-request baseline** — one ``transform`` per request, the
   synchronous servable path, and
2. the **micro-batched runtime** — admission queue, bucket padding, one
   device dispatch per tick,

and record both in a BASELINE-style ``BENCH_serving.json`` beside the
fit benchmarks: throughput, exact p50/p99, padding/fill, warmup compile
bill, steady-state compile count (must be 0 — the bucketing contract),
and a live hot-swap mid-run (the registry watcher adopts a
freshly-published version while requests are in flight). A small
window/bucket sweep rides along unless ``--smoke``.

``--mesh`` adds the **mesh-sharded dispatch sweep** (docs/serving.md
"Mesh-sharded dispatch"): one subprocess cell per simulated device
count (1/2/4/8 — smoke keeps the endpoints), each measuring the SAME
large-bucket closed-loop workload through an unsharded and a
mesh-sharded (``map_rows``) runtime, self-gated on (a) sharded >=
unsharded throughput at the max device count (enforced on >= 4-core
hosts, recorded skipped on fewer; always-on 0.5x collapse floor), (b)
zero steady-state compiles after the bucket x mesh warmup matrix, (c)
sharded-vs-unsharded prediction parity — plus the pipelined
dispatcher's pad/compute span-overlap proof and ``mltrace shards
--check`` over the traced max-device cell.

Gates (exit codes follow the repo convention): 0 ok; 1 an acceptance
gate failed (ratio < --min-ratio, steady compiles > 0, errors, p99 over
budget, hot-swap missed, trace overhead > --trace-overhead-budget, a
mesh-sweep gate); 2 broken environment; 4 the ``flink-ml-tpu-trace slo
--check`` artifact gate found a violated SLO.

The **trace-overhead** gate (docs/observability.md "Causal tracing,
critical path & incidents"): the same closed-loop workload at equal
offered load, measured with the ALWAYS-ON causal-tracing configuration
(the recent-span ring armed, no trace dir — per-TICK pad/batch/request
spans built and ringed; the per-REQUEST submit/resolve chain only arms
with a trace dir, the debugging mode, so its cost shows in the
informational ``diskTracedP99Ms``, not in this gate) and fully dark —
interleaved best-of-N p99s; the ring-armed
run must stay within ``--trace-overhead-budget`` (default 5%) of the
dark one, recorded as ``traceOverheadPct`` in BENCH_serving.json and
the bench.py one-liner. The budget enforces on >= 4-core
hosts and records itself skipped on fewer (a 1-core box's p99 noise
band is wider than the budget — the PR 11/12 precedent); a 50%
collapse floor enforces everywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from flink_ml_tpu.linalg.vectors import DenseVector  # noqa: E402
from flink_ml_tpu.servable.api import (  # noqa: E402
    DataFrame,
    DataTypes,
    Row,
)
from flink_ml_tpu.servable.lr import (  # noqa: E402
    LogisticRegressionModelData,
    LogisticRegressionModelServable,
)
from flink_ml_tpu.serving import (  # noqa: E402
    BatcherConfig,
    LoadGenConfig,
    MicroBatcher,
    ModelRegistry,
    compile_count,
    publish_model,
    run_loadgen,
    warm,
)

#: request row-count mix — singleton pings dominate, with small bursts
REQUEST_SIZES = (1, 2, 4)

#: the benchmark's SLO spec (evaluated over the dumped artifacts by
#: ``flink-ml-tpu-trace slo --check``): p99 per-tick transform latency
#: and the serving error ratio. Shed load (``rejected``) is NOT an
#: error — that distinction is the point of the rejected counter.
SLO_SPEC = {"slos": [
    {"name": "serving-batch-latency-p99", "kind": "latency",
     "histogram": "transformMs", "quantile": 0.99,
     "threshold_ms": 500.0},
    {"name": "serving-error-rate", "kind": "error-rate",
     "max_error_ratio": 0.01},
]}


def fail(code: int, message: str):
    print(f"serve_bench: FAIL — {message}", file=sys.stderr)
    raise SystemExit(code)


def train_ftrl(dim: int, rows: int, batch: int):
    """FTRL-train an LR model on a synthetic stream; returns the
    coefficient vector, the training-time drift baseline the traced-fit
    seam captured (observability/drift.py), the fit-time quality
    baseline (observability/evaluation.py — the live-AUC anchor) and
    the generating weights (the labeled loadgen's ground truth) — the
    online-learning producer whose snapshots the registry serves,
    published WITH the distribution AND quality they were trained
    on."""
    from flink_ml_tpu.common.table import Table, as_dense_vector_column
    from flink_ml_tpu.models.online import OnlineLogisticRegression

    rng = np.random.default_rng(7)
    w_true = rng.normal(size=dim)
    x = rng.normal(size=(rows, dim))
    y = (x @ w_true > 0).astype(np.float64)
    table = Table.from_columns(features=x, label=y)
    init = Table.from_columns(
        coefficient=as_dense_vector_column(np.zeros((1, dim))),
        modelVersion=np.asarray([0], np.int64))
    model = (OnlineLogisticRegression(global_batch_size=batch,
                                      alpha=0.5, beta=0.5)
             .set_initial_model_data(init).fit(table))
    return (np.asarray(model.coefficients, np.float64),
            getattr(model, "drift_baseline", None),
            getattr(model, "quality_baseline", None),
            w_true)


def make_frame_factory(dim: int):
    # a fresh Generator per frame: factories run on concurrent loadgen
    # workers and np.random.Generator is not thread-safe
    counter = [0]

    def frame(rows: int) -> DataFrame:
        counter[0] += 1
        rng = np.random.default_rng(counter[0])
        return DataFrame(
            ["features"], [DataTypes.vector()],
            [Row([DenseVector(rng.normal(size=dim))])
             for _ in range(rows)])

    return frame


def lr_loader(leaves, version):
    servable = LogisticRegressionModelServable().set_device_predict(True)
    servable.model_data = LogisticRegressionModelData(
        np.asarray(leaves[0], np.float64), version)
    return servable


# ---------------------------------------------------------------------------
# --mesh sweep: sharded vs unsharded dispatch per simulated device count
# ---------------------------------------------------------------------------

#: full-sweep device counts (PR 6 xla_force_host_platform_device_count
#: precedent); --smoke keeps the endpoints
MESH_DEVICE_COUNTS = (1, 2, 4, 8)
MESH_SMOKE_COUNTS = (1, 8)

#: the mesh cells' large-bucket workload: row counts sized so every
#: request lands in a bucket the 8-way mesh divides, with enough
#: per-row compute (dim) that the device leg is worth sharding
MESH_BUCKETS = (64, 256)
MESH_REQUEST_SIZES = (64, 256)
MESH_DIM = 512


def run_mesh_cell(args) -> int:
    """One sweep cell (a subprocess with its own XLA_FLAGS): measure
    the SAME large-bucket closed-loop workload through an unsharded and
    a mesh-sharded serving runtime, check prediction parity between the
    two dispatch paths, and print one JSON row."""
    import jax

    from flink_ml_tpu.common.metrics import ML_GROUP, metrics
    from flink_ml_tpu.observability import tracing
    from flink_ml_tpu.observability.exporters import dump_metrics
    from flink_ml_tpu.parallel import create_mesh

    n_dev = jax.device_count()
    rng = np.random.default_rng(11)
    dim = MESH_DIM
    coef = rng.normal(size=dim)
    watch_dir = os.path.join(tempfile.mkdtemp(prefix="serve-mesh-"),
                             "models")
    publish_model(watch_dir, [coef], 1)
    n_requests = args.requests or (120 if args.smoke else 400)

    counter = [0]

    def frame(rows: int) -> DataFrame:
        counter[0] += 1
        r = np.random.default_rng(counter[0])
        return DataFrame(
            ["features"], [DataTypes.vector()],
            [Row([DenseVector(r.normal(size=dim))])
             for _ in range(rows)])

    def request_frame(i: int) -> DataFrame:
        return frame(MESH_REQUEST_SIZES[i % len(MESH_REQUEST_SIZES)])

    def measure(mesh) -> dict:
        registry = ModelRegistry(watch_dir, lr_loader, model="lr",
                                 probe=lambda: frame(MESH_BUCKETS[0]),
                                 mesh=mesh)
        if not registry.poll():
            raise SystemExit(2)
        batcher = MicroBatcher(registry, BatcherConfig(
            buckets=MESH_BUCKETS, window_ms=1.0,
            max_queue_rows=16384), mesh=mesh).start()
        warm(batcher, frame_factory=frame, gate=False)
        steady_base = compile_count()
        best = None
        for _ in range(2):
            r = run_loadgen(batcher.submit, request_frame,
                            LoadGenConfig(mode="closed",
                                          requests=n_requests,
                                          concurrency=16))
            if best is None or r["throughput_rps"] > best["throughput_rps"]:
                best = r
        steady = compile_count() - steady_base
        batcher.stop()
        return {"throughput_rps": best["throughput_rps"],
                "rows_per_s": best["rows_per_s"],
                "p50_ms": best["latency_ms"]["p50"],
                "p99_ms": best["latency_ms"]["p99"],
                "errors": best["errors"],
                "steadyCompiles": steady,
                "pipelineDepth": batcher.config.pipeline_depth,
                "shardedDispatch": batcher.sharded_dispatch()}

    unsharded = measure(None)
    mesh = create_mesh()
    sharded = measure(mesh)

    # parity: the same frames through both dispatch paths — the
    # thresholded prediction column must be byte-identical; the raw
    # probabilities may differ in the last float32 ulp when the
    # per-device matmul shape changes, so they carry a measured maxdiff
    sv_plain = lr_loader([coef], 1)
    sv_mesh = lr_loader([coef], 1).set_mesh(mesh)
    parity_ok, raw_maxdiff = True, 0.0
    for rows in MESH_BUCKETS:
        base = frame(rows)
        vals = [list(r.values) for r in base.collect()]

        def clone():
            return DataFrame(base.column_names, base.data_types,
                             [Row(list(v)) for v in vals])

        a, b = sv_plain.transform(clone()), sv_mesh.transform(clone())
        if a.get("prediction").values != b.get("prediction").values:
            parity_ok = False
        ra = np.asarray([v.to_array() for v in
                         a.get("rawPrediction").values])
        rb = np.asarray([v.to_array() for v in
                         b.get("rawPrediction").values])
        raw_maxdiff = max(raw_maxdiff, float(np.max(np.abs(ra - rb))))

    snap = metrics.snapshot().get(f"{ML_GROUP}.serving", {})
    gauges = snap.get("gauges", {})
    imbalance = [v for k, v in gauges.items()
                 if k.startswith("shardImbalance")]
    reuse = sum(int(v) for k, v in snap.get("counters", {}).items()
                if k.startswith("paddingReuse"))
    row = {
        "deviceCount": n_dev,
        "meshShape": ",".join(f"{a}={int(mesh.shape[a])}"
                              for a in mesh.axis_names),
        "buckets": list(MESH_BUCKETS),
        "dim": dim,
        "requests": n_requests,
        "unsharded": unsharded,
        "sharded": sharded,
        "parity": parity_ok,
        "rawPredictionMaxDiff": raw_maxdiff,
        "shardImbalance": (max(imbalance) if imbalance else None),
        "paddingReuse": reuse,
    }
    if os.environ.get("FLINK_ML_TPU_TRACE_DIR"):
        tracing.tracer.shutdown()
        dump_metrics(os.environ["FLINK_ML_TPU_TRACE_DIR"])
    print(json.dumps(row), flush=True)
    return 0


def _spawn_mesh_cell(args, n_dev: int, trace_dir=None,
                     timeout=900) -> dict:
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev}")
    env.pop("FLINK_ML_TPU_TRACE_DIR", None)
    if trace_dir:
        env["FLINK_ML_TPU_TRACE_DIR"] = trace_dir
    argv = [sys.executable, os.path.abspath(__file__), "--mesh-cell"]
    if args.smoke:
        argv.append("--smoke")
    if args.requests:
        argv += ["--requests", str(args.requests)]
    proc = subprocess.run(argv, env=env, capture_output=True,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh cell devices={n_dev} failed "
            f"(rc={proc.returncode}):\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _pipeline_overlap(trace_dir: str) -> dict:
    """Scan the trace for pad/compute overlap: a ``serving.pad`` span
    of tick N+1 starting before the ``serving.batch`` span of tick N
    ends proves the pipelined dispatcher really overlaps host padding
    with device compute."""
    from flink_ml_tpu.observability.exporters import read_spans

    pads, batches = {}, {}
    for sp in read_spans(trace_dir):
        tick = sp.get("attrs", {}).get("tick")
        if tick is None:
            continue
        if sp.get("name") == "serving.pad":
            pads.setdefault(int(tick), sp)
        elif sp.get("name") == "serving.batch":
            batches.setdefault(int(tick), sp)
    overlaps = 0
    for tick, batch in batches.items():
        nxt = pads.get(tick + 1)
        if nxt is None or not batch.get("dur_us"):
            continue
        if nxt["ts_us"] < batch["ts_us"] + batch["dur_us"]:
            overlaps += 1
    return {"ticks": len(batches), "overlappingTicks": overlaps,
            "overlap": overlaps > 0}


def run_mesh_sweep(args, root: str) -> dict:
    """The parent side: spawn one cell per device count, gate, and
    return the ``mesh_sweep`` record for BENCH_serving.json."""
    import subprocess

    counts = MESH_SMOKE_COUNTS if args.smoke else MESH_DEVICE_COUNTS
    trace_dir = os.path.join(root, "mesh-trace")
    record = {"deviceCounts": list(counts), "cells": [], "gates": {}}
    for n_dev in counts:
        print(f"serve_bench: mesh cell devices={n_dev}",
              file=sys.stderr, flush=True)
        record["cells"].append(_spawn_mesh_cell(
            args, n_dev,
            trace_dir=trace_dir if n_dev == max(counts) else None))

    failures = []
    hi = max(counts)
    top = next(c for c in record["cells"] if c["deviceCount"] == hi)

    # gate (a): sharded >= unsharded throughput at the max device count
    # on the large buckets. Parallel speedup needs parallel hardware:
    # enforced on >= 4-core hosts, recorded skipped on fewer (the PR 11
    # native-threading precedent); a 0.5x sanity floor (sharding must
    # not collapse throughput) enforces everywhere.
    cores = os.cpu_count() or 1
    ratio = (top["sharded"]["throughput_rps"]
             / max(top["unsharded"]["throughput_rps"], 1e-9))
    enforced = cores >= 4
    record["gates"]["shardedThroughput"] = {
        "deviceCount": hi, "ratio": round(ratio, 3),
        "minRatio": args.mesh_min_ratio, "hostCores": cores,
        "enforced": enforced,
        "skipped": None if enforced else f"host has {cores} core(s)"}
    if enforced and ratio < args.mesh_min_ratio:
        failures.append(
            f"sharded/unsharded throughput ratio {ratio:.2f} at "
            f"{hi} devices below {args.mesh_min_ratio}")
    if ratio < 0.5:
        failures.append(
            f"sharded dispatch collapsed throughput ({ratio:.2f}x)")

    # gate (b): zero steady-state compiles in EVERY cell, both paths —
    # the expanded bucket x mesh warmup matrix really covers the
    # closed shape set
    compiles = {f'{c["deviceCount"]}': [c["unsharded"]["steadyCompiles"],
                                        c["sharded"]["steadyCompiles"]]
                for c in record["cells"]}
    record["gates"]["steadyCompiles"] = compiles
    if any(v != [0, 0] for v in compiles.values()):
        failures.append(f"steady-state compiles after warmup: {compiles}")

    # gate (c): sharded-vs-unsharded prediction parity in every cell
    parity = {str(c["deviceCount"]): c["parity"]
              for c in record["cells"]}
    record["gates"]["parity"] = parity
    if not all(parity.values()):
        failures.append(f"prediction parity broken: {parity}")

    # pipeline overlap + multi-device telemetry over the traced cell
    record["gates"]["pipelineOverlap"] = _pipeline_overlap(trace_dir)
    if not record["gates"]["pipelineOverlap"]["overlap"]:
        failures.append("no pad/compute overlap in the traced mesh "
                        "cell — the pipelined dispatcher is not "
                        "pipelining")
    shards = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "mltrace.py"), "shards", trace_dir, "--check"],
        capture_output=True, text=True, timeout=300)
    record["gates"]["shardsCheck"] = {"exit": shards.returncode}
    if shards.returncode != 0:
        failures.append("mltrace shards --check rejected the traced "
                        f"mesh cell: {shards.stdout}{shards.stderr}")

    record["gates"]["ok"] = not failures
    record["failures"] = failures
    return record


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--smoke", action="store_true",
                        help="small CI run: fewer requests, no sweep, "
                             "assert the hot-swap landed mid-run")
    parser.add_argument("--requests", type=int, default=None,
                        help="requests per measured run "
                             "(default 1200, smoke 400)")
    parser.add_argument("--concurrency", type=int, default=64)
    parser.add_argument("--repeats", type=int, default=2,
                        help="measured repeats per path; the best "
                             "throughput run is recorded (wall-clock "
                             "jitter on shared runners)")
    parser.add_argument("--dim", type=int, default=16)
    parser.add_argument("--window-ms", type=float, default=1.0)
    parser.add_argument("--buckets", default="8,32,128",
                        help="comma-separated bucket row counts")
    parser.add_argument("--min-ratio", type=float, default=3.0,
                        help="batched/per-request throughput gate")
    parser.add_argument("--p99-budget-ms", type=float, default=250.0,
                        help="loadgen end-to-end p99 gate (batched run)")
    parser.add_argument("--output", default="BENCH_serving.json")
    parser.add_argument("--trace-dir", default=None,
                        help="artifact dir (default: a temp dir; CI "
                             "points this at an uploadable path)")
    parser.add_argument("--mesh", action="store_true",
                        help="run the mesh-sharded dispatch sweep "
                             "(1/2/4/8 simulated devices, sharded vs "
                             "unsharded, self-gated)")
    parser.add_argument("--mesh-cell", action="store_true",
                        help="(internal) one sweep cell; prints JSON")
    parser.add_argument("--mesh-min-ratio", type=float, default=1.0,
                        help="sharded/unsharded throughput gate at the "
                             "max device count (>= 4-core hosts)")
    parser.add_argument("--trace-overhead-budget", type=float,
                        default=5.0,
                        help="max traced-vs-untraced steady-state p99 "
                             "overhead (percent) — the always-on "
                             "causal-tracing ring must stay cheap")
    args = parser.parse_args(argv)

    from flink_ml_tpu.utils import compile_cache

    compile_cache.configure()
    if args.mesh_cell:
        return run_mesh_cell(args)

    n_requests = args.requests or (400 if args.smoke else 1200)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    root = args.trace_dir or tempfile.mkdtemp(prefix="serve-bench-")
    trace_dir = os.path.join(root, "trace")
    os.environ["FLINK_ML_TPU_TRACE_DIR"] = trace_dir
    os.environ.setdefault("FLINK_ML_TPU_METRICS_PORT", "0")
    # the HEADLINE runs measure serving, not debugging-mode tracing:
    # with the dir armed and the default sample rate, every request
    # would pay the per-request submit/resolve causal chain serialized
    # onto the device thread (the diskTracedP99Ms informational probe
    # below shows that mode costs multiples of dark p99) — the ratchet
    # numbers and the CI >= 3x gate must not ratchet against it. The
    # overhead probe re-arms the sample for its disk-traced leg.
    os.environ.setdefault("FLINK_ML_TPU_TRACE_SAMPLE", "0")

    from flink_ml_tpu.observability import server, slo, tracing
    from flink_ml_tpu.observability.exporters import dump_metrics

    import jax

    frame = make_frame_factory(args.dim)

    def request_frame(i: int) -> DataFrame:
        return frame(REQUEST_SIZES[i % len(REQUEST_SIZES)])

    # -- train (FTRL) and publish v1 (baselines ride the checkpoint) ---------
    t0 = time.perf_counter()
    coef, baseline, quality_baseline, w_true = train_ftrl(
        args.dim, rows=4000 if args.smoke else 20000, batch=500)
    train_ms = (time.perf_counter() - t0) * 1000.0
    watch_dir = os.path.join(root, "models")
    publish_model(watch_dir, [coef], 1, baseline=baseline,
                  quality_baseline=quality_baseline)
    registry = ModelRegistry(watch_dir, lr_loader, model="lr",
                             probe=lambda: frame(buckets[0]),
                             poll_interval_s=0.05)
    if not registry.poll() or registry.version != 1:
        fail(2, "registry did not adopt the published v1 model")
    print(f"serve_bench: FTRL-trained lr@v1 ({args.dim} dims, "
          f"{train_ms:.0f} ms) published to {watch_dir}")

    # the labeled-loadgen feedback hook (serving/loadgen.py): join the
    # generating weights' ground truth back through the evaluation
    # plane's prediction ring, keyed by the request id the batcher
    # stamped on the future — the continuous-evaluation provenance
    # (auc_live / feedback_coverage) beside the drift fields
    from flink_ml_tpu.observability import evaluation

    def feedback(i, req_frame, fut):
        rid = getattr(fut, "request_id", None)
        if rid is None:
            return
        feats = np.asarray([r.values[0].to_array()
                            for r in req_frame.collect()])
        evaluation.record_feedback(
            rid, (feats @ w_true > 0).astype(np.float64))

    # -- per-request baseline ------------------------------------------------
    def best_of(submit, labeled: bool = False) -> dict:
        best = None
        for _ in range(max(1, args.repeats)):
            r = run_loadgen(submit, request_frame,
                            LoadGenConfig(mode="closed",
                                          requests=n_requests,
                                          concurrency=args.concurrency),
                            feedback=feedback if labeled else None)
            if best is None or r["throughput_rps"] > best["throughput_rps"]:
                best = r
        return best

    baseline_servable = registry.active
    for size in sorted(set(REQUEST_SIZES)):  # warm its shapes too:
        baseline_servable.transform(frame(size))  # compare steady states
    per_request = best_of(baseline_servable.transform)
    print(f"serve_bench: per-request {per_request['throughput_rps']} "
          f"rps, p99 {per_request['latency_ms']['p99']} ms")

    # -- micro-batched runtime: warmup, readiness, measured run --------------
    batcher = MicroBatcher(registry, BatcherConfig(
        buckets=buckets, window_ms=args.window_ms)).start()
    warm_report = warm(batcher, frame_factory=frame)
    srv = server.maybe_start()
    if srv is not None:
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            hz = json.loads(r.read())
        if hz.get("status") != "ok":
            fail(1, f"/healthz not ready after warmup: {hz}")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/serving", timeout=10) as r:
            live = json.loads(r.read())
        if (live.get("serving") or {}).get("servable") != "lr@v1":
            fail(1, f"/serving route does not show the runtime: {live}")

    registry.start_watcher()
    steady_base = compile_count()
    # publish v2 NOW: the watcher adopts it while the measured run is
    # in flight — the zero-downtime hot-swap under load (v2 carries the
    # same training baseline: the coefficients moved, the data did not)
    publish_model(watch_dir, [coef * 1.01], 2, baseline=baseline,
                  quality_baseline=quality_baseline)
    batched = best_of(batcher.submit, labeled=True)
    steady_compiles = compile_count() - steady_base
    swapped_version = registry.version
    registry.stop()
    print(f"serve_bench: batched {batched['throughput_rps']} rps, "
          f"p99 {batched['latency_ms']['p99']} ms, "
          f"steady compiles {steady_compiles}, "
          f"model now v{swapped_version}")

    # -- trace overhead: ring-armed vs dark steady-state p99 -----------------
    # The ALWAYS-ON half of causal tracing is the recent-span ring
    # (tracing.Tracer.recent — the flight recorder's evidence and the
    # /spans/recent route): production serving runs with the ring armed
    # and NO trace dir, so that is the configuration whose cost the
    # gate bounds. Same closed-loop workload at equal offered load,
    # best-of-N p99: ring armed (the per-TICK pad/batch/request spans
    # built and ringed, nothing on disk — the per-request
    # submit/resolve chain gates on an armed trace dir, so it is NOT
    # in this shape) vs fully dark (no spans at all), gated at
    # --trace-overhead-budget (default 5%). The full disk-traced p99
    # (dir + per-span flush + the per-request chain, the debugging
    # mode the measured runs above used) rides along as informational
    # provenance, not a gate.
    def overhead_p99(repeats: int = 3) -> float:
        n = max(120, n_requests // 2)
        best = None
        for _ in range(max(1, repeats)):
            r = run_loadgen(batcher.submit, request_frame,
                            LoadGenConfig(mode="closed", requests=n,
                                          concurrency=args.concurrency))
            p = r["latency_ms"]["p99"]
            best = p if best is None else min(best, p)
        return best

    saved_sample = os.environ.get("FLINK_ML_TPU_TRACE_SAMPLE")
    os.environ["FLINK_ML_TPU_TRACE_SAMPLE"] = "1"  # the probes run at
    # the DEFAULT sampling: the disk leg measures the full debugging
    # mode (per-request chain and all), the ring leg the full
    # always-on production shape — not the headline runs' sample=0
    disk_traced_p99 = overhead_p99()
    tracing.tracer.shutdown()       # close the sink; env still armed
    saved_dir = os.environ.pop("FLINK_ML_TPU_TRACE_DIR")
    saved_ring = tracing.tracer.keep_recent
    traced_p99 = untraced_p99 = None
    try:
        # interleave the A/B runs: host-load drift on a shared runner
        # must hit both modes equally, or the "overhead" would just
        # measure which half-minute was noisier (best-of-N min per
        # mode then kills the outliers)
        for _ in range(4):
            tracing.tracer.keep_recent = True   # always-on production
            p = overhead_p99(repeats=1)         # shape: ring, no dir
            traced_p99 = p if traced_p99 is None else min(traced_p99,
                                                          p)
            tracing.tracer.keep_recent = False  # fully dark
            p = overhead_p99(repeats=1)
            untraced_p99 = p if untraced_p99 is None \
                else min(untraced_p99, p)
    finally:
        os.environ["FLINK_ML_TPU_TRACE_DIR"] = saved_dir
        tracing.tracer.keep_recent = saved_ring
        if saved_sample is None:
            os.environ.pop("FLINK_ML_TPU_TRACE_SAMPLE", None)
        else:
            os.environ["FLINK_ML_TPU_TRACE_SAMPLE"] = saved_sample
    trace_overhead_pct = round(
        (traced_p99 - untraced_p99) / max(untraced_p99, 1e-9) * 100.0,
        2)
    # the budget needs quiet hardware to mean anything: on a 1-core
    # host the p99 noise band is wider than the budget itself (the
    # PR 11 native-threading / PR 12 mesh-throughput precedent) —
    # enforced on >= 4-core hosts, recorded skipped on fewer; an
    # always-on 50% collapse floor catches a real regression anywhere
    overhead_cores = os.cpu_count() or 1
    overhead_enforced = overhead_cores >= 4
    print(f"serve_bench: trace overhead — ring-armed p99 {traced_p99} "
          f"ms vs dark {untraced_p99} ms ({trace_overhead_pct:+.2f}%; "
          f"full disk tracing {disk_traced_p99} ms; budget "
          f"{'enforced' if overhead_enforced else 'skipped'} on "
          f"{overhead_cores} core(s))")

    # -- optional window/bucket sweep ----------------------------------------
    sweep = []
    if not args.smoke:
        for window_ms in (0.5, 2.0, 5.0):
            for table in ((8, 32, 128), (32, 128), (128,)):
                cfg = BatcherConfig(buckets=table, window_ms=window_ms)
                with MicroBatcher(registry, cfg) as b:
                    warm(b, frame_factory=frame, gate=False)
                    r = run_loadgen(
                        b.submit, request_frame,
                        LoadGenConfig(mode="closed",
                                      requests=max(200, n_requests // 4),
                                      concurrency=args.concurrency))
                sweep.append({"window_ms": window_ms,
                              "buckets": list(table),
                              "throughput_rps": r["throughput_rps"],
                              "p50_ms": r["latency_ms"]["p50"],
                              "p99_ms": r["latency_ms"]["p99"]})
                print(f"serve_bench: sweep window={window_ms} "
                      f"buckets={table}: {r['throughput_rps']} rps "
                      f"p99 {r['latency_ms']['p99']} ms")
    batcher.stop()

    # -- optional mesh-sharded dispatch sweep (subprocess cells) -------------
    mesh_sweep = None
    if args.mesh:
        try:
            mesh_sweep = run_mesh_sweep(args, root)
        except Exception as e:  # noqa: BLE001 — a cell that cannot run
            # is a broken environment, not a failed gate
            fail(2, f"mesh sweep environment broken: {e}")

    # -- record + gates ------------------------------------------------------
    ratio = (batched["throughput_rps"]
             / max(per_request["throughput_rps"], 1e-9))
    record = {
        "metric": "lr_serving_closed_loop_throughput",
        "value": batched["throughput_rps"],
        "unit": "requests/s",
        "vs_per_request": round(ratio, 2),
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        # dispatch provenance: the measured runtime above runs the
        # pipelined dispatcher but no mesh (the mesh cells below are
        # subprocesses with their own simulated device counts)
        "meshShape": None,
        "shardedDispatch": batcher.sharded_dispatch(),
        "pipelineDepth": batcher.config.pipeline_depth,
        "requests": n_requests,
        "concurrency": args.concurrency,
        "request_sizes": list(REQUEST_SIZES),
        "buckets": list(buckets),
        "window_ms": args.window_ms,
        "per_request": per_request,
        "batched": batched,
        "warmup": warm_report,
        "steady_compile_count": steady_compiles,
        "hot_swap": {"published": [1, 2],
                     "serving_version": swapped_version,
                     "swapped_mid_run": swapped_version == 2},
        "ftrl_train_ms": round(train_ms, 1),
        # causal-tracing cost provenance (docs/observability.md
        # "Causal tracing, critical path & incidents"): best-of-N p99
        # at equal offered load, armed vs dark — the always-on ring +
        # per-request spans must stay under the budget
        "traceOverheadPct": trace_overhead_pct,
        "trace_overhead": {"tracedP99Ms": traced_p99,
                           "untracedP99Ms": untraced_p99,
                           "diskTracedP99Ms": disk_traced_p99,
                           "budgetPct": args.trace_overhead_budget,
                           "hostCores": overhead_cores,
                           "enforced": overhead_enforced,
                           "skipped": (None if overhead_enforced else
                                       f"host has {overhead_cores} "
                                       f"core(s)")},
        "sweep": sweep,
        "mesh_sweep": mesh_sweep,
    }
    # drift provenance (observability/drift.py): the benchmark's own
    # traffic is drawn from the training distribution, so a non-null
    # psi here that crosses the threshold means the drift layer (not
    # the workload) regressed; baselineVersion proves the publish path
    # shipped the baseline
    from flink_ml_tpu.observability import drift

    drift.drift_report(emit=False)  # refresh the per-servable stats
    record.update(drift.provenance())
    # continuous-evaluation provenance (observability/evaluation.py):
    # the labeled loadgen above joined ground truth back to the served
    # predictions, so aucLive/feedbackCoverage carry real values here;
    # a plain fit bench records nulls on the same schema. The quality
    # block is the per-servable verdict detail (live vs baseline AUC,
    # join coverage, label lag) — BENCH provenance that the published
    # quality baseline actually anchored the live windows
    quality = evaluation.quality_report(emit=False)
    record.update(evaluation.provenance())
    record["quality"] = {
        "degraded": quality["degraded"],
        "thresholds": quality["thresholds"],
        "servables": {
            name: {"source": r["source"],
                   "live": r["live"],
                   "baselineAuc": ((r["baseline"] or {}).get("auc")),
                   "aucDelta": r["aucDelta"],
                   "coverage": r["coverage"],
                   "labelLagP99Ms": r["labelLagP99Ms"],
                   "thin": r["thin"]}
            for name, r in quality["servables"].items()},
    }
    # device-efficiency provenance (observability/profiling.py): the
    # hottest measured fn's utilization/achieved FLOPs when a profile
    # was captured beside this run's trace — null on host-fallback (a
    # CPU run honestly claims no utilization) or with no capture armed
    from flink_ml_tpu.observability import profiling

    record.update(profiling.provenance(trace_dir))
    with open(args.output, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=2)
    print(f"serve_bench: wrote {args.output}")

    tracing.tracer.shutdown()
    dump_metrics(trace_dir)
    spec_path = os.path.join(root, "serving-slo.json")
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(SLO_SPEC, f)
    rc_slo = slo.main([trace_dir, "--spec", spec_path, "--check"])
    if rc_slo != 0:
        fail(rc_slo, f"slo --check exited {rc_slo} on {trace_dir}")

    if batched["errors"] or per_request["errors"]:
        fail(1, f"request errors: batched {batched['errorsByClass']}, "
                f"per-request {per_request['errorsByClass']}")
    if steady_compiles != 0:
        fail(1, f"{steady_compiles} steady-state compile(s) after "
                "warmup — the bucketing contract is broken")
    if args.smoke and swapped_version != 2:
        fail(1, f"hot-swap did not land mid-run (serving v"
                f"{swapped_version})")
    if args.smoke and record.get("aucLive") is None:
        fail(1, "labeled loadgen joined no feedback — aucLive is null "
                "(the evaluation join ring is not receiving)")
    if batched["latency_ms"]["p99"] > args.p99_budget_ms:
        fail(1, f"batched p99 {batched['latency_ms']['p99']} ms over "
                f"the {args.p99_budget_ms} ms budget")
    if ratio < args.min_ratio:
        fail(1, f"batched/per-request ratio {ratio:.2f} below "
                f"{args.min_ratio}")
    if overhead_enforced and \
            trace_overhead_pct > args.trace_overhead_budget:
        fail(1, f"traced steady-state p99 is {trace_overhead_pct:.2f}% "
                f"over untraced — the causal-tracing layer exceeds its "
                f"{args.trace_overhead_budget:g}% budget")
    if trace_overhead_pct > 50.0:
        fail(1, f"traced steady-state p99 is {trace_overhead_pct:.2f}% "
                f"over untraced — the always-on ring collapsed serving "
                f"latency (the unconditional floor)")
    if mesh_sweep is not None and not mesh_sweep["gates"]["ok"]:
        fail(1, "mesh sweep gates failed: "
                + "; ".join(mesh_sweep["failures"]))
    print(f"serve_bench: OK — {ratio:.2f}x over per-request, p99 "
          f"{batched['latency_ms']['p99']} ms, 0 steady compiles, "
          f"hot-swap v{swapped_version}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
