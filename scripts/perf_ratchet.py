#!/usr/bin/env python
"""Automated perf ratchet: pre-vs-post worktree comparison + fusion gates.

The PR 9 pattern, scripted: trace the SAME SGD+KMeans+FTRL workload on
the merge-base checkout (a throwaway ``git worktree``) and on HEAD, then
gate HEAD with ``mltrace diff <pre> <post> --budget`` — span self-time
and compile-count regressions exit 4, exactly like the CI diff gate, but
against the REAL previous code instead of a self-diff. On top of the
diff, the hot-loop-fusion acceptance gates measure and self-gate:

1. **Donation** — the KMeans fit carry (and the SGD/FTRL carries) must
   be consumed in place (``is_deleted``) with ZERO "donated buffers were
   not usable" warnings across the workload.
2. **Segment-boundary fusion** — segment-mode device→host transfers per
   boundary must be exactly 1 (the stacked-scalar bundle), against > 1
   on the pre-fusion path (FLINK_ML_TPU_SEGMENT_FUSION=0).
3. **Native thread sweep** — factorize/doc-freq at 1/2/4 threads must be
   byte-identical at every count; with >= 4 cores the 4-thread pass must
   be >= 1.5x the single-threaded one. On fewer cores the speedup gate
   is recorded as skipped (the BASELINE.md single-core integrity
   precedent — threads cannot beat one core) while the byte-identity
   gate always enforces.

Writes ``BENCH_fusion.json`` (per-fit wall times pre and post, fetch
counts, donation counts, the thread sweep, every gate verdict).

Structure mirrors bench.py/mapreduce_bench.py: the PARENT NEVER IMPORTS
JAX — the workload, probe and native sweep each run in a subprocess, and
the merge-base side runs a self-contained workload script that only uses
APIs stable since PR 9.

Exit codes: 0 ok / 1 gate failed / 2 environment broken (no merge-base,
worktree failure, child crash) / 4 trace-diff regression (mltrace diff's
own code, propagated).
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # run from a checkout without installing
MLTRACE = os.path.join(REPO, "scripts", "mltrace.py")

#: the shared traced workload — run from BOTH worktrees, so it may only
#: use APIs that exist at the merge-base (the PR<=10 public surface):
#: a plain LogisticRegression fit, a checkpointed
#: segment-mode fit, KMeans device + segment-mode fits, and an FTRL
#: stream fit. Prints per-fit wall ms as JSON; tracing/metrics land in
#: FLINK_ML_TPU_TRACE_DIR.
WORKLOAD_SRC = r"""
import json, os, sys, time

sys.path.insert(0, os.getcwd())
import numpy as np

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.iteration.streaming import StreamTable
from flink_ml_tpu.models.classification import LogisticRegression
from flink_ml_tpu.models.clustering import KMeans
from flink_ml_tpu.models.online import OnlineLogisticRegression

rng = np.random.default_rng(7)
n, d = 6000, 24
x = rng.normal(size=(n, d))
y = (x @ rng.normal(size=d) > 0).astype(np.float64)
lr_table = Table.from_columns(features=x, label=y)
km_table = Table.from_columns(
    features=rng.normal(size=(n, d // 2)).astype(np.float32))

ckpt_root = sys.argv[1]
out = {}


def timed(name, fit):
    fit()                     # warmup: compile excluded (bench protocol)
    t0 = time.perf_counter()
    fit()
    out[name] = round((time.perf_counter() - t0) * 1000.0, 3)


timed("lr_plain", lambda: LogisticRegression(
    max_iter=12, global_batch_size=512, learning_rate=0.05,
    reg=0.01, elastic_net=0.3).fit(lr_table))

timed("lr_segmented", lambda: LogisticRegression(
    max_iter=12, global_batch_size=512,
    learning_rate=0.05).set_iteration_config(IterationConfig(
        mode="device", checkpoint_interval=4,
        checkpoint_manager=CheckpointManager(
            os.path.join(ckpt_root, "lr")))).fit(lr_table))

timed("kmeans_plain", lambda: KMeans(
    k=8, seed=3, max_iter=10).fit(km_table))

timed("kmeans_segmented", lambda: KMeans(
    k=8, seed=3, max_iter=10).set_iteration_config(IterationConfig(
        mode="device", checkpoint_interval=5,
        checkpoint_manager=CheckpointManager(
            os.path.join(ckpt_root, "km")))).fit(km_table))

bs = 256
xf = rng.normal(size=(16 * bs, d)).astype(np.float32)
yf = (xf @ rng.normal(size=d) > 0).astype(float)
ftrl_table = Table.from_columns(features=xf, label=yf)
init = Table.from_columns(coefficient=np.zeros((1, d)),
                          modelVersion=np.asarray([0]))


def ftrl_fit():
    est = OnlineLogisticRegression(global_batch_size=bs, reg=0.01,
                                   elastic_net=0.3)
    est.set_initial_model_data(init)
    return est.fit(StreamTable.from_table(ftrl_table, bs))


timed("ftrl", ftrl_fit)

from flink_ml_tpu.observability import tracing

tracing.maybe_dump_root_metrics()
print(json.dumps(out), flush=True)
"""


# ---------------------------------------------------------------------------
# HEAD-side children
# ---------------------------------------------------------------------------

def run_probe() -> dict:
    """Donation + segment-fetch measurements on the CURRENT checkout."""
    import warnings

    import numpy as np

    donation_warnings = []
    warnings.simplefilter("always")
    _orig = warnings.showwarning
    warnings.showwarning = lambda m, c, *a, **k: (
        donation_warnings.append(str(m))
        if "donat" in str(m).lower() else None)

    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.common.metrics import metrics
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.models.clustering import KMeans
    from flink_ml_tpu.models.clustering.kmeans import _build_lloyd_program
    from flink_ml_tpu.parallel.collective import ensure_on_mesh
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    rng = np.random.default_rng(7)
    out: dict = {}

    # -- donation: the KMeans carry is consumed in place -------------------
    mesh = default_mesh()
    x = rng.normal(size=(512, 8)).astype(np.float32)
    xs, _ = ensure_on_mesh(mesh, x, data_axes(mesh), jnp.float32)
    c0 = jax.device_put(jnp.asarray(x[:4]))
    counts0 = jax.device_put(jnp.zeros((4,), jnp.float32))
    prog = _build_lloyd_program(mesh, "euclidean", 6, unroll=False)
    jax.block_until_ready(prog(xs, jnp.int32(512), c0, counts0))
    out["donationConsumed"] = int(c0.is_deleted()) + int(
        counts0.is_deleted())

    # full public-API fits must stay donation-warning-free
    KMeans(k=4, seed=3, max_iter=8).fit(
        Table.from_columns(features=x))
    xl = rng.normal(size=(2048, 12))
    yl = (xl @ rng.normal(size=12) > 0).astype(np.float64)
    lr_table = Table.from_columns(features=xl, label=yl)
    LogisticRegression(max_iter=8, global_batch_size=256).fit(lr_table)

    # -- segment fetches: fused == 1 per boundary, pre-fusion > 1 ----------
    def fetches_per_boundary(fused, sub):
        os.environ["FLINK_ML_TPU_SEGMENT_FUSION"] = "1" if fused else "0"

        def counts():
            snap = metrics.snapshot().get("ml.iteration", {}).get(
                "counters", {})
            return (int(snap.get("boundaryFetches", 0)),
                    int(snap.get("boundaries", 0)))

        f0, b0 = counts()
        cfg = IterationConfig(
            mode="device", checkpoint_interval=3,
            checkpoint_manager=CheckpointManager(
                os.path.join(tempfile.mkdtemp(), sub)))
        LogisticRegression(max_iter=12, global_batch_size=256) \
            .set_iteration_config(cfg).fit(lr_table)
        f1, b1 = counts()
        # the fitted state's two leaves (coefficients, loss) cross
        # through read_boundary too, once a fit under one wait of their
        # own: they are no boundary's
        return round((f1 - f0 - 2) / max(b1 - b0, 1), 3)

    out["fusedFetchesPerBoundary"] = fetches_per_boundary(True, "f")
    out["unfusedFetchesPerBoundary"] = fetches_per_boundary(False, "u")
    os.environ.pop("FLINK_ML_TPU_SEGMENT_FUSION", None)

    out["donationWarnings"] = len(donation_warnings)
    out["donationWarningSamples"] = donation_warnings[:3]
    warnings.showwarning = _orig
    return out


def run_native_sweep(threads=(1, 2, 4)) -> dict:
    """Native factorize/doc-freq thread sweep: best-of-3 wall per thread
    count + byte-identity against the single-threaded output."""
    import numpy as np

    from flink_ml_tpu import native

    if not native.available():
        return {"available": False}
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 300_000, size=6_000_000).astype(np.int64)
    u = 4096
    codes = rng.integers(0, u, size=(400_000, 12)).astype(np.int64)

    def best_of(fn, reps=3):
        best = float("inf")
        result = None
        for _ in range(reps):
            t0 = time.perf_counter()
            result = fn()
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        return best, result

    out: dict = {"available": True, "cores": os.cpu_count(),
                 "keys": len(keys), "docFreqCells": int(codes.size)}
    base_fact = base_df = None
    for kernel, fn in (
            ("factorize",
             lambda t: native.factorize_i64(keys, n_threads=t)),
            ("docFreq",
             lambda t: native.doc_freq_i64(codes, u, n_threads=t))):
        rec: dict = {"wallMs": {}, "byteIdentical": True}
        base = None
        for t in threads:
            ms, result = best_of(lambda t=t: fn(t))
            rec["wallMs"][str(t)] = round(ms, 3)
            if t == threads[0]:
                base = result
            else:
                same = (all(np.array_equal(a, b)
                            for a, b in zip(base, result))
                        if isinstance(base, tuple)
                        else np.array_equal(base, result))
                rec["byteIdentical"] = rec["byteIdentical"] and bool(same)
        hi = str(threads[-1])
        lo = str(threads[0])
        rec["speedupAt%s" % hi] = round(
            rec["wallMs"][lo] / max(rec["wallMs"][hi], 1e-9), 3)
        out[kernel] = rec
    return out


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _git(*args, cwd=REPO) -> str:
    return subprocess.run(["git", *args], cwd=cwd, capture_output=True,
                          text=True, check=True).stdout.strip()


def resolve_base(base_arg) -> str:
    if base_arg:
        return _git("rev-parse", base_arg)
    for ref in ("origin/main", "origin/master"):
        try:
            return _git("merge-base", "HEAD", ref)
        except subprocess.CalledProcessError:
            continue
    return _git("rev-parse", "HEAD~1")


def _spawn_child(mode: str, timeout=1200) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), mode],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"{mode} child failed (rc={proc.returncode}):\n"
                           f"{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_workload(cwd: str, trace_dir: str, timeout=1200) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLINK_ML_TPU_TRACE_DIR=trace_dir)
    env.pop("FLINK_ML_TPU_SEGMENT_FUSION", None)
    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, "ratchet_workload.py")
        with open(script, "w") as f:
            f.write(WORKLOAD_SRC)
        proc = subprocess.run(
            [sys.executable, script, os.path.join(tmp, "ckpt")],
            env=env, cwd=cwd, capture_output=True, text=True,
            timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"workload in {cwd} failed (rc={proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="perf_ratchet")
    parser.add_argument("--base", default=None,
                        help="merge-base ref/sha (default: merge-base "
                             "with origin/main, else HEAD~1)")
    parser.add_argument("--budget", type=float, default=25.0,
                        help="mltrace diff span/compile budget %%")
    parser.add_argument("--min-ms", type=float, default=100.0,
                        help="mltrace diff self-time floor (wall jitter "
                             "on shared runners)")
    parser.add_argument("--output",
                        default=os.path.join(REPO, "BENCH_fusion.json"))
    parser.add_argument("--trace-root", default=None,
                        help="where the pre/post trace dirs land "
                             "(default: a temp dir; pass a path to keep "
                             "them for CI artifact upload)")
    parser.add_argument("--probe", action="store_true",
                        help="(internal) donation/fetch probe child")
    parser.add_argument("--native-sweep", action="store_true",
                        help="(internal) native thread sweep child")
    args = parser.parse_args(argv)

    if args.probe:
        print(json.dumps(run_probe()), flush=True)
        return 0
    if args.native_sweep:
        print(json.dumps(run_native_sweep()), flush=True)
        return 0

    record: dict = {"gates": {}, "failures": []}
    failures = record["failures"]

    # -- resolve base + worktree -------------------------------------------
    try:
        head = _git("rev-parse", "HEAD")
        base = resolve_base(args.base)
    except subprocess.CalledProcessError as e:
        print(f"environment broken (git): {e.stderr}", file=sys.stderr)
        return 2
    record["head"], record["base"] = head, base
    if base == head:
        print("merge-base equals HEAD — nothing to ratchet against",
              file=sys.stderr)
        return 2

    trace_root = args.trace_root or tempfile.mkdtemp(
        prefix="perf-ratchet-")
    os.makedirs(trace_root, exist_ok=True)
    record["traceRoot"] = trace_root
    pre_dir = os.path.join(trace_root, "pre")
    post_dir = os.path.join(trace_root, "post")
    worktree = tempfile.mkdtemp(prefix="ratchet-base-")
    shutil.rmtree(worktree)  # git worktree add wants to create it

    try:
        _git("worktree", "add", "--detach", worktree, base)
    except subprocess.CalledProcessError as e:
        print(f"environment broken (worktree): {e.stderr}",
              file=sys.stderr)
        return 2

    try:
        # -- the pre-vs-post traced workload -------------------------------
        try:
            print(f"[ratchet] workload @ base {base[:12]}",
                  file=sys.stderr, flush=True)
            record["pre"] = run_workload(worktree, pre_dir)
            print(f"[ratchet] workload @ HEAD {head[:12]}",
                  file=sys.stderr, flush=True)
            record["post"] = run_workload(REPO, post_dir)
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            print(f"environment broken (workload): {e}", file=sys.stderr)
            return 2

        # -- the diff gate (HEAD's mltrace reads both artifact sets) -------
        diff = subprocess.run(
            [sys.executable, MLTRACE, "diff", pre_dir, post_dir,
             "--budget", str(args.budget), "--min-ms", str(args.min_ms)],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        record["diff"] = {"exit": diff.returncode,
                          "budgetPct": args.budget, "minMs": args.min_ms}
        print(diff.stdout, file=sys.stderr)
        if diff.returncode == 2:
            print("environment broken (diff rejected the artifacts):\n"
                  + diff.stderr, file=sys.stderr)
            return 2

        # -- fusion gates ---------------------------------------------------
        try:
            probe = _spawn_child("--probe")
            native = _spawn_child("--native-sweep")
        except (RuntimeError, subprocess.TimeoutExpired,
                json.JSONDecodeError) as e:
            print(f"environment broken (probe): {e}", file=sys.stderr)
            return 2
        record["probe"] = probe
        record["native"] = native

        if probe["donationConsumed"] < 2:
            failures.append("KMeans fit carry not consumed in place "
                            f"(consumed={probe['donationConsumed']})")
        if probe["donationWarnings"]:
            failures.append(
                f"{probe['donationWarnings']} donation warnings: "
                f"{probe['donationWarningSamples']}")
        if probe["fusedFetchesPerBoundary"] != 1.0:
            failures.append(
                "fused segment boundary costs "
                f"{probe['fusedFetchesPerBoundary']} transfers (want 1)")
        if probe["unfusedFetchesPerBoundary"] <= \
                probe["fusedFetchesPerBoundary"]:
            failures.append("pre-fusion path not measurably worse — the "
                            "fetch counter is broken")
        record["gates"]["donation"] = {
            "consumed": probe["donationConsumed"],
            "warnings": probe["donationWarnings"]}
        record["gates"]["segmentFetches"] = {
            "fusedPerBoundary": probe["fusedFetchesPerBoundary"],
            "unfusedPerBoundary": probe["unfusedFetchesPerBoundary"]}

        if not native.get("available"):
            failures.append("native tier unavailable (g++ build failed) "
                            "— the thread sweep cannot run")
        else:
            cores = native.get("cores") or 1
            enforce = cores >= 4
            gate = {"speedupGate": ("enforced" if enforce else
                                    f"skipped ({cores}-core host — "
                                    "threads cannot beat one core; the "
                                    "BASELINE.md integrity precedent)")}
            for kernel in ("factorize", "docFreq"):
                rec = native[kernel]
                gate[kernel] = {"speedupAt4": rec.get("speedupAt4"),
                                "byteIdentical": rec["byteIdentical"]}
                if not rec["byteIdentical"]:
                    failures.append(
                        f"native {kernel}: threaded output differs from "
                        "single-threaded (must be byte-identical)")
                if enforce and rec.get("speedupAt4", 0) < 1.5:
                    failures.append(
                        f"native {kernel}: {rec.get('speedupAt4')}x at 4 "
                        f"threads on a {cores}-core host (need >= 1.5x)")
            record["gates"]["nativeThreads"] = gate

        record["gates"]["diffExit"] = diff.returncode
        record["gates"]["ok"] = (not failures
                                 and diff.returncode == 0)

        with open(args.output, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(json.dumps({"output": args.output,
                          "ok": record["gates"]["ok"],
                          "diffExit": diff.returncode,
                          "failures": failures}, indent=2))

        if diff.returncode != 0:
            return 4
        return 1 if failures else 0
    finally:
        subprocess.run(["git", "worktree", "remove", "--force", worktree],
                       cwd=REPO, capture_output=True)


if __name__ == "__main__":
    sys.exit(main())
