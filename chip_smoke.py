#!/usr/bin/env python
"""Chip smoke: the quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the training path and the serving path once,
through the entry points a user calls, at the full width of models the repo
ships, on the device JAX reports — ONE process, no child that needs the chip,
a mesh over all ``jax.devices()`` (so the same file is the one-chip and the
four-chip run). It times nothing for the record: every number it prints is a
smoke fact, not a benchmark.

Phases (each prints one JSON line, ``{"phase": ..., "ok": ...}``):

- ``lr_fit``     LogisticRegression on the vendored north-star config
                 (10M x 100 f32, batch 100k, 20 rounds) through
                 ``benchmark.runner.run_benchmark`` -> ``Estimator.fit`` ->
                 ``SGD.optimize`` (the ``lax.while_loop`` program as one
                 segment), then the same width on learnable labels against
                 a float64 reference, and the same program run as
                 checkpointed K-round segments against the plain fit.
- ``kmeans``     KMeans fit + transform on the vendored config (1M x 100,
                 k = 10).
- ``serving``    FTRL-train -> ``publish_model`` -> ``ModelRegistry.poll`` ->
                 ``MicroBatcher`` over the device LR servable -> ``warm`` ->
                 requests of 1-4 rows; answers against numpy, and no compile
                 after warm-up.
- ``kernels``    every Pallas kernel, compiled, against its XLA twin at the
                 shapes the fits use (``scripts/tpu_kernel_check.py``).
- ``host_tier``  a CountVectorizer fit large enough to fork the host pool
                 from a process that holds a live device client.
- ``multichip``  (more than one device) full-batch LR on all devices against
                 one device.

The run exits non-zero — and prints no result line — when
``jax.devices()[0].platform`` is not ``tpu`` (no flag or environment variable
turns that off), when any phase fails, or when it is run from a directory
that holds nothing else of the repo. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

XLA's persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.jax_cache`` (``flink_ml_tpu/utils/
compile_cache.py``); the run reports the directory, its entry count before
and after, and how many compiles reached the backend, so a second run in the
same place shows the cache working.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG_DIR = os.path.join(REPO, "flink_ml_tpu", "benchmark", "configs")


class SmokeFailure(Exception):
    """A phase's result check failed."""


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# -- what the run observes about itself --------------------------------------

class CompileCounter:
    """Counts compile requests and persistent-cache hits from the
    ``jax.monitoring`` channels; a request that was not a cache hit reached
    the backend compiler."""

    def __init__(self):
        from jax import monitoring

        self.requests = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def backend_compiles(self) -> int:
        return self.requests - self.cache_hits


def mesh_shape(mesh) -> str:
    return ",".join(f"{a}={int(mesh.shape[a])}" for a in mesh.axis_names)


def require_row_sharded(name: str, array, mesh, n_rows: int) -> dict:
    """The array is a device array with ONE addressable shard per mesh
    device, each holding ``n_rows / devices`` rows — nothing silently on
    device 0."""
    import jax

    require(isinstance(array, jax.Array), f"{name} is not a device array")
    devices = list(mesh.devices.flat)
    shards = array.addressable_shards
    require({s.device for s in shards} == set(devices),
            f"{name} lives on {sorted(str(s.device) for s in shards)}, "
            f"not on every device of the mesh")
    require(len(shards) == len(devices),
            f"{name} has {len(shards)} shards for {len(devices)} devices")
    rows = sorted({int(s.data.shape[0]) for s in shards})
    require(rows == [n_rows // len(devices)],
            f"{name} shard rows {rows}, expected {n_rows // len(devices)}")
    require({d.platform for d in array.devices()}
            == {devices[0].platform}, f"{name} is on another platform")
    return {"shards": len(shards), "rowsPerShard": rows[0]}


def vendored(config_file: str, stage: dict = None, data: dict = None):
    """``(name, spec)`` of a single-benchmark vendored config, with
    optional paramMap overrides (the tier-1 tests cut sizes with them;
    ``main`` passes none)."""
    from flink_ml_tpu.benchmark.runner import load_config

    (name, spec), = load_config(
        os.path.join(CONFIG_DIR, config_file)).items()
    spec["stage"].setdefault("paramMap", {}).update(stage or {})
    spec["inputData"].setdefault("paramMap", {}).update(data or {})
    return name, spec


def build(spec_part: dict, resolve):
    obj = resolve(spec_part["className"])()
    obj.params_from_json(spec_part.get("paramMap", {}), strict=True)
    return obj


def run_row(name: str, spec: dict) -> dict:
    """One ``run_benchmark`` row, cut to what a smoke reports."""
    from flink_ml_tpu.benchmark.runner import run_benchmark

    row = run_benchmark(name, spec)
    return {"executionPath": row.get("executionPath"),
            "wallMs": round(row["totalTimeMs"], 1),
            "dataGenMs": round(row["dataGenTimeMs"], 1),
            "compileCount": row["compileCount"],
            "inputRecordNum": row["inputRecordNum"]}


# -- LR fit -------------------------------------------------------------------

def reference_sgd(x_dev, y_dev, n_shards: int, batch: int, rounds: int,
                  learning_rate: float) -> np.ndarray:
    """Plain float64 minibatch SGD for binary logistic loss over the rows
    the fit's static schedule visits: round ``r`` takes rows
    ``[r*lb, (r+1)*lb)`` of every shard (``lb = batch / shards``), unit
    sample weights, ``w -= lr / batch * grad``. Only the visited rows are
    fetched from the device."""
    n, d = x_dev.shape
    local_n, lb = n // n_shards, batch // n_shards
    require(rounds * lb <= local_n and batch % n_shards == 0,
            "reference_sgd covers the no-wrap, uniform-share schedule only")
    xs = [np.asarray(x_dev[s * local_n: s * local_n + rounds * lb],
                     np.float64) for s in range(n_shards)]
    ys = [np.asarray(y_dev[s * local_n: s * local_n + rounds * lb],
                     np.float64) for s in range(n_shards)]
    w = np.zeros(d, np.float64)
    for r in range(rounds):
        xb = np.concatenate([x[r * lb:(r + 1) * lb] for x in xs])
        sign = 2.0 * np.concatenate([y[r * lb:(r + 1) * lb]
                                     for y in ys]) - 1.0
        margins = (xb @ w) * sign
        grad = xb.T @ (-sign / (np.exp(margins) + 1.0))
        w -= learning_rate / len(sign) * grad
    return w


def lr_fit_phase(mesh, stage: dict = None, data: dict = None,
                 ckpt_rounds: int = 6, ckpt_interval: int = 2,
                 sample_rows: int = 200_000, min_accuracy: float = 0.75,
                 max_loss: float = 0.685, ref_tol: float = 1e-4,
                 seg_tol: float = 1e-6) -> dict:
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.benchmark.datagen import resolve_generator
    from flink_ml_tpu.benchmark.runner import resolve_stage
    from flink_ml_tpu.iteration.checkpoint import CheckpointManager
    from flink_ml_tpu.iteration.iteration import IterationConfig
    from flink_ml_tpu.parallel.mesh import data_shard_count

    name, spec = vendored("logisticregression-benchmark.json", stage, data)
    want_path = "xla-while"
    out = {"vendored": run_row(name, spec)}
    require(out["vendored"]["executionPath"] == want_path,
            f"vendored LR fit took {out['vendored']['executionPath']!r}, "
            f"expected {want_path!r}")

    # the same table with labels a linear model can learn: the vendored
    # generator's labels are independent of its features, so nothing about
    # the fitted model could be checked on them
    table = build(spec["inputData"], resolve_generator).get_data()
    x = table.column("features")
    n, d = x.shape
    p = data_shard_count(mesh)
    out["input"] = require_row_sharded("LR features", x, mesh, n)
    require_row_sharded("LR label", table.column("label"), mesh, n)
    w_true = np.random.default_rng(5).normal(size=d).astype(np.float32)
    y = jax.jit(
        lambda xs: (xs @ w_true > 0.5 * float(w_true.sum())).astype(
            jnp.float32),
        out_shardings=table.column("label").sharding)(x)
    table = table.with_column("label", y)

    def fit(max_iter=None, config=None):
        est = build(spec["stage"], resolve_stage)
        if max_iter is not None:
            est.set_max_iter(max_iter)
        if config is not None:
            est.set_iteration_config(config)
        model = est.fit(table)
        coef = np.asarray(model.coefficients, np.float64)
        require(coef.shape == (d,) and np.isfinite(coef).all(),
                "LR coefficients are not finite (d,) values")
        return est, coef

    est, coef = fit()
    out["learnablePath"] = est.last_execution_path
    require(est.last_execution_path == want_path,
            f"learnable LR fit took {est.last_execution_path!r}")

    # loss fell and the model separates the generated labels, on a sample
    m = min(sample_rows, n // p)
    xs, ys = np.asarray(x[:m], np.float64), np.asarray(y[:m], np.float64)
    margins = (xs @ coef) * (2.0 * ys - 1.0)
    loss = float(np.mean(np.logaddexp(0.0, -margins)))
    accuracy = float(np.mean((xs @ coef >= 0) == (ys > 0.5)))
    out.update(loss=round(loss, 5), lossAtZero=round(math.log(2.0), 5),
               accuracy=round(accuracy, 4))
    require(loss < max_loss, f"LR loss {loss:.5f} did not fall below "
            f"{max_loss} (ln 2 = {math.log(2.0):.5f})")
    require(accuracy > min_accuracy,
            f"LR accuracy {accuracy:.4f} <= {min_accuracy}")

    # the while-loop rounds hold float32 on the chip: 6.1e-7 of the
    # float64 reference's largest entry read there (PERF.md section 6, PR
    # 31); the tolerance is the benchmark's own limit for that number, and
    # the observed error is printed
    ref = reference_sgd(x, y, p, est.global_batch_size, est.max_iter,
                        est.learning_rate)
    err = float(np.max(np.abs(coef - ref)) / np.max(np.abs(ref)))
    out["refRelErr"] = float(f"{err:.3g}")
    require(err < ref_tol, f"LR coefficients differ from the float64 "
            f"reference by {err:.3g} of its largest entry (tol {ref_tol})")

    # the same program as K-round segments with the carry snapshotted
    # between them, against the plain fit's one segment
    class CountingManager(CheckpointManager):
        saves = 0  # a completed fit clears its snapshots: count them

        def save(self, *args, **kwargs):
            self.saves += 1
            return super().save(*args, **kwargs)

    _, coef_plain = fit(max_iter=ckpt_rounds)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-ckpt-") as ckpt_dir:
        manager = CountingManager(ckpt_dir)
        seg_est, coef_seg = fit(
            max_iter=ckpt_rounds,
            config=IterationConfig(
                mode="device", checkpoint_interval=ckpt_interval,
                checkpoint_manager=manager))
    out.update(segmentPath=seg_est.last_execution_path,
               checkpointSaves=manager.saves)
    require(seg_est.last_execution_path == "xla-while-segments",
            f"checkpointed fit took {seg_est.last_execution_path!r}")
    require(manager.saves > 0, "the checkpointed fit saved no snapshot")
    seg_err = float(np.max(np.abs(coef_seg - coef_plain))
                    / np.max(np.abs(coef_plain)))
    out["segmentVsPlainRelErr"] = float(f"{seg_err:.3g}")
    require(seg_err < seg_tol, f"the checkpointed and the plain fit differ "
            f"by {seg_err:.3g} (tol {seg_tol})")
    return out


# -- KMeans -------------------------------------------------------------------

def kmeans_phase(mesh, stage: dict = None, data: dict = None,
                 sample_rows: int = 100_000, tie_tol: float = 2e-2) -> dict:
    from flink_ml_tpu.benchmark.datagen import resolve_generator
    from flink_ml_tpu.benchmark.runner import resolve_stage
    from flink_ml_tpu.ops.pallas_kernels import pallas_supported

    name, spec = vendored("kmeans-benchmark.json", stage, data)
    kernel = pallas_supported()
    want_fit = "pallas-lloyd" if kernel else "xla-lloyd"
    want_assign = "pallas-assign" if kernel else "xla-assign"
    out = {"vendored": run_row(name, spec)}
    require(out["vendored"]["executionPath"] == want_fit,
            f"vendored KMeans fit took "
            f"{out['vendored']['executionPath']!r}, expected {want_fit!r}")

    table = build(spec["inputData"], resolve_generator).get_data()
    x = table.column("features")
    n, d = x.shape
    out["input"] = require_row_sharded("KMeans features", x, mesh, n)

    def fit(max_iter=None):
        est = build(spec["stage"], resolve_stage)
        if max_iter is not None:
            est.set_max_iter(max_iter)
        model = est.fit(table)
        require(est.last_execution_path == want_fit,
                f"KMeans fit took {est.last_execution_path!r}")
        c = np.asarray(model.centroids, np.float64)
        require(c.shape == (est.k, d) and np.isfinite(c).all(),
                "KMeans centroids are not finite (k, d) values")
        # exact: any other total means dropped or double-counted rows
        require(float(np.sum(model.weights)) == float(n),
                f"KMeans weights sum to {np.sum(model.weights)}, not {n}")
        return model, c

    t0 = time.perf_counter()
    model, c_full = fit()
    _, c_one = fit(max_iter=1)
    t1 = time.perf_counter()
    labels = np.asarray(model.transform(table)[0].column(
        model.prediction_col))
    t2 = time.perf_counter()
    require(model.last_execution_path == want_assign,
            f"KMeans transform took {model.last_execution_path!r}")
    require(labels.shape == (n,), f"KMeans labels shape {labels.shape}")

    # Lloyd's is monotone: the full fit's centroids must beat the
    # one-round fit's on the same points; and the transform's label must
    # be a nearest centroid up to the chip's matmul rounding
    m = min(sample_rows, n)
    xs = np.asarray(x[:m], np.float64)

    def sq_dists(c):
        return ((xs * xs).sum(1)[:, None] - 2.0 * xs @ c.T
                + (c * c).sum(1)[None, :])

    d_full = sq_dists(c_full)
    inertia_full = float(d_full.min(1).mean())
    inertia_one = float(sq_dists(c_one).min(1).mean())
    shift = float(np.sqrt(np.sum((c_full - c_one) ** 2)))
    chosen = d_full[np.arange(m), labels[:m]]
    tie_err = float(np.max(chosen / d_full.min(1)) - 1.0)
    out.update(fitsS=round(t1 - t0, 2), transformS=round(t2 - t1, 2),
               checksS=round(time.perf_counter() - t2, 2),
               inertiaOneRound=round(inertia_one, 5),
               inertiaFullFit=round(inertia_full, 5),
               centroidShift=round(shift, 5),
               transformPath=model.last_execution_path,
               assignRelExcess=float(f"{tie_err:.3g}"))
    require(shift > 0 and inertia_full < inertia_one,
            f"KMeans did not improve on its first round: inertia "
            f"{inertia_one} -> {inertia_full}, shift {shift}")
    require(tie_err < tie_tol, f"a transform label is {tie_err:.3g} "
            f"farther than the nearest centroid (tol {tie_tol})")
    return out


# -- serving ------------------------------------------------------------------

def serving_phase(mesh, dim: int = 100, rows: int = 300_000,
                  batch: int = 100_000, requests: int = 48,
                  buckets=(8, 32), prob_tol: float = 2e-2) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    from flink_ml_tpu.common.table import Table, as_dense_vector_column
    from flink_ml_tpu.linalg.vectors import DenseVector
    from flink_ml_tpu.models.online import OnlineLogisticRegression
    from flink_ml_tpu.parallel.mesh import data_shard_count
    from flink_ml_tpu.servable.api import DataFrame, DataTypes, Row
    from flink_ml_tpu.servable.lr import (
        LogisticRegressionModelData,
        LogisticRegressionModelServable,
    )
    from flink_ml_tpu.serving import (
        BatcherConfig,
        MicroBatcher,
        ModelRegistry,
        compile_count,
        publish_model,
        warm,
    )

    # the train-while-serve producer: FTRL over a few dense batches (the
    # dense device program of models/online.py)
    rng = np.random.default_rng(7)
    w_true = rng.normal(size=dim)
    x = rng.normal(size=(rows, dim)).astype(np.float32)
    y = (x @ w_true > 0).astype(np.float64)
    est = OnlineLogisticRegression(global_batch_size=batch, alpha=0.5,
                                   beta=0.5)
    est.set_initial_model_data(Table.from_columns(
        coefficient=as_dense_vector_column(np.zeros((1, dim))),
        modelVersion=np.asarray([0], np.int64)))
    model = est.fit(Table.from_columns(features=x, label=y))
    coef = np.asarray(model.coefficients, np.float64)
    require(est.last_execution_path == "device-batches",
            f"FTRL fit took {est.last_execution_path!r}")
    require(coef.shape == (dim,) and np.isfinite(coef).all(),
            "FTRL coefficients are not finite")
    train_acc = float(np.mean((x[:batch] @ coef >= 0) == (y[:batch] > 0.5)))
    require(train_acc > 0.8, f"FTRL accuracy {train_acc:.3f} <= 0.8")

    def loader(leaves, version):
        servable = LogisticRegressionModelServable().set_device_predict(True)
        servable.model_data = LogisticRegressionModelData(
            np.asarray(leaves[0], np.float64), version)
        return servable

    def frame(values: np.ndarray) -> DataFrame:
        return DataFrame(["features"], [DataTypes.vector()],
                         [Row([DenseVector(v)]) for v in values])

    sizes = [(1, 2, 4)[i % 3] for i in range(requests)]
    inputs = [rng.normal(size=(k, dim)) for k in sizes]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-models-") as root:
        watch_dir = os.path.join(root, "models")
        publish_model(watch_dir, [coef], 1)
        registry = ModelRegistry(
            watch_dir, loader, model="lr", mesh=mesh,
            probe=lambda: frame(np.zeros((buckets[0], dim))))
        require(registry.poll(), "the registry did not adopt version 1")
        batcher = MicroBatcher(registry, BatcherConfig(
            buckets=tuple(buckets), window_ms=2.0), mesh=mesh).start()
        try:
            report = warm(batcher, gate=False)
            steady = compile_count()
            with ThreadPoolExecutor(8) as pool:
                futures = [pool.submit(
                    lambda v=v: batcher.submit(frame(v)).result(timeout=120))
                    for v in inputs]
                answers = [f.result(timeout=180) for f in futures]
            steady_compiles = compile_count() - steady
        finally:
            batcher.stop()

    # answers against a numpy evaluation of the PUBLISHED coefficients
    worst = 0.0
    for v, df in zip(inputs, answers):
        dots = v @ coef
        want = 1.0 - 1.0 / (1.0 + np.exp(dots))
        got = np.asarray([r.to_array()[1]
                          for r in df.get("rawPrediction").values])
        require(got.shape == want.shape, "a request lost or gained rows")
        worst = max(worst, float(np.max(np.abs(got - want))))
        pred = np.asarray(df.get("prediction").values)
        firm = np.abs(dots) > 0.05  # margins the chip's rounding can flip
        require(np.array_equal(pred[firm], (dots[firm] >= 0).astype(float)),
                "a served prediction disagrees with the coefficients")
    sharded = report["sharded_buckets"]
    require(worst < prob_tol, f"served probabilities differ from numpy by "
            f"{worst:.3g} (tol {prob_tol})")
    require(report["compiles"] > 0, "warm-up compiled nothing")
    require(steady_compiles == 0,
            f"{steady_compiles} compile(s) after warm-up")
    require(bool(sharded) == (data_shard_count(mesh) > 1),
            f"sharded buckets {sharded} on a {mesh_shape(mesh)} mesh")
    return {"trainPath": est.last_execution_path,
            "trainAccuracy": round(train_acc, 4),
            "requests": len(answers), "buckets": list(buckets),
            "shardedBuckets": sharded, "meshDevices": report["mesh_devices"],
            "warmCompiles": report["compiles"],
            "steadyCompiles": steady_compiles,
            "probMaxAbsErr": float(f"{worst:.3g}")}


# -- kernels ------------------------------------------------------------------

def kernels_phase(shrink: int = 1) -> dict:
    """Every Pallas kernel, compiled, against its XLA twin at the shapes
    the fits use — ``scripts/tpu_kernel_check.py`` run in this process."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpu_kernel_check",
        os.path.join(REPO, "scripts", "tpu_kernel_check.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    rc = module.main(shrink=shrink)
    require(rc == 0, f"tpu_kernel_check exited {rc} (2 = wrong results, "
            f"3 = a kernel did not lower, compile or run)")
    return {"kernels": ["assign_nearest", "lloyd_partial_sums",
                        "category_counts", "grouped_moments",
                        "segment_reduce_sum", "knn_topk_indices"],
            "rc": rc}


# -- host tier ----------------------------------------------------------------

def host_tier_phase(num_values: int = 400_000, array_size: int = 10,
                    distinct: int = 100) -> dict:
    """A CountVectorizer fit large enough to fork the host pool
    (common/hostpool.py) from a process that holds a live device client
    and its threads; afterwards the parent's client must still work."""
    import resource

    import jax.numpy as jnp

    from flink_ml_tpu.benchmark.datagen import RandomStringArrayGenerator
    from flink_ml_tpu.common.hostpool import host_parallelism
    from flink_ml_tpu.models.feature import CountVectorizer

    # a wedged child is killed at this deadline and fails the phase —
    # it must never hang the one process that holds the chip
    os.environ.setdefault("FLINK_ML_TPU_HOST_TIMEOUT_S", "180")
    gen = RandomStringArrayGenerator()
    gen.params_from_json({"colNames": [["input"]], "seed": 2,
                          "numValues": num_values, "arraySize": array_size,
                          "numDistinctValues": distinct}, strict=True)
    table = gen.get_data()
    before = resource.getrusage(resource.RUSAGE_CHILDREN)
    model = CountVectorizer().set_input_col("input").fit(table)
    after = resource.getrusage(resource.RUSAGE_CHILDREN)
    child_cpu = ((after.ru_utime + after.ru_stime)
                 - (before.ru_utime + before.ru_stime))
    vocab = len(model.vocabulary)
    require(vocab == distinct, f"vocabulary {vocab}, expected {distinct}")
    if host_parallelism() > 1:
        require(child_cpu > 0, "the host pool forked no worker")
    alive = float(jnp.arange(8.0).sum())
    require(alive == 28.0, "the device client did not survive the fork")
    return {"workers": host_parallelism(), "vocabulary": vocab,
            "childCpuS": round(child_cpu, 3)}


# -- more than one device -----------------------------------------------------

def multichip_phase(mesh, rows: int = 400_000, dim: int = 100,
                    max_iter: int = 5, tol: float = 1e-3) -> dict:
    """Full-batch LR on every device against ONE device: with the whole
    table as the batch the two fits see identical data, so the
    coefficients differ only by the order of the cross-device sum
    (``__graft_entry__.dryrun_multichip``'s invariant, on hardware)."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.parallel import mesh as mesh_mod

    rng = np.random.default_rng(3)
    x = rng.random((rows, dim), dtype=np.float32)
    y = (x @ rng.normal(size=dim) > 0).astype(np.float64)
    table = Table.from_columns(features=x, label=y)

    def fit(on_mesh):
        mesh_mod.set_default_mesh(on_mesh)
        try:
            est = LogisticRegression(max_iter=max_iter,
                                     global_batch_size=rows)
            return est, np.asarray(est.fit(table).coefficients, np.float64)
        finally:
            mesh_mod.set_default_mesh(mesh)

    est_all, coef_all = fit(mesh)
    one = mesh_mod.create_mesh(devices=list(mesh.devices.flat)[:1])
    est_one, coef_one = fit(one)
    err = float(np.max(np.abs(coef_all - coef_one))
                / np.max(np.abs(coef_one)))
    require(np.isfinite(coef_all).all() and err < tol,
            f"{mesh_shape(mesh)} LR differs from one device by {err:.3g} "
            f"(tol {tol})")
    return {"devices": int(mesh.devices.size),
            "pathAll": est_all.last_execution_path,
            "pathOne": est_one.last_execution_path,
            "relErrVsOneDevice": float(f"{err:.3g}")}


# -- driver -------------------------------------------------------------------

def run_phases(phases, counter: CompileCounter) -> bool:
    """Run every phase even after one fails — one chip call should show
    every problem — and report each on its own JSON line."""
    ok = True
    for name, phase in phases:
        t0, c0 = time.perf_counter(), counter.backend_compiles
        record = {"phase": name}
        try:
            record.update(ok=True, **phase())
        except Exception as e:  # noqa: BLE001 — the phase boundary: record
            # the failure with its traceback and keep checking
            ok = False
            record.update(ok=False, error=f"{type(e).__name__}: {e}")
            traceback.print_exc()
        record["wallS"] = round(time.perf_counter() - t0, 2)
        record["backendCompiles"] = counter.backend_compiles - c0
        print(json.dumps(record), flush=True)
    return ok


def main() -> int:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {device}",
              file=sys.stderr)
        return 2

    from flink_ml_tpu.parallel.mesh import default_mesh
    from flink_ml_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    entries_before = compile_cache.entry_count(cache_dir)
    counter = CompileCounter()
    mesh = default_mesh()
    print(json.dumps({"phase": "start", **device,
                      "meshShape": mesh_shape(mesh),
                      "jax": jax.__version__, "cacheDir": cache_dir,
                      "cacheEntriesBefore": entries_before}), flush=True)

    phases = [("lr_fit", lambda: lr_fit_phase(mesh)),
              ("kmeans", lambda: kmeans_phase(mesh)),
              ("serving", lambda: serving_phase(mesh)),
              ("kernels", kernels_phase),
              ("host_tier", host_tier_phase)]
    if len(devices) > 1:
        phases.append(("multichip", lambda: multichip_phase(mesh)))
    ok = run_phases(phases, counter)

    stats = devices[0].memory_stats() or {}
    print(json.dumps({
        "phase": "end", "ok": ok, "cacheDir": cache_dir,
        "cacheEntriesBefore": entries_before,
        "cacheEntriesAfter": compile_cache.entry_count(cache_dir),
        "compileRequests": counter.requests,
        "cacheHits": counter.cache_hits,
        "backendCompiles": counter.backend_compiles,
        "peakHbmBytes": stats.get("peak_bytes_in_use")}), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
