"""One-shot TPU profiling for the headline bench path.

Run on the real chip to localize where the KMeans-demo milliseconds go:
dispatch latency, H2D/D2H transfer, the compiled Lloyd program at 1 vs 20
rounds, and the end-to-end benchmark. Prints a timing table, then the
bench.py JSON line.

Compiles go through ``observability.compilestats.aot_compile`` (exact
compile timing, cost_analysis FLOP/byte capture) and every section is a
span under ``FLINK_ML_TPU_TRACE_DIR`` (default
``profiles/trace_profile_bench/``), so the TPU window leaves
``flink-ml-tpu-trace``-readable artifacts beside the stdout table.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp

from flink_ml_tpu.observability import compilestats, profiling, tracing

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def t(label, fn, repeat=5):
    fn()  # warm
    best = min(_timed(fn) for _ in range(repeat))
    print(f"{label:42s} {best * 1e3:8.2f} ms")
    return best


def _timed(fn):
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def main():
    print("devices:", jax.devices())
    os.environ.setdefault(
        tracing.TRACE_DIR_ENV,
        os.path.join(ROOT, "profiles", "trace_profile_bench"))
    compilestats.install()
    print("trace dir:", os.environ[tracing.TRACE_DIR_ENV])
    with tracing.tracer.span("tpu_profile_bench"):
        _profile()
    tracing.maybe_dump_root_metrics()
    print(f"\ninspect: python scripts/mltrace.py "
          f"{os.environ[tracing.TRACE_DIR_ENV]}")


def _profile():
    with tracing.tracer.span("dispatch-and-transfer") as sp:
        x_small = jnp.zeros(8)
        f_triv = compilestats.instrumented_jit(lambda v: v + 1,
                                               name="trivial_add")
        t("trivial jit dispatch", lambda: f_triv(x_small))

        host = np.random.default_rng(0).random((10000, 10)).astype(
            np.float32)
        t("H2D 10k x 10 f32", lambda: jax.device_put(host))
        dev = jax.device_put(host)
        t("D2H 10k x 10 f32", lambda: np.asarray(dev))

        # transfer at benchmark scale: 500k x 100 f32 = 200 MB. Round 2's
        # 4 MB probe hid a 60x variance on identical 200 MB puts;
        # print each sample, not just the best.
        big = np.random.default_rng(1).random((500_000, 100)).astype(
            np.float32)
        for i in range(5):
            dt = _timed(lambda: jax.device_put(big))
            print(f"H2D 500k x 100 f32 (200 MB) sample {i}     "
                  f"{dt * 1e3:8.2f} ms  ({big.nbytes / dt / 1e9:6.2f} GB/s)")
        big_dev = jax.device_put(big)
        dt = _timed(lambda: np.asarray(big_dev))
        print(f"D2H 500k x 100 f32 (200 MB)               {dt * 1e3:8.2f} ms"
              f"  ({big.nbytes / dt / 1e9:6.2f} GB/s)")

        # device datagen at the same scale: the transfer-free on-ramp
        from flink_ml_tpu.benchmark.datagen import _device_random
        t("device datagen 500k x 100 f32",
          lambda: _device_random(0, (500_000, 100)))
        del big, big_dev
        compilestats.sample_memory("transfer", span=sp)

    from flink_ml_tpu.models.clustering.kmeans import _build_lloyd_program
    from flink_ml_tpu.parallel.collective import shard_batch
    from flink_ml_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    xs, n = shard_batch(mesh, host)

    # the program DONATES its (c0, counts0) carry — every invocation
    # (the AOT compile's example args included) needs fresh buffers
    def carry():
        return jnp.asarray(host[:2]), jnp.zeros((2,), jnp.float32)

    for iters in (1, 2, 5, 20):
        fit = _build_lloyd_program(mesh, "euclidean", iters)
        with tracing.tracer.span(f"program:lloyd-{iters}") as sp:
            fit_c = compilestats.aot_compile(fit, xs, jnp.int32(n),
                                             *carry(),
                                             name=f"lloyd_{iters}")
            best = t(f"lloyd program, {iters:2d} round(s)",
                     lambda fit_c=fit_c: fit_c(xs, jnp.int32(n),
                                               *carry()))
            sp.set_attribute("best_wall_ms", round(best * 1e3, 3))
            compilestats.sample_memory("program", span=sp)

    # a captured window over the headline 20-round program: per-op
    # device-time attribution + profile.json through the shared capture
    # path (observability/profiling.py) — no hand-rolled profiler calls
    prof_dir = os.path.join(ROOT, "profiles", "bench_lloyd20")
    fit20_c = compilestats.aot_compile(
        _build_lloyd_program(mesh, "euclidean", 20), xs, jnp.int32(n),
        *carry(), name="lloyd_20_profiled")
    with profiling.profile_window("bench-lloyd20", out_dir=prof_dir):
        jax.block_until_ready(fit20_c(xs, jnp.int32(n), *carry()))
    print("\nlloyd 20-round device ops (profile.json in "
          f"{os.path.relpath(prof_dir, ROOT)}):")
    try:
        for row in profiling.parse_profile_dir(prof_dir)["ops"][:10]:
            print(f"  {row['selfMs']:10.2f} ms  x{row['count']:4d}  "
                  f"{row['op'][:72]}")
    except profiling.ProfileParseError as e:
        print(f"  (no trace captured: {e})")

    from flink_ml_tpu.ops.losses import BinaryLogisticLoss
    from flink_ml_tpu.ops.optimizer import SGD, SGDParams

    y = (host @ np.arange(10) > 4.5).astype(np.float32)
    sgd = SGD(SGDParams(max_iter=20, global_batch_size=1000))
    with tracing.tracer.span("program:sgd-10kx10"):
        t("sgd optimize 10k x 10, 20 rounds",
          lambda: sgd.optimize(BinaryLogisticLoss(),
                               np.zeros(10, np.float32), host, y)[0],
          repeat=3)

    import bench

    print("\nbench.py:")
    t0 = time.perf_counter()
    with tracing.tracer.span("bench.py"):
        bench.main()
    print(f"bench total wall: {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
