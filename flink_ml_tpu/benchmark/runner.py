"""Benchmark runner CLI.

Ref parity: Benchmark.java:41/main:129 + BenchmarkUtils.java:47 — parse a
JSON config (version 1; named benchmarks each holding stage / inputData /
optional modelData specs with className + paramMap), instantiate via the
param system, execute, report per-benchmark results
{totalTimeMs, inputRecordNum, inputThroughput, outputRecordNum,
outputThroughput} (BenchmarkUtils.java:130-143). Estimators are timed as
``fit(input).get_model_data()``; AlgoOperators as ``transform(input)`` —
same as the reference. Reference Java class names are accepted.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from typing import Dict

import numpy as np

from flink_ml_tpu.api.stage import AlgoOperator, Estimator, Model, Stage
from flink_ml_tpu.benchmark.datagen import resolve_generator

_STAGES: Dict[str, type] = {}


def _stage_registry() -> Dict[str, type]:
    """Short class name → Stage class, discovered from the models package
    (the reflective instantiation of ParamUtils.instantiateWithParams)."""
    if _STAGES:
        return _STAGES
    import flink_ml_tpu.models as models_pkg

    def walk(cls):
        for sub in cls.__subclasses__():
            if (not sub.__name__.startswith("_")
                    and "Base" not in sub.__name__
                    and ".models." in sub.__module__):
                _STAGES[sub.__name__] = sub
            walk(sub)

    walk(Stage)
    return _STAGES


def resolve_stage(class_name: str) -> type:
    short = class_name.rsplit(".", 1)[-1]
    registry = _stage_registry()
    try:
        return registry[short]
    except KeyError:
        raise ValueError(f"unknown stage {class_name!r}; known: "
                         f"{sorted(registry)}")


def load_config(path: str) -> dict:
    """Reference configs carry // license comments; strip them."""
    with open(path) as f:
        text = f.read()
    text = re.sub(r"^\s*//.*$", "", text, flags=re.M)
    config = json.loads(text)
    if config.pop("version", 1) != 1:
        raise ValueError("unsupported benchmark config version")
    return config


def run_benchmark(name: str, spec: dict) -> dict:
    """One named benchmark; with FLINK_ML_TPU_TRACE_DIR armed the whole
    run is a span (datagen + fit/transform + materialization nested
    inside), so a BENCH sweep leaves an inspectable trace per row.

    Every run also carries its compile accounting: ``compileCount`` /
    ``compileTimeMs`` are the XLA compiles this run triggered (the
    jax.monitoring delta across the run — 0 on a warm cache), so sweep
    rows separate compile from steady-state without anyone watching."""
    from flink_ml_tpu.observability import compilestats, tracing

    # with monitoring available the phase channel sees every compile in
    # the run; without it, only instrumented functions are visible. The
    # delta must subtract within ONE source — mixing them can go negative
    source = "phase" if compilestats.install() else "perfn"
    with tracing.tracer.span("benchmark.run", benchmark=name,
                             stage=spec["stage"]["className"]) as sp:
        before = compilestats.compile_totals_split()[source]
        result = _run_benchmark(name, spec)
        after = compilestats.compile_totals_split()[source]
        result["compileCount"] = after["count"] - before["count"]
        result["compileTimeMs"] = round(
            after["timeMs"] - before["timeMs"], 3)
        sp.set_attribute("totalTimeMs", round(result["totalTimeMs"], 3))
        sp.set_attribute("inputThroughput",
                         round(result["inputThroughput"], 1))
        sp.set_attribute("compileCount", result["compileCount"])
        sp.set_attribute("compileTimeMs", result["compileTimeMs"])
        if "deviceCount" in result:
            sp.set_attribute("deviceCount", result["deviceCount"])
            sp.set_attribute("meshShape", result["meshShape"])
    tracing.maybe_dump_root_metrics()
    return result


def _run_benchmark(name: str, spec: dict) -> dict:
    try:  # a row must carry only ITS OWN run's update-state provenance
        from flink_ml_tpu.parallel import elastic, update_sharding

        update_sharding.reset_last()
        elastic.reset_stats()
    except Exception:  # noqa: BLE001 — provenance only
        pass
    stage = resolve_stage(spec["stage"]["className"])()
    stage.params_from_json(spec["stage"].get("paramMap", {}), strict=True)

    gen = resolve_generator(spec["inputData"]["className"])()
    gen.params_from_json(spec["inputData"].get("paramMap", {}), strict=True)

    model_gen = None
    if "modelData" in spec:
        model_gen = resolve_generator(spec["modelData"]["className"])()
        model_gen.params_from_json(spec["modelData"].get("paramMap", {}),
                                   strict=True)

    # datagen is part of the measured job in the reference; keep it inside
    start = time.perf_counter()
    input_table = gen.get_data()
    model_table = None if model_gen is None else model_gen.get_data()
    _block_device_columns(input_table)  # honest datagen/execute split
    datagen_ms = (time.perf_counter() - start) * 1000.0
    if model_table is not None:
        if isinstance(stage, Estimator) and hasattr(
                stage, "set_initial_model_data"):
            # online trainers seed from model data instead of consuming it
            # as a fitted model (OnlineLogisticRegression.java:440)
            stage.set_initial_model_data(model_table)
        else:
            stage.set_model_data(model_table)

    if isinstance(stage, Estimator):
        outputs = stage.fit(input_table).get_model_data()
    elif isinstance(stage, AlgoOperator):
        outputs = stage.transform(input_table)
    else:
        raise ValueError(f"unsupported stage class {type(stage)}")
    output_num = sum(t.num_rows for t in outputs)
    for t in outputs:  # async-dispatched device outputs must materialize
        _block_device_columns(t)
    total_ms = (time.perf_counter() - start) * 1000.0

    input_num = gen.num_values
    exec_ms = total_ms - datagen_ms
    input_bytes = _table_bytes(input_table)
    if model_table is not None:
        input_bytes += _table_bytes(model_table)
    return {
        # mesh provenance: a throughput number from a 1-device cpu
        # fallback and one from an 8-way mesh must never be confused in
        # a BENCH artifact (docs/observability.md "Distributed
        # telemetry")
        **_mesh_provenance(),
        # native-kernel thread provenance (native.native_threads): a
        # string-tier number measured with 4-way threaded kernels is a
        # different machine state than a single-threaded one
        **_native_provenance(),
        "totalTimeMs": total_ms,
        "inputRecordNum": input_num,
        "inputThroughput": input_num * 1000.0 / total_ms,
        "outputRecordNum": output_num,
        "outputThroughput": output_num * 1000.0 / total_ms,
        # extra provenance beyond the reference's schema: where the time went
        "dataGenTimeMs": datagen_ms,
        "executeTimeMs": exec_ms,
        # roofline context (SURVEY §6 extended): the stage must read its
        # input at least once, so inputBytes / executeTime is a LOWER
        # bound on achieved bandwidth — comparable against the platform
        # roofline (v5e HBM ~819 GB/s; host DRAM ~10s of GB/s) to spot
        # rows running far below the memory bound
        "inputBytes": input_bytes,
        "achievedGBps": input_bytes / max(exec_ms, 1e-9) / 1e6,
        # which execution path the stage actually took (e.g. KnnModel
        # reports "pallas" vs "xla-chunked") — benchmark rows must name
        # the code path their number measures
        **({"executionPath": stage.last_execution_path}
           if getattr(stage, "last_execution_path", None) else {}),
    }


def _native_provenance() -> dict:
    """``nativeThreads``: the validated FLINK_ML_TPU_NATIVE_THREADS
    value the row's native factorize/doc-freq kernels ran with (1 =
    single-threaded, the default). Never fails a finished measurement."""
    try:
        from flink_ml_tpu import native

        return {"nativeThreads": native.native_threads()}
    except Exception:  # noqa: BLE001 — provenance only
        return {}


def _mesh_provenance() -> dict:
    """``deviceCount`` + ``meshShape`` of the default mesh the benchmark
    actually ran on (``"data=8"`` style), ``processCount`` /
    ``processIndex`` of the runtime that measured it (a row from one
    process of a jax.distributed mesh is a different machine state than
    a single-process one — parallel/distributed.py), plus
    ``updateSharding`` (whether the cross-replica sharded update was
    armed — parallel/update_sharding.py) and ``optStateBytesPerReplica``
    (the per-replica update-state bytes the fit recorded; shrinks ~1/N
    when sharding is on) — benchmark rows must say whether their number
    is a 1-device cpu fallback or a real mesh, and whether optimizer
    state was replicated or sharded. Never fails a finished
    measurement: if the mesh is somehow unavailable the keys are simply
    absent."""
    try:
        from flink_ml_tpu.parallel import update_sharding
        from flink_ml_tpu.parallel.distributed import (
            process_count, process_index)
        from flink_ml_tpu.parallel.mesh import default_mesh

        mesh = default_mesh()
        return {"deviceCount": int(mesh.devices.size),
                "meshShape": ",".join(f"{a}={int(mesh.shape[a])}"
                                      for a in mesh.axis_names),
                "processCount": process_count(),
                "processIndex": process_index(),
                **update_sharding.provenance(),
                **_serving_provenance(),
                **_fleet_provenance()}
    except Exception:  # noqa: BLE001 — provenance only
        return {}


def _serving_provenance() -> dict:
    """``shardedDispatch`` + ``pipelineDepth`` of the live serving
    runtime, read from the ``/serving`` status provider when a
    micro-batcher is running beside this benchmark (serving/batcher.py)
    — null on plain fit benches: a fit row honestly says it measured no
    serving dispatch at all. Never fails a finished measurement."""
    sharded, depth = None, None
    try:
        from flink_ml_tpu.observability import server

        status = server.get_serving_status()
        if status is not None:
            live = status() if callable(status) else status
            sharded = bool(live.get("sharded_dispatch", False))
            depth = live.get("pipeline_depth")
    except Exception:  # noqa: BLE001 — provenance only
        pass
    return {"shardedDispatch": sharded, "pipelineDepth": depth}


def _fleet_provenance() -> dict:
    """``fleetMembers`` + ``fleetP99Ms`` from the live fleet telemetry
    plane (observability/fleet.py) when a fleet dir resolves and holds
    beacons — null on single-process / disarmed benches: a solo row
    honestly says no fleet measured it. Never fails a finished
    measurement."""
    try:
        from flink_ml_tpu.observability import fleet

        return fleet.provenance()
    except Exception:  # noqa: BLE001 — provenance only
        return {"fleetMembers": None, "fleetP99Ms": None}


def _table_bytes(table) -> int:
    """Actual byte size of a Table's columns (device, numpy, CSR); object
    columns are estimated from a 256-row sample — benchmark provenance,
    not an allocator audit."""
    total = 0
    for name in table.column_names:
        col = table.column(name)
        if getattr(col, "is_csr_vector_column", False):
            m = col.matrix
            total += int(m.data.nbytes + m.indices.nbytes
                         + m.indptr.nbytes)
            continue
        dtype = getattr(col, "dtype", None)
        if dtype is not None and dtype != np.dtype(object):
            total += int(col.size) * int(dtype.itemsize)
            continue
        n = len(col)
        if n:
            sample = min(n, 256)
            per_row = sum(
                np.asarray(col[i]).nbytes for i in range(sample))
            total += per_row * n // sample
    return total


def best_of(name: str, spec: dict, runs: int = 3) -> dict:
    """The measurement protocol every published number uses: one identical
    warmup run (XLA compile excluded — the JVM baseline's steady state
    excludes JIT warmup too), then best inputThroughput of ``runs``.

    The warmup's compile accounting rides on the returned best row as
    the compile/steady split: ``warmupTimeMs`` / ``warmupCompileTimeMs``
    / ``warmupCompileCount`` say what the excluded warmup actually paid,
    and the best run's own ``compileCount`` should be ~0 — a nonzero
    steady-state compile count is itself a recompile signal worth a look
    with the storm detector (docs/observability.md)."""
    warmup = run_benchmark(name, spec)
    best = None
    for _ in range(runs):
        r = run_benchmark(name, spec)
        if best is None or r["inputThroughput"] > best["inputThroughput"]:
            best = r
    best["warmupTimeMs"] = round(warmup["totalTimeMs"], 3)
    best["warmupCompileTimeMs"] = warmup.get("compileTimeMs", 0.0)
    best["warmupCompileCount"] = warmup.get("compileCount", 0)
    return best


def _block_device_columns(table) -> None:
    """Wait for any device-resident columns before the timestamp: JAX
    dispatch returns before the device finishes, and the reference's
    benchmark sink consumes every record
    (BenchmarkUtils.CountingAndDiscardingSink:156), so data must actually
    exist, not merely be scheduled."""
    import jax

    columns = (table.column(name) for name in table.column_names)
    jax.block_until_ready([c for c in columns if isinstance(c, jax.Array)])


def run_benchmarks(config: dict) -> dict:
    """One failing benchmark doesn't abort the rest (the reference demo
    config deliberately includes broken entries)."""
    results = {}
    for name, spec in config.items():
        entry = {}
        try:
            entry["stage"] = spec["stage"]
            entry["inputData"] = spec["inputData"]
            entry["results"] = run_benchmark(name, spec)
        except Exception as e:  # noqa: BLE001 — report and continue
            entry["exception"] = f"{type(e).__name__}: {e}"
        results[name] = entry
    return results


def main(argv=None) -> int:
    """CLI parity with bin/benchmark-run.sh <config> [--output-file r.json]."""
    parser = argparse.ArgumentParser(prog="flink-ml-tpu-benchmark")
    parser.add_argument("config", help="benchmark config JSON file")
    parser.add_argument("--output-file", default=None)
    args = parser.parse_args(argv)

    from flink_ml_tpu.utils import compile_cache

    compile_cache.configure()
    results = run_benchmarks(load_config(args.config))
    text = json.dumps(results, indent=2)
    print(text)
    if args.output_file:
        with open(args.output_file, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
