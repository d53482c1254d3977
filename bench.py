#!/usr/bin/env python
"""Headline benchmark. ONE process, on the chip. Prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "platform": "tpu",
 "device_kind": ..., "device_count": N, ..., "northstar": {...}}

The process that imports JAX is the process that measures: a chip belongs
to one process at a time, so no child is started. The run exits non-zero —
and prints no result line — when ``jax.devices()[0].platform`` is not
``tpu`` or when any row raises; there is no CPU stand-in.

Workload: the reference's own benchmark demo (flink-ml-benchmark
benchmark-demo.json "KMeans-1": KMeans with default params on 10,000 uniform
dense vectors of dim 10, seed 2) — the ONLY workload the reference publishes
a number for: totalTimeMs 7148 → inputThroughput 1398.99 records/s on a local
standalone Flink cluster (flink-ml-benchmark/README.md). vs_baseline is
measured against that number. The JVM reference cannot be re-measured in this
image (no Java toolchain); see BASELINE.md. Attached under ``northstar``: the
reference's vendored north-star configs (LR 10Mx100, KMeans 1Mx100, KNN,
FTRL), one row each.

Measurement matches BenchmarkUtils.java:130-143: totalTimeMs covers data
generation + fit + model-data materialization; inputThroughput =
numValues*1000/totalTimeMs. One identical warmup run first so XLA compile
time (absent from the JVM baseline's steady-state too) is excluded; the
warmup's compile bill rides on the row.

The ``workloads`` table, metric definitions and regression bounds are
ROADMAP S1's job; this file only guarantees that what it prints was measured
on the device it names.
"""

import json
import os
import sys

REFERENCE_DEMO_THROUGHPUT = 1398.9927252378288  # records/s, README sample

DEMO_SPEC = {
    "stage": {
        "className": "org.apache.flink.ml.clustering.kmeans.KMeans",
        "paramMap": {"featuresCol": "features", "predictionCol": "prediction"},
    },
    "inputData": {
        "className": ("org.apache.flink.ml.benchmark.datagenerator.common."
                      "DenseVectorGenerator"),
        "paramMap": {"seed": 2, "colNames": [["features"]],
                     "numValues": 10000, "vectorDim": 10},
    },
}

#: the judged workloads (BASELINE.md), most- to least-important
NORTHSTAR_CONFIGS = ("logisticregression-benchmark.json",
                     "kmeans-benchmark.json",
                     "knn-benchmark.json",
                     "onlinelogisticregression-benchmark.json")


def _northstar_row(best: dict) -> dict:
    row = {
        "inputRecordNum": best["inputRecordNum"],
        "totalTimeMs": round(best["totalTimeMs"], 1),
        "inputThroughput": round(best["inputThroughput"], 1),
        # compile/steady split (docs/observability.md): what the excluded
        # warmup paid, and whether the measured run recompiled anything
        # (should be 0)
        "warmupCompileMs": round(best.get("warmupCompileTimeMs", 0.0), 1),
        "warmupCompileCount": best.get("warmupCompileCount", 0),
        "steadyCompileCount": best.get("compileCount", 0),
    }
    # mesh / multi-process / elastic / serving-dispatch / update-sharding /
    # native-thread / fleet provenance, as the runner recorded it
    for key in ("deviceCount", "meshShape", "processCount", "processIndex",
                "elasticEvents", "participationMin", "shardedDispatch",
                "pipelineDepth", "updateSharding", "optStateBytesPerReplica",
                "nativeThreads", "fleetMembers", "fleetP99Ms"):
        row[key] = best.get(key)
    if "executionPath" in best:
        row["executionPath"] = best["executionPath"]
    return row


def _demo_line(best: dict, device: dict) -> dict:
    from flink_ml_tpu.observability import drift, evaluation, profiling

    value = best["inputThroughput"]
    line = {
        "metric": "kmeans_demo_input_throughput_10kx10",
        "value": round(value, 1),
        "unit": "records/s",
        "vs_baseline": round(value / REFERENCE_DEMO_THROUGHPUT, 2),
        # the device as JAX reports it — the run refuses anything but tpu
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "mesh_shape": best.get("meshShape"),
        # multi-process provenance (parallel/distributed.py)
        "process_count": best.get("processCount"),
        "process_index": best.get("processIndex"),
        # elastic provenance (parallel/elastic.py)
        "elastic_events": best.get("elasticEvents"),
        "participation_min": best.get("participationMin"),
        # serving-dispatch provenance (serving/batcher.py); null on plain
        # fit benches
        "sharded_dispatch": best.get("shardedDispatch"),
        "pipeline_depth": best.get("pipelineDepth"),
        # cross-replica sharded update (parallel/update_sharding.py)
        "update_sharding": best.get("updateSharding"),
        "opt_state_bytes_per_replica": best.get("optStateBytesPerReplica"),
        "native_threads": best.get("nativeThreads"),
        # compile/steady split: the warmup's compile bill (excluded from
        # the measured number) and the measured run's own compile count,
        # which should be 0
        "warmup_compile_ms": round(best.get("warmupCompileTimeMs", 0.0), 1),
        "warmup_compile_count": best.get("warmupCompileCount", 0),
        "steady_compile_count": best.get("compileCount", 0),
        # causal-tracing cost and fleet provenance; null on plain fit
        # benches (scripts/serve_bench.py records real values)
        "trace_overhead_pct": best.get("traceOverheadPct"),
        "fleet_members": best.get("fleetMembers"),
        "fleet_p99_ms": best.get("fleetP99Ms"),
    }
    # drift / continuous-evaluation / device-efficiency provenance: null on
    # a plain fit bench, carried so downstream consumers see one shape
    dprov = drift.provenance()
    line["drift_psi_max"] = dprov["driftPsiMax"]
    line["baseline_version"] = dprov["baselineVersion"]
    qprov = evaluation.provenance()
    line["auc_live"] = qprov["aucLive"]
    line["feedback_coverage"] = qprov["feedbackCoverage"]
    line["label_lag_p99_ms"] = qprov["labelLagP99Ms"]
    pprov = profiling.provenance()
    line["profile_source"] = pprov["profileSource"]
    line["utilization"] = pprov["utilization"]
    line["achieved_flops"] = pprov["achievedFlops"]
    return line


def main() -> int:
    import jax

    from flink_ml_tpu.benchmark.runner import best_of, load_config
    from flink_ml_tpu.utils import compile_cache

    compile_cache.configure()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"bench.py measures on a TPU and found {device}; a CPU "
              "timing is never written under a device metric's name",
              file=sys.stderr)
        return 2

    line = _demo_line(best_of("KMeans-demo", DEMO_SPEC), device)
    cfg_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "flink_ml_tpu", "benchmark", "configs")
    # a raising row propagates: the run exits non-zero with no result line
    line["northstar"] = {
        name: _northstar_row(best_of(name, spec))
        for cfg_file in NORTHSTAR_CONFIGS
        for name, spec in load_config(
            os.path.join(cfg_dir, cfg_file)).items()}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
