"""Incident smoke: a forced SLO violation under loadgen must leave a
flight-recorder bundle that ``mltrace incident --check`` exits 4 on —
and a clean run must exit 0 (docs/observability.md "Causal tracing,
critical path & incidents").

CPU-only CI harness: pins ``JAX_PLATFORMS=cpu`` (and may start child
processes) — never a chip check. A chip belongs to one process;
``python chip_smoke.py`` is the check that runs there.

Flow, all in one process:

1. arm a trace dir, serve a small closed-loop run through the
   micro-batcher (the causal submit→pad→batch→resolve chain lands in
   the artifacts);
2. ``mltrace path --check`` over the traced dir: the request paths must
   reconstruct, with attribution coverage >= 0.9 (the acceptance bar)
   and the queue-wait share under a generous budget;
3. evaluate a deliberately impossible latency SLO with ``emit=True`` —
   the violation trips the flight recorder → ``incident-000/`` with the
   triggering event and the preceding spans inside;
4. ``mltrace incident --check`` must exit 4 (unacknowledged), then 0
   after ``--ack``; a separate clean trace dir exits 0 throughout.

Exit codes: 0 ok, 1 a gate failed, 2 broken environment.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def fail(code: int, message: str):
    print(f"incident_smoke: FAIL — {message}", file=sys.stderr)
    raise SystemExit(code)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default="/tmp/incident-smoke")
    parser.add_argument("--requests", type=int, default=40)
    args = parser.parse_args(argv)

    trace_dir = os.path.join(args.root, "trace")
    clean_dir = os.path.join(args.root, "clean")
    os.makedirs(clean_dir, exist_ok=True)
    os.environ["FLINK_ML_TPU_TRACE_DIR"] = trace_dir

    from flink_ml_tpu.observability import flightrecorder, tracing
    from flink_ml_tpu.observability.exporters import dump_metrics
    from flink_ml_tpu.observability.flightrecorder import (
        main as incident_main,
    )
    from flink_ml_tpu.observability.path import main as path_main
    from flink_ml_tpu.observability.slo import SLO, evaluate_slos
    from flink_ml_tpu.servable.api import (
        DataFrame,
        DataTypes,
        Row,
        TransformerServable,
    )
    from flink_ml_tpu.serving import (
        BatcherConfig,
        LoadGenConfig,
        MicroBatcher,
        run_loadgen,
    )

    class Echo(TransformerServable):
        def transform(self, df: DataFrame) -> DataFrame:
            return df

    def frame(rows: int) -> DataFrame:
        return DataFrame(["x"], [DataTypes.DOUBLE],
                         [Row([float(i)]) for i in range(rows)])

    # 1. a small traced serving run through the pipelined dispatcher
    with MicroBatcher(Echo(), BatcherConfig(
            buckets=(1, 4, 8), window_ms=1.0, pipeline_depth=1)) as b:
        result = run_loadgen(
            b.submit, lambda i: frame(1 + i % 3),
            LoadGenConfig(mode="closed", requests=args.requests,
                          concurrency=4))
        if result["errors"]:
            fail(2, f"loadgen errors: {result['errorsByClass']}")

        # 3. the forced violation fires INSIDE the serving window, so
        # the span ring still holds the batches that "caused" it
        impossible = SLO(name="smoke-impossible-latency",
                         kind="latency", threshold_ms=1e-6)
        verdicts = evaluate_slos([impossible], emit=True)
        if verdicts[0]["ok"]:
            fail(2, "the impossible SLO did not violate — no traffic?")

    tracing.tracer.shutdown()
    dump_metrics(trace_dir)
    print(f"incident_smoke: served {args.requests} request(s), forced "
          f"an SLO violation, artifacts in {trace_dir}")

    # 2. the critical-path gate over the same artifacts
    rc = path_main([trace_dir, "--check", "--budget", "99"])
    if rc != 0:
        fail(1, f"mltrace path --check exited {rc} on the traced run")
    from flink_ml_tpu.observability.exporters import read_spans
    from flink_ml_tpu.observability.path import analyze_paths

    report = analyze_paths(read_spans(trace_dir))
    coverage = report["requests"]["coverage"] or 0.0
    if report["requests"]["count"] < args.requests:
        fail(1, f"only {report['requests']['count']} of "
                f"{args.requests} request paths reconstructed")
    if coverage < 0.9:
        fail(1, f"path attribution coverage {coverage:.1%} below 90%")
    print(f"incident_smoke: {report['requests']['count']} request "
          f"path(s), coverage {coverage:.1%}, queue-wait "
          f"{report['requests']['queue_share']:.1%}")

    # 4. the incident bundle + the --check/--ack cycle
    rows = flightrecorder.read_incidents(trace_dir)
    if not rows:
        fail(1, "no incident bundle after the forced SLO violation")
    inc = rows[-1]  # a reused --root extends the series; judge the
    # bundle THIS run just recorded
    if inc["kind"] != "slo" or \
            inc["attrs"].get("slo") != "smoke-impossible-latency":
        fail(1, f"bundle does not name the trigger: {inc['attrs']}")
    if not any(sp.get("name") == "serving.batch"
               for sp in inc["recent_spans"]):
        fail(1, "the preceding serving spans are not in the bundle")
    for artifact in ("metrics.json", "slo.json", "spans-recent.jsonl"):
        if not os.path.isfile(os.path.join(inc["dir"], artifact)):
            fail(1, f"bundle missing {artifact}")

    rc = incident_main([trace_dir, "--check"])
    if rc != 4:
        fail(1, f"incident --check exited {rc} on an unacknowledged "
                f"bundle (wanted 4)")
    rc = incident_main([clean_dir, "--check"])
    if rc != 0:
        fail(1, f"incident --check exited {rc} on a clean dir "
                f"(wanted 0)")
    rc = incident_main([trace_dir, "--ack", "--check"])
    if rc != 0:
        fail(1, f"incident --check exited {rc} after --ack (wanted 0)")
    print("incident_smoke: OK — violation bundled (exit 4), clean dir "
          "and acknowledged dir exit 0")
    print(json.dumps({"incidents": len(rows),
                      "coverage": round(coverage, 4),
                      "queue_share": report["requests"]["queue_share"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
