"""Run the committed command on one cell, run after run, each a process of
its own, and print what the contract's rule needs: per metric the spread
(quartile distance over median, ``statistics.quantiles(n=4)``) of each set
and the medians. The parent never touches JAX.

    python benchmarks/tools/sets.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13,14,15,16 --sets 2 [--trace-seeds 21,22,23]
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def one_run(command, workload, seed, seconds, trace):
    t = time.perf_counter()
    proc = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    took = time.perf_counter() - t
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if proc.returncode == 0 else None
    except (IndexError, json.JSONDecodeError):
        result = None
    return {"seed": seed, "trace": trace, "rc": proc.returncode,
            "took_s": took, "result": result,
            "info": lines[:-1][-2:],
            "stderr_tail": proc.stderr.strip().splitlines()[-6:]}


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=int, required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--trace-seeds", default="")
    args = parser.parse_args(argv)
    command = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    log = open(out_dir / f"sets_{args.workload}.jsonl", "a")
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            run = one_run(command, args.workload, seed, args.seconds, 0)
            run["set"] = k
            log.write(json.dumps(run) + "\n")
            log.flush()
            runs.append(run)
        sets.append(runs)
    for seed in [int(s) for s in args.trace_seeds.split(",") if s]:
        run = one_run(command, args.workload, seed, args.seconds, 1)
        log.write(json.dumps(run) + "\n")
        log.flush()
        print(json.dumps({"traced": run["result"], "rc": run["rc"],
                          "took_s": run["took_s"],
                          "stderr_tail": run["stderr_tail"]}))
    report = {"workload": args.workload, "seconds": args.seconds}
    good = [[r for r in runs if r["result"]] for runs in sets]
    report["correct"] = [[r["result"]["correct"] for r in runs]
                         for runs in good]
    report["rcs"] = [[r["rc"] for r in runs] for runs in sets]
    report["took_s"] = [[round(r["took_s"], 1) for r in runs]
                        for runs in sets]
    report["memory_peak_bytes"] = [
        r["result"]["device"]["memory_peak_bytes"] for r in good[0]][:1]
    names = list(good[0][0]["result"]["metrics"]) if good and good[0] else []
    for name in names:
        per_set = [[r["result"]["metrics"][name]["value"] for r in runs]
                   for runs in good]
        rest = [v[1:] if name == "setup_s" else v for v in per_set]
        report[name] = {
            "values": per_set,
            "medians": [statistics.median(v) for v in rest],
            "spreads": [spread(v) for v in rest if len(v) >= 2],
        }
    print(json.dumps(report, indent=1))
    (out_dir / f"sets_{args.workload}.report.json").write_text(
        json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
