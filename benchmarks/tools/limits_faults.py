"""``tools/limits.py`` with the smallest upper reading of each number: the
same lower readings (the program against the plain reference over the
seeds, through ``run_cell.run``), and the upper readings with the control
and every fault of the reference module's ``FAULTS``, each taken through
``check.decide`` with the configuration's limits, where ``correct`` has to
come out false.

    python benchmarks/tools/limits_faults.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--seconds 1] [--twice]

``--twice`` reads every seed a second time, in the same process (a warm
one: the second reading shows the answer does not depend on what was
compiled or cached).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import spec  # noqa: E402
from benchmarks.tools.limits import lower_reading, upper_readings  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--twice", action="store_true")
    args = parser.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run_cell.configure_compile_cache()
    out_dir = spec.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    report = {"workload": args.workload, "lower": [], "upper": []}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds * (2 if args.twice else 1):
        report["lower"].append(lower_reading(args.workload, seed,
                                             args.seconds))
        print(json.dumps(report["lower"][-1]), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        report["upper"].append(upper_readings(cell, seed))
        print(json.dumps(report["upper"][-1]), flush=True)
    numbers, smallest = {}, {}
    for row in report["lower"]:
        for name, value in row["compared"].items():
            numbers.setdefault(name, []).append(value)
    for row in report["upper"]:
        for variant, found in row.items():
            if isinstance(found, dict) and "compared" in found:
                for name, c in found["compared"].items():
                    smallest.setdefault(variant, {}).setdefault(
                        name, []).append(c["value"])
    report["largest_lower"] = {k: max(v) for k, v in numbers.items()}
    report["smallest_upper"] = {
        variant: {k: min(v) for k, v in by_name.items()}
        for variant, by_name in smallest.items()}
    report["every_control_and_fault_not_correct"] = all(
        v["correct"] is False for row in report["upper"]
        for v in row.values() if isinstance(v, dict))
    print(json.dumps({k: report[k] for k in (
        "largest_lower", "smallest_upper",
        "every_control_and_fault_not_correct")}))
    (out_dir / f"limits_{args.workload}.json").write_text(
        json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
