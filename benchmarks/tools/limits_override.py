"""``tools/limits_faults.py`` on a table the cell does not run: the same
lower readings (the program against the plain reference through
``run_cell.run``) and upper readings (the control and every fault of the
reference module, each through ``check.decide`` with the configuration's
limits), with the generator's parameters overridden. Not a cell: a look at
where the limits stand on other entries.

    python benchmarks/tools/limits_override.py --workload anova_fit_ref \\
        --input featureArity=0 --seeds 1,2 --control-seeds 1 [--seconds 5]

``anova_fit_ref``'s published table holds zeros and ones, on which every
sum is a whole number under 2**24 and a float32 accumulator is exact;
``featureArity=0`` makes the entries uniform in [0, 1), where float32
accumulation really bites: the program has to stay under the same limits
there and ``float32_chain`` over them.
"""

from __future__ import annotations

import argparse
import io
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import spec  # noqa: E402
from benchmarks.tools.limits import upper_readings  # noqa: E402


def _value(text: str):
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        return text


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--input", action="append", default=[],
                        help="key=value of the generator's paramMap")
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)
    changed = {k: _value(v) for k, v in
               (item.split("=", 1) for item in args.input)}
    overrides = {"inputData": changed}
    cell = spec.load_cell(args.workload)
    run_cell.configure_compile_cache()
    report = {"workload": args.workload, "overrides": overrides,
              "lower": [], "upper": []}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        out, err = io.StringIO(), io.StringIO()
        rc = run_cell.run(args.workload, seed, args.seconds, False,
                          overrides=overrides, out=out, err=err)
        lines = out.getvalue().strip().splitlines()
        result = json.loads(lines[-1]) if rc == 0 and lines else {}
        info = json.loads(lines[0]) if lines else {}
        report["lower"].append({
            "seed": seed, "rc": rc, "correct": result.get("correct"),
            "fits": result.get("attempted"),
            "paths": info.get("execution_paths"),
            "fit_wall_median_ms": info.get("fit_wall_median_ms"),
            "compared": {k: v["value"] for k, v in
                         result.get("compared", {}).items()}})
        print(json.dumps(report["lower"][-1]), flush=True)
    overridden = run_cell._apply_overrides(cell, overrides)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        report["upper"].append(upper_readings(overridden, seed))
        print(json.dumps(report["upper"][-1]), flush=True)
    report["every_lower_correct"] = all(
        row["correct"] is True for row in report["lower"])
    report["not_correct"] = {
        variant: all(row[variant]["correct"] is False
                     for row in report["upper"])
        for variant in (report["upper"][0] if report["upper"] else {})
        if isinstance(report["upper"][0][variant], dict)}
    print(json.dumps({k: report[k] for k in ("every_lower_correct",
                                             "not_correct")}))
    out_dir = spec.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"limits_override_{args.workload}.json").write_text(
        json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
