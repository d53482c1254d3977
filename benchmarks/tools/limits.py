"""Read, on the chip and at the cell's own size, what the limits of
``correct`` are set from (PERF.md section 2):

- the lower readings: each number compared, the program against the plain
  reference, over a dozen seeds — a short window of the cell's own traffic
  through ``run_cell.run`` in this process;
- the upper readings: the control (the reference one precision below the
  configuration's) and the reference with each of its module's ``FAULTS``
  planted, each put in
  the program's place as the window's one answer and taken through
  ``check.decide`` with the configuration's limits, on a few seeds:
  ``correct`` has to come out false for every one of them.

    python benchmarks/tools/limits.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--seconds 1]
"""

from __future__ import annotations

import argparse
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import check, references, spec  # noqa: E402


def lower_reading(workload: str, seed: int, seconds: float) -> dict:
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(workload, seed, seconds, False, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    info = json.loads(lines[0]) if lines else {}
    return {"seed": seed, "rc": rc,
            "correct": result and result["correct"],
            "fits": result and result["attempted"],
            "paths": info.get("execution_paths"),
            "compared": {k: v["value"] for k, v in
                         (result or {}).get("compared", {}).items()}}


def upper_readings(cell: spec.Cell, seed: int) -> dict:
    import jax

    from benchmarks.harness import system

    columns, params = run_cell.make_inputs(cell, seed, system,
                                           jax.devices()[:cell.chips])
    ref_spec = cell.config["correct"]
    module = references.load(ref_spec["reference"])
    args = ref_spec.get("args", {})
    t = time.perf_counter()
    reference = module.run(columns, params, cell.chips, **args)
    out = {"seed": seed, "reference_s": time.perf_counter() - t}
    variants = [("control_bfloat16", {"precision": "bfloat16"})]
    variants += [(f"fault_{f}", {"fault": f}) for f in module.FAULTS
                 if f != "no_exchange" or cell.chips > 1]
    for name, kw in variants:
        try:
            other = module.run(columns, params, cell.chips, **args, **kw)
            answer = {k: v for k, v in other.items() if not k.startswith("_")}
            correct, compared = check.decide(
                [answer], module, reference, ref_spec["limits"],
                extra={"window_backend_compiles": (0, 0)})
            out[name] = {"correct": correct, "compared": compared}
        except Exception as exc:  # noqa: BLE001 — a control that crashes has failed
            out[name] = {"correct": False, "crashed": repr(exc)[:200]}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=1.0)
    args = parser.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run_cell.configure_compile_cache()
    out_dir = spec.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    report = {"workload": args.workload,
              "lower": [], "upper": []}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        report["lower"].append(lower_reading(args.workload, seed,
                                             args.seconds))
        print(json.dumps(report["lower"][-1]), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        report["upper"].append(upper_readings(cell, seed))
        print(json.dumps(report["upper"][-1]), flush=True)
    numbers = {}
    for row in report["lower"]:
        for name, value in row["compared"].items():
            numbers.setdefault(name, []).append(value)
    report["largest_lower"] = {k: max(v) for k, v in numbers.items()}
    report["every_control_and_fault_not_correct"] = all(
        v["correct"] is False for row in report["upper"]
        for v in row.values() if isinstance(v, dict))
    print(json.dumps({"largest_lower": report["largest_lower"],
                      "every_control_and_fault_not_correct":
                      report["every_control_and_fault_not_correct"]}))
    (out_dir / f"limits_{args.workload}.json").write_text(
        json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
