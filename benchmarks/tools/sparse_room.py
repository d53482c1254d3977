"""What a sparse click-through configuration costs before any program runs
it, read on the chip (PERF.md section 7): for each seed, the table its
generator makes (seconds, bytes on the device, array by array), and the
plain reference over it (seconds, rounds) with the control and each of the
reference's ``FAULTS`` put in the program's place and taken through
``check.decide`` under the configuration's limits, where ``correct`` has to
come out false.

    python benchmarks/tools/sparse_room.py --config <configuration.json> \\
        --seeds 1,2,3 [--rows 23000000]

One process, one chip, one table at a time; the report also goes to
``chiprun_out/sparse_room.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.harness import check, device, generators, references  # noqa: E402
from benchmarks.harness import spec  # noqa: E402


def _leaves(columns):
    for name, col in columns.items():
        if isinstance(col, dict):
            for part, array in col.items():
                if array.ndim:          # the size is no part of the table
                    yield f"{name}.{part}", array
        else:
            yield name, col


def one_seed(config: dict, params: dict, seed: int, used) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.Mesh(used, ("data",))

    def sharding(ndim):
        if ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))

    data = config["inputData"]
    t = time.perf_counter()
    columns = generators.make_columns(data["className"], params, seed,
                                      sharding)
    out = {"seed": seed, "datagen_s": time.perf_counter() - t,
           "arrays": {name: {"shape": list(a.shape), "dtype": str(a.dtype),
                             "device_bytes": a.on_device_size_in_bytes()}
                      for name, a in _leaves(columns)}}
    out["table_bytes"] = sum(a["device_bytes"]
                             for a in out["arrays"].values())
    out["table_bytes_per_row"] = out["table_bytes"] / params["numValues"]
    out["memory_peak_bytes"] = device.memory_peak_bytes(used)
    ref_spec = config["correct"]
    module = references.load(ref_spec["reference"])
    args = ref_spec.get("args", {})
    stage = config["stage"]["paramMap"]
    t = time.perf_counter()
    reference = module.run(columns, stage, len(used), **args)
    out["reference_s"] = time.perf_counter() - t
    out["reference_rounds"] = reference.get("_rounds")
    variants = [("control_bfloat16", {"precision": "bfloat16"})]
    variants += [(f"fault_{f}", {"fault": f}) for f in module.FAULTS]
    for name, kw in variants:
        t = time.perf_counter()
        other = module.run(columns, stage, len(used), **args, **kw)
        answer = {k: v for k, v in other.items() if not k.startswith("_")}
        correct, compared = check.decide([answer], module, reference,
                                         ref_spec["limits"])
        out[name] = {"correct": correct, "seconds": time.perf_counter() - t,
                     "compared": {k: v["value"]
                                  for k, v in compared.items()}}
    return out


def main(argv=None) -> int:
    import jax

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", type=int)
    args = parser.parse_args(argv)
    config = json.loads(Path(args.config).read_text())
    spec.check_source(config)
    params = dict(config["inputData"]["paramMap"])
    if args.rows:
        params["numValues"] = args.rows
    used = jax.devices()[:config["mesh"]["data"]]
    stats = used[0].memory_stats() or {}
    report = {"config": config["name"], "rows": params["numValues"],
              "device": used[0].device_kind,
              "bytes_limit": stats.get("bytes_limit"), "seeds": []}
    for seed in [int(s) for s in args.seeds.split(",")]:
        report["seeds"].append(one_seed(config, params, seed, used))
        print(json.dumps(report["seeds"][-1]), flush=True)
    report["every_control_and_fault_not_correct"] = all(
        v["correct"] is False for row in report["seeds"]
        for v in row.values() if isinstance(v, dict) and "correct" in v)
    print(json.dumps({k: v for k, v in report.items() if k != "seeds"}))
    out_dir = spec.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "sparse_room.json").write_text(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
