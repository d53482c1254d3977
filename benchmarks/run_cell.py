"""Run one cell of ``BENCHMARK.json`` once.

    python benchmarks/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips: set-up (compile cache, the look
for the chip, the table from the seed, the stage, warm fits until one
compiles nothing), the window, then the plain reference and the numbers
compared. The last line of standard output is the result.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.harness import check, compiles, counts, generators  # noqa: E402
from benchmarks.harness import device, references, spec, windows  # noqa: E402
from benchmarks.harness import readers, trace_reduce  # noqa: E402

MAX_WARM_FITS = 6


def configure_compile_cache() -> str:
    """JAX's persistent cache at the fixed path the program's one setter
    gives — ``<checkout>/.jax_cache``, or ``JAX_COMPILATION_CACHE_DIR`` where
    the machine sets it — keeping every executable whatever its compile
    took (JAX's default keeps only compiles of a second or more)."""
    import jax

    from benchmarks.harness import system

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return system.configure_compile_cache()


def _finite(value: float) -> float:
    """JSON has no infinity: a gap that is not finite prints as 1e300."""
    return value if value == value and abs(value) < 1e300 else 1e300


def _slice_medians_ms(walls: list, slices: int = 8) -> list:
    """The median fit of each eighth of the window, in order: a run that
    drifts shows here, one that is slow throughout does not."""
    step = max(1, len(walls) // slices)
    return [round(statistics.median(walls[i:i + step]) * 1e3, 4)
            for i in range(0, step * slices, step) if walls[i:i + step]]


def _info(out, **fields) -> None:
    print(json.dumps(fields), file=out, flush=True)


def _apply_overrides(cell: spec.Cell, overrides: dict) -> spec.Cell:
    """Tests cut a cell to a size the CPU holds; the command cannot."""
    import dataclasses

    config = copy.deepcopy(cell.config)
    traffic = copy.deepcopy(cell.traffic)
    config["stage"].setdefault("paramMap", {}).update(
        overrides.get("stage", {}))
    config["inputData"].setdefault("paramMap", {}).update(
        overrides.get("inputData", {}))
    config["correct"]["limits"].update(overrides.get("limits", {}))
    traffic.update(overrides.get("traffic", {}))
    return dataclasses.replace(cell, config=config, traffic=traffic)


def make_inputs(cell: spec.Cell, seed: int, system, devices):
    """The mesh over ``devices``, the table's columns made on it from the
    seed, and the stage's parameters as this run has them."""
    mesh = system.configure_mesh(devices)
    data = cell.config["inputData"]
    columns = generators.make_columns(data["className"], data["paramMap"],
                                      seed, system.row_sharding(mesh))
    params = cell.stage_params()
    if cell.config.get("stage_seed_param"):
        params[cell.config["stage_seed_param"]] = seed
    return columns, params


def read_layer_metrics(cell: spec.Cell, ctx: dict, root: Path) -> dict:
    """Each of the cell's per-layer metrics through the reader its file
    names; a reader that finds nothing to read leaves its metric out."""
    values = {}
    for metric in cell.per_layer:
        reader = readers.load(
            spec.layer_metric_file(metric["name"], root)["reader"])
        value = reader(ctx)
        if value is not None:
            values[metric["name"]] = value
    return values


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = spec.ROOT, system=None, require_tpu: bool = True,
        overrides: dict = None, peaks: dict = None, on_trace=None,
        out=sys.stdout, err=sys.stderr) -> int:
    cell = spec.load_cell(workload, root)
    if overrides:
        cell = _apply_overrides(cell, overrides)
    import jax

    listener = compiles.CompileListener().install()
    phases = {"imports_s": time.perf_counter() - _PROCESS_START}
    t = time.perf_counter()
    devices = jax.devices()
    phases["devices_s"] = time.perf_counter() - t
    if require_tpu:
        device_info = device.require_chips(devices, cell.chips)
    else:
        device_info = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
    if peaks is None:
        peaks = device.peaks_for(device_info["kind"])
    if system is None:
        from benchmarks.harness import system

    # -- set-up ---------------------------------------------------------
    used = devices[:cell.chips]
    t = time.perf_counter()
    columns, params = make_inputs(cell, seed, system, used)
    phases["datagen_s"] = time.perf_counter() - t
    data = cell.config["inputData"]
    try:
        table = system.make_table(columns)
    except NotImplementedError as exc:
        print(f"cannot run: {exc}", file=err, flush=True)
        return 2
    stage = system.build_stage(cell.config["stage"]["className"], params)
    count = counts.per_fit(cell.config["counts"], params, data["paramMap"])
    least = counts.least_seconds(count, peaks, cell.chips)

    def fit():
        return system.fit(stage, table)

    def to_host(model):
        return system.model_to_host(stage, model)

    warm_fits = 0
    t_warm = time.perf_counter()
    while True:
        before = listener.snapshot()
        to_host(fit())
        warm_fits += 1
        if compiles.delta(listener.snapshot(),
                          before)["backend_compiles"] == 0:
            break
        if warm_fits >= MAX_WARM_FITS:
            print(f"still compiling after {warm_fits} warm fits", file=err)
            return 1
    setup_compiles = listener.snapshot()
    phases["warm_fits_s"] = time.perf_counter() - t_warm

    # -- the window -----------------------------------------------------
    tracer = None
    if trace:
        capture = float(cell.traffic.get("trace_capture_s", 2.0))
        tracer = windows.TraceControl(
            start_after_s=max(0.0, (seconds - capture) / 2.0),
            capture_s=capture)
    loop = windows.load(cell.traffic["kind"])
    window_start = time.perf_counter()
    setup_s = window_start - _PROCESS_START
    win = loop(fit, to_host, seconds, count["rows"], tracer)
    window_compiles = compiles.delta(listener.snapshot(), setup_compiles)
    memory_peak = device.memory_peak_bytes(used)

    reduction = None
    if tracer is not None and tracer.dir is not None:
        try:
            plain = trace_reduce.load_xplane(
                trace_reduce.find_xplane(tracer.dir))
            if on_trace is not None:
                on_trace(plain)
            reduction = trace_reduce.reduce(plain)
        except (FileNotFoundError, ValueError) as exc:
            print(f"trace not reduced: {exc}", file=err)
        finally:
            tracer.cleanup()

    # -- correct: every answer of the window against the reference -------
    del stage, fit, to_host
    ref_spec = cell.config["correct"]
    ref_module = references.load(ref_spec["reference"])
    t = time.perf_counter()
    reference = ref_module.run(columns, params, cell.chips,
                               **ref_spec.get("args", {}))
    reference_s = time.perf_counter() - t
    correct, compared = check.decide(
        win.pop("answers"), ref_module, reference, ref_spec["limits"],
        extra={"window_backend_compiles": (
            window_compiles["backend_compiles"], 0)})

    # -- the lines ------------------------------------------------------
    paths = sorted(set(map(str, win.pop("paths"))))
    walls = win["walls_s"]
    _info(out, cell=cell.name, seed=seed, mesh=f"data={cell.chips}",
          execution_paths=paths, fits=win["attempted"],
          window_s=win["window_s"],
          fit_wall_median_ms=statistics.median(walls) * 1e3,
          fit_wall_slice_medians_ms=_slice_medians_ms(walls),
          warm_fits=warm_fits,
          setup_compiles=setup_compiles, window_compiles=window_compiles,
          setup_phases=phases, reference_s=reference_s,
          reference_rounds=reference.get("_rounds"),
          rows_per_fit=count["rows"], least_fit_s=least)
    if window_compiles["backend_compiles"]:
        _info(out, warning="compiles inside the window: not steady",
              window_compiles=window_compiles)

    if trace:
        listed = cell.per_layer
        values = read_layer_metrics(cell, {
            "trace": reduction, "window": win, "count": count,
            "least": least, "peaks": peaks, "chips": cell.chips,
            "compiles": {"setup": setup_compiles, "window": window_compiles},
            "setup": phases}, root)
    else:
        listed = cell.end_to_end
        values = dict(win["metrics"], setup_s=setup_s)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in listed if m["name"] in values}

    device_info = dict(device_info, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics,
              "device": device_info}
    if trace and reduction is not None:
        device_info["busy_s"] = reduction["busy_s_mean"]
        device_info["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["compared"] = {
        name: {"value": _finite(c["value"]), "limit": c["limit"]}
        for name, c in compared.items()}
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=err)
    print(f"correct: {correct}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        # before JAX: a bad name costs nothing
        spec.load_cell(args.workload)
        import flink_ml_tpu  # noqa: F401 — the system under test is here
    except (spec.SpecError, ImportError) as exc:
        print(f"cannot run: {exc}", file=sys.stderr)
        return 2
    configure_compile_cache()
    try:
        return run(args.workload, args.seed, args.seconds, bool(args.trace))
    except device.DeviceError as exc:
        print(f"cannot run: {exc}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
