"""Where the device idles, by what the program was doing: the reading on one
clock. Runs a cell's fits back to back as the window does, captures
``jax.profiler`` for ``--capture-s`` seconds starting ``--capture-at``
seconds in (several starts, comma-separated, give several captures of one
run: an early and a late one show what grows), reads each raw
``.xplane.pb`` itself and prints, for the busiest device:

- its idle time and its busy time by the *innermost* host span that covers
  each instant: the harness's ``bench.*`` and the program's own
  (``<Stage>.fit``, ``fit.*``, ``sgd.*``, ``collective.*``, ``segment``,
  ``epoch``), which lie nested on the caller's line of the host plane;
- the ``XLA Modules`` a fit runs, by name, and whether every operation of the
  segment program lies between the start of its fit's ``sgd.launch`` and the
  end of its ``sgd.fetch``;
- the median ``bench.fit`` span, and the seven parts of a fit from the
  program's ring (``harness/program_spans.py``) for the same fits.

``harness/trace_reduce.load_xplane`` keeps of the host only ``bench.*`` and
splits idle time over spans it takes to be sequential, so the result line's
``breakdown.idle_gaps`` cannot say this. A builder's tool, like ``sets.py``:
the driver does not run it. On a program without spans of its own the
tables name ``bench.*`` alone.

    python benchmarks/tools/program_gaps.py --workload <cell> --seed <n> \\
        --seconds 40 --capture-at 5,35 --capture-s 2
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import device, program_spans, spec  # noqa: E402
from benchmarks.harness import trace_reduce as tr  # noqa: E402
from benchmarks.harness.windows import span  # noqa: E402

PROGRAM_PREFIXES = ("bench.", "fit.", "sgd.", "collective.")
PROGRAM_NAMES = ("segment", "epoch")
SEGMENT_MODULE = "jit_sgd_segment"


def is_span(name: str) -> bool:
    return (name.startswith(PROGRAM_PREFIXES) or name in PROGRAM_NAMES
            or name.endswith((".fit", ".transform")))


def load_raw(path) -> dict:
    """The plain form of ``trace_reduce`` with the program's spans kept."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        on_device = bool(tr.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events if on_device or is_span(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def caller_spans(trace: dict) -> list:
    """``[(name, start, end)]`` sorted by start, outer before inner: the
    spans of the host line that holds ``bench.fit``."""
    for plane in trace["planes"]:
        if tr.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if any(e[0] == tr.FIT_SPAN for e in line["events"]):
                return sorted(((e[0], e[1], e[1] + e[2])
                               for e in line["events"]),
                              key=lambda s: (s[1], -s[2]))
    return []


def innermost(spans) -> list:
    """Nested spans cut into disjoint ``(start, end, name)`` pieces, each
    named after the innermost span that covers it."""
    out, stack = [], []   # stack entries: [name, end, covered up to]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))

    for name, start, end in spans:
        close(start)
        if stack:
            end = min(end, stack[-1][1])
            if start > stack[-1][2]:
                out.append((stack[-1][2], start, stack[-1][0]))
            stack[-1][2] = end
        stack.append([name, end, start])
    close(float("inf"))
    return sorted(out)


def attribute(intervals, pieces) -> dict:
    """``{name: ns}``: each interval's time by the piece it falls in."""
    out, j = {}, 0
    for lo, hi in intervals:
        while j < len(pieces) and pieces[j][1] <= lo:
            j += 1
        k, left = j, hi - lo
        while k < len(pieces) and pieces[k][0] < hi:
            part = min(hi, pieces[k][1]) - max(lo, pieces[k][0])
            if part > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0) + part
                left -= part
            k += 1
        if left > 0:
            out["outside-spans"] = out.get("outside-spans", 0) + left
    return out


def module_name(raw: str) -> str:
    """``jit_sgd_segment(1234)`` -> ``jit_sgd_segment``."""
    return raw.split("(", 1)[0]


def reduce_raw(trace: dict) -> dict:
    spans = caller_spans(trace)
    fits = [s for s in spans if s[0] == tr.FIT_SPAN]
    ops = tr.device_lines(trace, tr.OPS_LINE)
    if len(fits) < 2 or not ops:
        raise ValueError(f"{len(fits)} fits and {len(ops)} devices with "
                         f"operations in the capture")
    lo, hi = fits[0][1], fits[-1][1]
    busy = {dev: tr.clip(tr.union((e[1], e[1] + e[2]) for e in evs), lo, hi)
            for dev, evs in ops.items()}
    busiest = max(busy, key=lambda dev: tr.length(busy[dev]))
    pieces = innermost(spans)
    cycles = len(fits) - 1

    def per_fit_ms(table):
        return {name: ns / cycles / 1e6 for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])}

    modules = [e for e in tr.device_lines(trace, tr.MODULES_LINE)
               .get(busiest, []) if lo <= e[1] < hi]
    by_module = {}
    for name, _, dur in modules:
        entry = by_module.setdefault(module_name(name), [0, 0])
        entry[0] += 1
        entry[1] += dur

    # the segment program's operations against its fit's launch and fetch
    launches = [s for s in spans if s[0] == "sgd.launch"]
    fetches = [s for s in spans if s[0] == "sgd.fetch"]
    seg_ms, inside, outside = [], 0, 0
    for _, a, b in fits[:-1]:
        mine = [e for e in modules if a <= e[1] < b
                and module_name(e[0]) == SEGMENT_MODULE]
        first = [s[1] for s in launches if a <= s[1] < b]
        last = [s[2] for s in fetches if a <= s[1] < b]
        for _, start, dur in mine:
            seg_ms.append(dur / 1e6)
            seg_ops = [e for e in ops[busiest]
                       if start <= e[1] < start + dur]
            ok = bool(first and last) and all(
                min(first) <= e[1] and e[1] + e[2] <= max(last)
                for e in seg_ops)
            inside += ok
            outside += not ok

    nested = sum(1 for s in spans if s[0].endswith(".fit")
                 and s[0] != tr.FIT_SPAN
                 and any(f[1] <= s[1] and s[2] <= f[2] for f in fits))
    return {
        "fits": cycles,
        "busiest_device": busiest,
        "bench_fit_median_ms": statistics.median(
            b - a for _, a, b in fits[:-1]) / 1e6,
        "idle_share": 1 - tr.length(busy[busiest]) / (hi - lo),
        "idle_ms_per_fit_by_innermost_span": per_fit_ms(
            attribute(tr.gaps(busy[busiest], lo, hi), pieces)),
        "busy_ms_per_fit_by_innermost_span": per_fit_ms(
            attribute(busy[busiest], pieces)),
        "modules_per_fit": {name: [count / cycles, ns / cycles / 1e6]
                            for name, (count, ns) in sorted(
                                by_module.items(), key=lambda kv: -kv[1][1])},
        "program_roots_inside_bench_fit": nested,
        "segment_program_median_ms": (statistics.median(seg_ms)
                                      if seg_ms else None),
        "segment_runs_inside_launch_to_fetch": inside,
        "segment_runs_outside": outside,
    }


def run(workload: str, seed: int, seconds: float, capture_at: list,
        capture_s: float, require_tpu: bool = True, overrides: dict = None,
        out=sys.stdout) -> int:
    cell = spec.load_cell(workload)
    if overrides:
        cell = run_cell._apply_overrides(cell, overrides)
    import jax

    from benchmarks.harness import system

    devices = jax.devices()
    if require_tpu:
        device.require_chips(devices, cell.chips)
    columns, params = run_cell.make_inputs(cell, seed, system,
                                           devices[:cell.chips])
    table = system.make_table(columns)
    stage = system.build_stage(cell.config["stage"]["className"], params)
    for _ in range(3):
        system.model_to_host(stage, system.fit(stage, table))

    pending = sorted(capture_at)
    captures, current, walls = [], None, []
    ring = program_spans.ring()
    start = time.perf_counter()
    while time.perf_counter() - start < seconds or current is not None:
        t_call = time.perf_counter()
        with span("bench.fit"):
            model = system.fit(stage, table)
        with span("bench.model_data"):
            system.model_to_host(stage, model)
        with span("bench.gap"):
            now = time.perf_counter()
            walls.append(now - t_call)
            del model
            if current is None and pending and now - start >= pending[0]:
                if hasattr(ring, "clear"):
                    ring.clear()
                current = {"at_s": pending.pop(0), "first_fit": len(walls),
                           "dir": tempfile.mkdtemp(prefix="gaps-trace-")}
                jax.profiler.start_trace(current["dir"])
            elif (current is not None
                  and now - start >= current["at_s"] + capture_s):
                jax.profiler.stop_trace()
                current["parts_ms"] = program_spans.medians_ms(ring)
                current["last_fit"] = len(walls)
                captures.append(current)
                current = None

    result = {"cell": cell.name, "seed": seed, "fits": len(walls),
              "path": getattr(stage, "last_execution_path", None),
              "fit_wall_median_ms": statistics.median(walls) * 1e3,
              "captures": []}
    for cap in captures:
        inside = walls[cap["first_fit"]:cap["last_fit"]]
        entry = {"at_s": cap["at_s"], "capture_s": capture_s,
                 "fit_wall_median_ms_in_capture":
                     statistics.median(inside) * 1e3,
                 "program_span_medians_ms": cap["parts_ms"]}
        try:
            entry.update(reduce_raw(load_raw(tr.find_xplane(cap["dir"]))))
        except (FileNotFoundError, ValueError) as exc:
            entry["not_reduced"] = str(exc)
        finally:
            shutil.rmtree(cap["dir"], ignore_errors=True)
        result["captures"].append(entry)
    text = json.dumps(result, indent=1)
    print(text, file=out, flush=True)
    out_dir = spec.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"program_gaps_{cell.name}_{seed}.json").write_text(text)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--capture-at", default="19")
    parser.add_argument("--capture-s", type=float, default=2.0)
    args = parser.parse_args(argv)
    try:
        spec.load_cell(args.workload)
    except spec.SpecError as exc:
        print(f"cannot run: {exc}", file=sys.stderr)
        return 2
    run_cell.configure_compile_cache()
    try:
        return run(args.workload, args.seed, args.seconds,
                   [float(a) for a in args.capture_at.split(",") if a],
                   args.capture_s)
    except device.DeviceError as exc:
        print(f"cannot run: {exc}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
