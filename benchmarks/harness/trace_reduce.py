"""From a profiler trace to numbers. The profiler's ``.xplane.pb`` is read
with ``jax.profiler.ProfileData`` into a plain form — planes, their lines,
events as ``[name, start_ns, duration_ns]`` — that also loads from the
gzipped JSON kept under ``fixtures/``; every reduction below works on that
form, so the test against the recorded trace exercises the same code as a
run on the chip.

What is a device, what is an operation: a plane named ``/device:TPU:<i>``
is a chip; its line ``XLA Ops`` holds the operations (nested: a ``while``
contains its body's operations) and ``XLA Modules`` one event per program
execution. Host spans are the ``bench.*`` events that the harness writes
with ``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
FIT_SPAN = "bench.fit"
#: XLA's names for collectives, and the names JAX's own leave in the trace
#: (the four-chip fit's per-round reduction shows as ``psum.<n>``)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|psum|pmax|pmin|pmean|ppermute|all_gather"
    r"|all_to_all|psum_scatter", re.I)


# -- loading -----------------------------------------------------------------

def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path) -> dict:
    """The plain form of an ``.xplane.pb``. Of host planes only the
    ``bench.*`` events are kept: the rest is the interpreter's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def dump_json_gz(trace: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_json_gz(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def cut(trace: dict, lo_ns: int, hi_ns: int) -> dict:
    """The events that lie wholly inside ``[lo_ns, hi_ns]``."""
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [e for e in line["events"]
                      if e[1] >= lo_ns and e[1] + e[2] <= hi_ns]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


# -- interval arithmetic -----------------------------------------------------

def union(intervals):
    """Sorted, disjoint ``[(start, end)]`` covering the same points."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def length(intervals) -> int:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b):
    """Points of the disjoint sorted ``a`` not covered by the disjoint
    sorted ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy, lo: int, hi: int):
    return subtract([(lo, hi)], clip(busy, lo, hi))


# -- what the planes hold ----------------------------------------------------

def op_name(raw: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()[:80]


def device_lines(trace: dict, line_name: str) -> dict:
    """``{device index: [[name, start, duration]]}`` sorted by start."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == line_name:
                out.setdefault(int(m.group(1)), []).extend(line["events"])
    return {dev: sorted(evs, key=lambda e: (e[1], -e[2]))
            for dev, evs in out.items()}


def host_spans(trace: dict):
    """``[(name, start, end)]`` of the harness's spans, sorted by start."""
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans.extend((e[0], e[1], e[1] + e[2]) for e in line["events"]
                         if e[0].startswith(SPAN_PREFIX))
    return sorted(spans, key=lambda s: s[1])


def self_times(events):
    """``[(name, start, end, self_ns, is_leaf)]`` for nested events on one
    line: an event's self time leaves out what its children cover."""
    out, stack = [], []

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, lo, hi, covered, kids = stack.pop()
            out.append((name, lo, hi, (hi - lo) - covered, kids == 0))

    for name, start, dur in events:
        close(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][2])
            stack[-1][3] += end - start
            stack[-1][4] += 1
        stack.append([name, start, end, 0, 0])
    close(float("inf"))
    return out


# -- the reduction -----------------------------------------------------------

def reduce(trace: dict) -> dict:
    """Everything the readers need, over the whole fit cycles the trace
    holds: from the start of the first whole ``bench.fit`` span to the
    start of the last. Raises ``ValueError`` where the trace holds fewer
    than two fits or no device operation."""
    spans = host_spans(trace)
    fits = [s for s in spans if s[0] == FIT_SPAN]
    ops = device_lines(trace, OPS_LINE)
    if not ops:
        raise ValueError("the trace holds no device operation")
    if len(fits) < 2:
        raise ValueError(f"the trace holds {len(fits)} whole fits; two or "
                         f"more are needed")
    lo, hi = fits[0][1], fits[-1][1]
    cycles = len(fits) - 1
    busy = {dev: clip(union((e[1], e[1] + e[2]) for e in evs), lo, hi)
            for dev, evs in ops.items()}
    busy_ns = {dev: length(iv) for dev, iv in busy.items()}
    busiest = max(busy_ns, key=busy_ns.get)

    # per fit: the span, and the busiest device's work inside it
    fit_ns, fit_busy_ns = [], []
    for _, a, b in fits[:-1]:
        fit_ns.append(b - a)
        fit_busy_ns.append(length(clip(busy[busiest], a, b)))

    # operations by self time; collectives not covered by compute
    table, coll, compute = {}, [], []
    for name, a, b, self_ns, leaf in self_times(
            [e for e in ops[busiest] if e[1] >= lo and e[1] + e[2] <= hi]):
        short = op_name(name)
        table[short] = table.get(short, 0) + self_ns
        if COLLECTIVE.search(short):
            coll.append((a, b))
        elif leaf:
            compute.append((a, b))
    exposed = subtract(union(coll), union(compute))

    # idle time, split over the (sequential) spans the host was in
    idle = {}
    ends = [sp[2] for sp in spans]
    for a, b in gaps(busy[busiest], lo, hi):
        left = b - a
        i = bisect.bisect_right(ends, a)
        while i < len(spans) and spans[i][1] < b:
            part = min(b, spans[i][2]) - max(a, spans[i][1])
            if part > 0:
                idle[spans[i][0]] = idle.get(spans[i][0], 0) + part
                left -= part
            i += 1
        if left > 0:
            idle["outside-spans"] = idle.get("outside-spans", 0) + left

    modules = device_lines(trace, MODULES_LINE).get(busiest, [])
    programs = sum(1 for e in modules if lo <= e[1] < hi)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "cycles": cycles,
        "window_s": (hi - lo) / 1e9,
        "devices": sorted(busy_ns),
        "busiest_device": busiest,
        "busy_s_by_device": {d: v / 1e9 for d, v in busy_ns.items()},
        "busy_s_mean": sum(busy_ns.values()) / len(busy_ns) / 1e9,
        "busy_s_busiest": busy_ns[busiest] / 1e9,
        "fit_s": [v / 1e9 for v in fit_ns],
        "fit_busy_s": [v / 1e9 for v in fit_busy_ns],
        "programs": programs,
        "collective_s": length(union(coll)) / 1e9,
        "collective_exposed_s": length(exposed) / 1e9,
        "device_ops": top(table),
        "idle_gaps": top(idle),
    }
