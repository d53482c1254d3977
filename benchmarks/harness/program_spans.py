"""The program's own spans, read after the window: where a fit's host time
goes, from inside.

While a ``jax.profiler`` capture runs, the program records a span at each
boundary a fit crosses (``flink_ml_tpu/observability/tracing.py``: the root
``<Stage>.fit``, ``sgd.optimize`` and its children, ``fit.extract``,
``fit.model``) into its in-memory ring; the traced window's capture is such
a capture, so after it the ring holds the traced fits. This module takes the
whole fits out of the ring — a root of kind ``fit`` with every span under it
still there — and splits each into seven parts that sum to the root.

Beside ``system.py`` this is the one module of the harness that imports the
program, and it imports that one module. A program without such spans (or
with an empty ring: a ``--trace 0`` run) gives no fits, and every reader
returns None.
"""

from __future__ import annotations

import statistics

#: fewer whole fits than this and a median says nothing: no metric
MIN_FITS = 10
OPTIMIZE = "sgd.optimize"
#: part of a fit -> the span whose duration it is
NAMED = {"place": "sgd.place_inputs", "carry": "sgd.init_carry",
         "build": "sgd.build_program", "launch": "sgd.launch",
         "fetch": "sgd.fetch"}
PARTS = ("seam",) + tuple(NAMED) + ("other",)


def ring():
    """The program's ring of finished spans, oldest first; empty where the
    program has none."""
    try:
        from flink_ml_tpu.observability.tracing import tracer
    except ImportError:
        return ()
    return getattr(tracer, "recent", ())


def whole_fits(records) -> list:
    """``[[span, ...]]``, one list a whole fit. Each span is the ring's
    record with ``self_us`` added: its duration minus what its children
    cover. Left out: a trace whose root is missing (still open) or is not of
    kind ``fit``, one whose spans name a parent that is gone, and — when the
    ring is full — the one that holds the ring's oldest record: what came
    before that record was evicted, and may have been its child."""
    full = getattr(records, "maxlen", None) == len(records)
    records = list(records)
    by_trace = {}
    for rec in records:
        by_trace.setdefault(rec["trace"], []).append(rec)
    if full and records:
        del by_trace[records[0]["trace"]]
    return [_with_self_times(spans) for spans in by_trace.values()
            if _is_whole(spans)]


def _is_whole(spans) -> bool:
    ids = {s["id"] for s in spans}
    roots = [s for s in spans if s["parent"] is None]
    return (len(roots) == 1
            and roots[0].get("attrs", {}).get("kind") == "fit"
            and all(s["parent"] in ids for s in spans
                    if s["parent"] is not None))


def _with_self_times(spans) -> list:
    covered = {}
    for s in spans:
        if s["parent"] is not None:
            covered[s["parent"]] = covered.get(s["parent"], 0) + s["dur_us"]
    return [dict(s, self_us=max(0, s["dur_us"] - covered.get(s["id"], 0)))
            for s in spans]


def split_us(fit) -> dict:
    """One fit in seven parts, microseconds, that sum to its root span:
    ``seam`` is the root less ``sgd.optimize`` (the stage wrapper,
    ``fit.extract``, ``fit.model``); the five named parts are their spans
    (summed where a fit has several: one ``sgd.launch`` and ``sgd.fetch`` a
    segment); ``other`` is what is left of ``sgd.optimize``: the health
    guard, the checks, and whatever no span names."""
    def total(name):
        return sum(s["dur_us"] for s in fit if s["name"] == name)

    root = next(s for s in fit if s["parent"] is None)
    parts = {part: total(name) for part, name in NAMED.items()}
    optimize = total(OPTIMIZE)
    parts["seam"] = root["dur_us"] - optimize
    parts["other"] = optimize - sum(parts[p] for p in NAMED)
    return parts


def medians_ms(records=None):
    """``{part: median over the whole fits, ms}`` plus ``root`` and ``fits``,
    or None with fewer than ``MIN_FITS`` whole fits."""
    fits = whole_fits(ring() if records is None else records)
    if len(fits) < MIN_FITS:
        return None
    splits = [split_us(fit) for fit in fits]
    out = {part: statistics.median(s[part] for s in splits) / 1e3
           for part in PARTS}
    out["root"] = statistics.median(sum(s.values()) for s in splits) / 1e3
    out["fits"] = len(fits)
    return out


def read(part: str):
    """What a reader returns: one part's median, or None."""
    found = medians_ms()
    return None if found is None else found[part]
