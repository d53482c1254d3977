"""A sparse LogisticRegression fit's host time from inside (a fit over a
device sparse column: ``SGD.optimize_sparse``, ``flink_ml_tpu/ops/
optimizer.py``): the program's spans under the root ``<Stage>.fit``, read
from the program's ring after the traced window as ``program_spans`` reads
the dense SGD fit's. Only fits whose ``sgd.optimize`` names a sparse path
(its ``path`` attribute starts ``sparse-``) are read.

Four parts that sum to the root span: ``place`` (``sgd.place_inputs``),
``launch`` (``sgd.launch``: the enqueue), ``fetch`` (``sgd.fetch``: the
blocking read, where the wait for the rounds falls), each the sum of the
fit's spans of that name, and ``other`` (the root less the three: the stage
wrapper, ``fit.extract``, ``fit.model``, ``sgd.init_carry``,
``sgd.build_program``, ``sgd.health``). ``batch_reads`` is the
``batch_reads`` attribute of the fit's ``sgd.optimize``: the HBM reads of a
round's batch the fit made (one a round while the window is read once).

A program without these spans or paths (an older one), an empty ring (a
``--trace 0`` run) or fewer than ``MIN_FITS`` whole fits: every reader
returns None.
"""

from __future__ import annotations

import statistics

from . import program_spans

#: a fit is some 0.7 s on a TPU v5e (PERF.md, section 6), so a capture of 2 s,
#: started and stopped between fits, holds three whole fits or more; fewer
#: says the capture was cut short
MIN_FITS = 3
OPTIMIZE = "sgd.optimize"
#: part of a fit -> the span whose duration it is
NAMED = {"place": "sgd.place_inputs", "launch": "sgd.launch",
         "fetch": "sgd.fetch"}
PARTS = tuple(NAMED) + ("other",)


def is_sparse(fit) -> bool:
    return any(s["name"] == OPTIMIZE and str(
        s.get("attrs", {}).get("path", "")).startswith("sparse-")
        for s in fit)


def split_us(fit) -> dict:
    """One whole fit in four parts, microseconds, that sum to its root."""
    root = next(s for s in fit if s["parent"] is None)
    parts = {part: sum(s["dur_us"] for s in fit if s["name"] == name)
             for part, name in NAMED.items()}
    parts["other"] = root["dur_us"] - sum(parts.values())
    return parts


def batch_reads(fit):
    """The ``batch_reads`` attribute of the fit's ``sgd.optimize``."""
    found = [s.get("attrs", {}).get("batch_reads") for s in fit
             if s["name"] == OPTIMIZE]
    return next((r for r in found if r is not None), None)


def medians_ms(records=None):
    """``{part: median over the whole sparse fits, ms}`` plus ``root``,
    ``fits`` and ``batch_reads`` (median over the fits that carry it, or
    None), or None with fewer than ``MIN_FITS`` whole sparse fits."""
    fits = [fit for fit in program_spans.whole_fits(
        program_spans.ring() if records is None else records)
        if is_sparse(fit)]
    if len(fits) < MIN_FITS:
        return None
    splits = [split_us(fit) for fit in fits]
    out = {part: statistics.median(s[part] for s in splits) / 1e3
           for part in PARTS}
    out["root"] = statistics.median(sum(s.values()) for s in splits) / 1e3
    out["fits"] = len(fits)
    reads = [r for r in map(batch_reads, fits) if r is not None]
    out["batch_reads"] = statistics.median(reads) if reads else None
    return out


def read(part: str):
    """What a reader returns: one part's median, or None."""
    found = medians_ms()
    return None if found is None else found[part]
