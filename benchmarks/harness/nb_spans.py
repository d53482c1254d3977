"""A NaiveBayes fit's host time from inside: the program's spans under the
root ``NaiveBayes.fit`` (``flink_ml_tpu/models/classification/
naivebayes.py``), read from the program's ring after the traced window as
``program_spans`` reads the SGD fit's and ``lloyd_spans`` the Lloyd fit's.
Six parts that sum to the root span: the five named below and ``other``
(the root less the five: ``nb.build_program``, ``fit.model``, the stage
wrapper). A fit whose first rows misjudged the table's range looks and
counts twice: its ``nb.check``, ``nb.launch`` and ``nb.fetch`` spans are
summed.

A program without these spans (the parent of the PR that added them), an
empty ring (a ``--trace 0`` run) or fewer than ``MIN_FITS`` whole fits:
every reader returns None.
"""

from __future__ import annotations

import statistics

from . import program_spans

#: a fit is some 15 ms, so a 2 s capture holds over a hundred; a median
#: over fewer than this says the capture was cut short
MIN_FITS = 20
#: part of a fit -> the span whose duration it is
NAMED = {"place": "nb.place_inputs", "check": "nb.check",
         "launch": "nb.launch", "fetch": "nb.fetch",
         "finalize": "nb.finalize"}
PARTS = tuple(NAMED) + ("other",)


def split_us(fit) -> dict:
    """One whole fit in six parts, microseconds, that sum to its root."""
    root = next(s for s in fit if s["parent"] is None)
    parts = {part: sum(s["dur_us"] for s in fit if s["name"] == name)
             for part, name in NAMED.items()}
    parts["other"] = root["dur_us"] - sum(parts.values())
    return parts


def medians_ms(records=None):
    """``{part: median over the whole NaiveBayes fits, ms}`` plus ``root``
    and ``fits``, or None with fewer than ``MIN_FITS`` of them."""
    names = set(NAMED.values())
    fits = [fit for fit in program_spans.whole_fits(
        program_spans.ring() if records is None else records)
        if names <= {s["name"] for s in fit}]
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
