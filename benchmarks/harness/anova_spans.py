"""A UnivariateFeatureSelector fit's host time from inside (the ANOVA
F-test: continuous features against a categorical label): the program's
spans under the root ``UnivariateFeatureSelector.fit``
(``flink_ml_tpu/models/feature/selectors.py``, driven by
``ops/stats.py::moments_on_device``), read from the program's ring after the
traced window as ``program_spans`` reads the SGD fit's, ``lloyd_spans`` the
Lloyd fit's, ``nb_spans`` the NaiveBayes fit's and ``select_spans`` the
RobustScaler fit's. Six parts that sum to the root span: the five named
below, each the sum of the fit's spans of that name (a fit whose first rows
misjudged a column's reach launches and fetches twice; ``anova.test`` opens
once for F and p and once for the selection), and ``other`` (the root less
the five: ``anova.build_program``, ``fit.model``, the stage wrapper).
``passes`` is the sum of the ``passes`` attributes of the fit's
``anova.fetch`` spans, the one carrier of them: the whole reads of the
table each blocking read waited for.

A program without these spans (the parent of the PR that added them), an
empty ring (a ``--trace 0`` run) or fewer than ``MIN_FITS`` whole fits:
every reader returns None.
"""

from __future__ import annotations

import statistics

from . import program_spans

#: a fit is some 15 ms, so a 2 s capture holds over a hundred; a median
#: over fewer than this says the capture was cut short
MIN_FITS = 10
#: part of a fit -> the span whose duration it is
NAMED = {"place": "anova.place_inputs", "check": "anova.check",
         "launch": "anova.launch", "fetch": "anova.fetch",
         "test": "anova.test"}
PARTS = tuple(NAMED) + ("other",)


def split_us(fit) -> dict:
    """One whole fit in six parts, microseconds, that sum to its root."""
    root = next(s for s in fit if s["parent"] is None)
    parts = {part: sum(s["dur_us"] for s in fit if s["name"] == name)
             for part, name in NAMED.items()}
    parts["other"] = root["dur_us"] - sum(parts.values())
    return parts


def passes_of(fit):
    """The ``passes`` the fit's ``anova.fetch`` spans name, summed; None
    where none names any."""
    found = [s.get("attrs", {}).get("passes") for s in fit
             if s["name"] == NAMED["fetch"]]
    found = [p for p in found if p is not None]
    return sum(found) if found else None


def medians_ms(records=None):
    """``{part: median over the whole fits, ms}`` plus ``root``, ``fits``
    and ``passes`` (median over the fits that name them, or None), or None
    with fewer than ``MIN_FITS`` whole fits."""
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
    passes = [p for p in map(passes_of, fits) if p is not None]
    out["passes"] = statistics.median(passes) if passes else None
    return out


def read(part: str):
    """What a reader returns: one part's median, or None."""
    found = medians_ms()
    return None if found is None else found[part]
