"""Seconds of set-up a sparse column's dictionaries cost: the program's cold
spans ``sparse:index`` (the column's index, once a column: the ids' range,
the hot positions, each position's proposed dictionary from a sorted sample)
and ``sparse:check`` (a fit's check of the rows it reads against those
dictionaries, once for rows no earlier fit checked), summed over the records
that closed no later than the first ``first_fit`` root, by
``cold_spans.setup_records``' rule. None where the program keeps no cold
records or recorded neither span (a program whose index opens no cold span,
or a cell with no sparse column)."""
from __future__ import annotations

from benchmarks.harness import cold_spans

NAMES = ("sparse:index", "sparse:check")


def seconds(records):
    """The two spans' seconds in set-up's records, or None where there are
    none."""
    kept, _ = cold_spans.setup_records(records)
    found = [r["dur_us"] for r in kept if r["name"] in NAMES]
    return sum(found) / 1e6 if found else None


def read(ctx):
    records = cold_spans.cold()
    return None if records is None else seconds(records)
