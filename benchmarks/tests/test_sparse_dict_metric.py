"""``sparse_dict_entry_pct``: the share of a sparse fit's window entries the
dictionary form took, read from the ``sgd.optimize`` span, on a hand-made
ring and in the CPU rehearsal of ``criteo_fit_ref20``."""

import json

import pytest

from benchmarks.harness import readers, spec
from benchmarks.tests.test_sparse_cell import CELL, drive, fit_records

METRIC = "sparse_dict_entry_pct"


def with_entries(records, taken, entries=78_000_000):
    for r in records:
        if r["name"] == "sgd.optimize":
            r["attrs"].update(dict_entries=taken, entries=entries)
    return records


def test_the_metric_is_the_criteo_cell_s_alone():
    assert readers.load(spec.layer_metric_file(METRIC)["reader"]) is not None
    with open(spec.ROOT / "BENCHMARK.json") as f:
        workloads = json.load(f)["workloads"]
    for work in workloads:
        listed = {m["name"] for m in spec.load_cell(work["name"]).per_layer}
        assert (METRIC in listed) == (work["name"] == CELL)


def test_the_reader_gives_the_median_share_or_nothing():
    read = readers.load(METRIC)
    shares = (22_000_000, 22_000_000, 20_000_000)
    records = [r for t, taken in enumerate(shares, 1)
               for r in with_entries(fit_records(t), taken)]
    dense = with_entries(fit_records(9, path="xla-while"), 0)
    assert read({}, records + dense) == pytest.approx(
        100 * 22_000_000 / 78_000_000)
    # a program without the attributes, and too few fits: nothing
    older = [r for t in range(1, 4) for r in fit_records(t)]
    assert read({}, older) is None
    assert read({}, records[:14]) is None


def test_the_rehearsal_reads_eleven_of_thirty_nine_positions():
    """The generator's fields of at most 1,024 buckets (C2, C5, C6, C8, C9,
    C14, C17, C20, C22, C23, C25) take the dictionary form."""
    _, result = drive(True, 3.0)
    assert result["correct"] is True
    assert result["metrics"][METRIC]["value"] == pytest.approx(
        100 * 11 / 39)
