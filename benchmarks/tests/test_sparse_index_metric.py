"""``setup_sparse_index_s``: the set-up seconds of a sparse column's index
and of the first fit's check of its dictionaries, read from the program's
cold spans ``sparse:index`` and ``sparse:check`` on hand-made record lists."""

import json

import pytest

from benchmarks.harness import readers, spec
from benchmarks.harness.readers import setup_sparse_index_s
from benchmarks.tests.test_cold_spans import rec

METRIC = "setup_sparse_index_s"
CELL = "criteo_fit_ref20"


def cold_start():
    """The click-through cell's set-up: the index (its program's build
    inside it) before the first fit, the check inside the first fit, and a
    later check, after it, of rows a longer fit reads."""
    return [
        rec("pkg", None, "import:flink_ml_tpu", 0, 2_000_000),
        rec("b0", "ix", "build:sparse_index", 2_500_000, 100),
        rec("ix", None, "sparse:index", 2_400_000, 80_000, rows=23_000_000,
            entries=39, proposed=16),
        rec("b1", "ck", "build:sparse_check", 3_000_100, 100),
        rec("ck", "ff", "sparse:check", 3_000_000, 45_000, rows=2_000_000,
            positions=16, demoted=0),
        rec("b2", "ff", "build:sgd_segment", 3_100_000, 200),
        rec("ff", None, "first_fit", 2_900_000, 1_200_000, kind="first_fit",
            stage="LogisticRegression"),
        rec("late", None, "sparse:check", 9_000_000, 300_000,
            rows=21_000_000, positions=16, demoted=1),
    ]


def test_the_metric_is_the_criteo_cell_s_alone():
    assert readers.load(spec.layer_metric_file(METRIC)["reader"]) is not None
    with open(spec.ROOT / "BENCHMARK.json") as f:
        workloads = json.load(f)["workloads"]
    for work in workloads:
        listed = {m["name"] for m in spec.load_cell(work["name"]).per_layer}
        assert (METRIC in listed) == (work["name"] == CELL)


def test_the_index_and_the_first_fit_s_check_are_summed():
    assert setup_sparse_index_s.seconds(cold_start()) == pytest.approx(
        0.080 + 0.045)


def test_a_check_after_the_first_fit_is_not_set_up():
    records = cold_start()
    late = [r for r in records if r["id"] != "ck"]
    assert setup_sparse_index_s.seconds(late) == pytest.approx(0.080)
    # no first fit at all: every record is set-up's
    alone = [r for r in records if r["id"] not in ("ff", "b2")]
    assert setup_sparse_index_s.seconds(alone) == pytest.approx(
        0.080 + 0.045 + 0.300)


def test_a_program_without_the_spans_reads_nothing():
    """A program older than the two spans keeps cold records of its
    imports and first fit, and none of its index: nothing, not 0.0."""
    parent = [r for r in cold_start() if not r["name"].startswith("sparse:")]
    assert setup_sparse_index_s.seconds(parent) is None
    assert setup_sparse_index_s.seconds([]) is None


def test_the_reader_reads_the_program_s_cold_list(monkeypatch):
    from flink_ml_tpu.observability.tracing import tracer

    monkeypatch.setattr(tracer, "cold", cold_start())
    assert readers.load(METRIC)({}) == pytest.approx(0.125)
    monkeypatch.delattr(tracer, "cold")
    assert readers.load(METRIC)({}) is None
