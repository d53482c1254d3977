"""The five readers of the program's cold spans, against record lists written
out by hand: nesting is not counted twice, what closed after the first
``first_fit`` root is left out, ``cache_load_s`` is not added to
``compile_s``, a program with the list and no such span reads 0.0 and one
without the list reads nothing; then the CPU rehearsal of a cell with the
five metrics in its traced line."""

import io
import json

import pytest

from benchmarks import run_cell
from benchmarks.harness import cold_spans, readers, spec

FIVE = ("setup_import_s", "setup_import_deps_s", "setup_first_fit_s",
        "setup_first_fit_import_s", "setup_first_fit_build_s")
PARTS = {"setup_import_s": "import", "setup_import_deps_s": "import_deps",
         "setup_first_fit_s": "first_fit",
         "setup_first_fit_import_s": "first_fit_import",
         "setup_first_fit_build_s": "first_fit_build"}
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
CUT = {"inputData": {"numValues": 48000}, "stage": {"globalBatchSize": 400}}
SEED = 2**31 + 3838


def rec(sid, parent, name, ts_us, dur_us, **attrs):
    return {"type": "span", "trace": "t", "id": sid, "parent": parent,
            "name": name, "ts_us": ts_us, "dur_us": dur_us, "attrs": attrs,
            "events": []}


def cold_start():
    """A process as the kernel cells run it, in the list's order (a child
    closes before its parent): the package with jax and numpy inside it,
    the models with the two scipy imports inside them, the first fit with
    Pallas' import (its tpu half inside it) and two builds, then — after
    the first fit — a second stage's first fit and a late import."""
    return [
        rec("np", "pkg", "import:numpy", 10, 200_000),
        rec("jax", "pkg", "import:jax", 210_010, 1_500_000),
        rec("pkg", None, "import:flink_ml_tpu", 0, 2_000_000),
        rec("sc", "agg", "import:scipy.cluster", 2_100_000, 700_000),
        rec("agg", "models", "import:flink_ml_tpu.models.clustering",
            2_050_000, 800_000),
        rec("ss", "models", "import:scipy.stats", 2_900_000, 1_000_000),
        rec("models", None, "import:flink_ml_tpu.models", 2_000_100,
            2_000_000),
        rec("tpu", "pal", "import:pallas.tpu", 5_100_000, 100_000),
        rec("pal", "ff", "import:pallas", 5_000_100, 1_200_000),
        rec("b1", "ff", "build:init_rows", 6_300_000, 300, lower_s=0.01),
        rec("b2", "ff", "build:lloyd", 6_400_000, 400, trace_s=0.02,
            traces=3),
        rec("ff", None, "first_fit", 5_000_000, 1_450_000,
            kind="first_fit", stage="KMeans", trace_s=0.05, lower_s=0.04,
            compile_s=0.06, cache_load_s=0.05, compiles=2, cache_hits=2),
        rec("late", None, "import:scipy.optimize", 7_000_000, 300_000),
        rec("b3", "ff2", "build:sgd_segment", 8_000_100, 200, trace_s=9.0),
        rec("ff2", None, "first_fit", 8_000_000, 900_000, kind="first_fit",
            stage="LogisticRegression", trace_s=1.0, compile_s=2.0),
    ]


def test_the_five_numbers_of_a_cold_start():
    got = cold_spans.seconds(cold_start())
    # the two top-level trees, whole: jax, numpy and the scipy imports lie
    # inside them and are not added again; pallas is the first fit's
    assert got["import"] == pytest.approx(2.0 + 2.0)
    # jax + numpy + scipy.cluster + scipy.stats: the topmost third parties
    assert got["import_deps"] == pytest.approx(1.5 + 0.2 + 0.7 + 1.0)
    assert got["first_fit"] == pytest.approx(1.45)
    # import:pallas.tpu lies inside import:pallas: counted once
    assert got["first_fit_import"] == pytest.approx(1.2)
    # the root's and its builds' trace, lower and compile seconds; the
    # cache load lies inside compile_s and is not added
    assert got["first_fit_build"] == pytest.approx(
        0.05 + 0.04 + 0.06 + 0.01 + 0.02)
    assert set(got) == set(PARTS.values())


def test_what_closed_after_the_first_first_fit_is_left_out():
    kept, first = cold_spans.setup_records(cold_start())
    assert first["attrs"]["stage"] == "KMeans"
    assert {r["id"] for r in kept} == {
        "np", "jax", "pkg", "sc", "agg", "ss", "models", "tpu", "pal",
        "b1", "b2", "ff"}
    # with the late import and the second first fit taken away nothing
    # moves: they were never read
    assert cold_spans.seconds(cold_start()[:12]) == cold_spans.seconds(
        cold_start())


def test_imports_inside_the_first_fit_and_before_it_add_and_overlap_nowhere():
    got = cold_spans.seconds(cold_start())
    every_top_import = sum(
        r["dur_us"] for r in cold_start()[:12]
        if r["name"].startswith("import:")
        and r["parent"] in (None, "ff")) / 1e6
    assert got["import"] + got["first_fit_import"] == pytest.approx(
        every_top_import)
    assert (got["first_fit_import"] + got["first_fit_build"]
            <= got["first_fit"])


def test_a_nested_first_fit_is_not_a_root():
    """A pipeline's first fit holds its stages' first fits: the root is the
    pipeline's, and a stage's build seconds are in its tree once."""
    records = [
        rec("b", "inner", "build:lloyd", 120, 10, trace_s=0.5),
        rec("inner", "outer", "first_fit", 100, 500, stage="KMeans",
            compile_s=0.25),
        rec("outer", None, "first_fit", 0, 1_000_000, stage="Pipeline",
            lower_s=0.125),
    ]
    kept, first = cold_spans.setup_records(records)
    assert first["attrs"]["stage"] == "Pipeline" and len(kept) == 3
    got = cold_spans.seconds(records)
    assert got["first_fit"] == 1.0
    assert got["first_fit_build"] == 0.5 + 0.25 + 0.125


def test_a_root_whose_parent_is_another_process_s_span_is_still_a_root():
    """A launched child's roots name the launcher's span as their parent
    (``FLINK_ML_TPU_TRACE_PARENT``): it is not in the list."""
    records = [rec("i", "far", "import:flink_ml_tpu", 0, 3_000_000),
               rec("ff", "far", "first_fit", 3_000_000, 250_000)]
    got = cold_spans.seconds(records)
    assert got["import"] == 3.0 and got["first_fit"] == 0.25


@pytest.mark.parametrize("part", sorted(PARTS.values()))
def test_a_program_with_the_list_and_no_such_span_reads_zero(part):
    assert cold_spans.seconds([])[part] == 0.0
    # imports and no fit yet: the first-fit numbers are 0.0, not missing
    only_imports = cold_start()[:7]
    got = cold_spans.seconds(only_imports)
    if part.startswith("first_fit"):
        assert got[part] == 0.0
    else:
        assert got[part] > 0.0
    assert isinstance(got[part], float)


@pytest.mark.parametrize("metric", FIVE)
def test_a_program_without_the_list_reads_nothing(metric, monkeypatch):
    """The parent of the PR that added the cold spans: its tracer has no
    ``cold``; the reader returns None and does not raise."""
    monkeypatch.setattr(cold_spans, "cold", lambda: None)
    assert readers.load(metric)({}) is None


@pytest.mark.parametrize("metric", FIVE)
def test_each_reader_reads_its_part_of_the_program_s_list(
        metric, monkeypatch):
    monkeypatch.setattr(cold_spans, "cold", cold_start)
    assert readers.load(metric)({}) == cold_spans.seconds(
        cold_start())[PARTS[metric]]


def test_the_program_s_tracer_keeps_the_list_the_readers_read():
    from flink_ml_tpu.observability.tracing import tracer

    assert cold_spans.cold() is tracer.cold
    # this process imported the package: its stamps were adopted
    assert any(r["name"] == "import:flink_ml_tpu" for r in tracer.cold)


def test_the_five_are_listed_for_every_cell_and_move_setup_s():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    # found by name, in their order among themselves: later PRs append
    mine = [m for m in bench["per_layer"] if m["name"] in FIVE]
    assert [m["name"] for m in mine] == list(FIVE)
    for m in mine:
        assert m["moves"] == "setup_s" and m["unit"] == "s"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert "workloads" not in m
        assert spec.layer_metric_file(m["name"])["reader"] == m["name"]
    for work in bench["workloads"]:
        listed = {m["name"] for m in spec.load_cell(work["name"]).per_layer}
        assert set(FIVE) <= listed


def test_traced_rehearsal_prints_the_five():
    """The cell itself on the CPU, cut small: the traced line holds the five,
    they obey the inequalities the records promise, and an untraced line
    holds none of them (they are per-layer metrics)."""
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run("lr_fit_ref20", SEED, 0.6, True, require_tpu=False,
                      overrides=CUT, peaks=PEAKS, out=out, err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    values = {name: result["metrics"][name]["value"] for name in FIVE}
    assert all(result["metrics"][name]["unit"] == "s" for name in FIVE)
    assert all(v >= 0.0 for v in values.values())
    assert values["setup_import_s"] > 0.0
    assert values["setup_import_deps_s"] <= values["setup_import_s"]
    assert (values["setup_first_fit_import_s"]
            + values["setup_first_fit_build_s"]
            <= values["setup_first_fit_s"])
    out = io.StringIO()
    rc = run_cell.run("lr_fit_ref20", SEED, 0.3, False, require_tpu=False,
                      overrides=CUT, peaks=PEAKS, out=out, err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert not set(FIVE) & set(result["metrics"])
