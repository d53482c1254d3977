"""The readers of the program's own spans: the split of a fit on a hand-made
ring, the raw-trace reading of ``tools/program_gaps.py`` on a hand-made
trace, and the CPU rehearsal of both cells with the seven metrics in the
line."""

import collections
import io
import json

import pytest

from benchmarks import run_cell
from benchmarks.harness import program_spans, spec
from benchmarks.tools import program_gaps

PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
CUT = {"inputData": {"numValues": 48000}, "stage": {"globalBatchSize": 400}}
CUTS = {"lr_fit_ref20": CUT, "lr_fit_ref20_dp4": CUT}
SEED = 2**31 + 2626
SEVEN = tuple(f"fit_span_{part}_ms" for part in program_spans.PARTS)


def span(trace, sid, parent, name, dur_us, **attrs):
    return {"type": "span", "trace": trace, "id": sid, "parent": parent,
            "name": name, "ts_us": 0, "dur_us": dur_us, "attrs": attrs}


def fit(k, root_us=1000, launches=1):
    """One fit's records in the ring's order: children before parents."""
    t = f"t{k}"
    out = [span(t, f"{k}-e", f"{k}-r", "fit.extract", 30),
           span(t, f"{k}-h1", f"{k}-p", "collective.host", 40, op="x"),
           span(t, f"{k}-h2", f"{k}-p", "collective.host", 50, op="y"),
           span(t, f"{k}-p", f"{k}-o", "sgd.place_inputs", 120),
           span(t, f"{k}-c", f"{k}-o", "sgd.init_carry", 200),
           span(t, f"{k}-b", f"{k}-o", "sgd.build_program", 300)]
    for i in range(launches):
        out += [span(t, f"{k}-l{i}", f"{k}-o", "sgd.launch", 60),
                span(t, f"{k}-f{i}", f"{k}-o", "sgd.fetch", 70)]
    out += [span(t, f"{k}-g", f"{k}-o", "sgd.health", 10),
            span(t, f"{k}-o", f"{k}-r", "sgd.optimize",
                 640 + 130 * launches),
            span(t, f"{k}-m", f"{k}-r", "fit.model", 20),
            span(t, f"{k}-r", None, "LogisticRegression.fit", root_us,
                 kind="fit")]
    return out


def ring_of(fits, maxlen=None):
    return collections.deque((r for f in fits for r in f), maxlen=maxlen)


def test_self_time_leaves_out_what_the_children_cover():
    whole, = program_spans.whole_fits(ring_of([fit(0)]))
    self_us = {s["id"]: s["self_us"] for s in whole}
    assert self_us["0-p"] == 120 - 40 - 50
    assert self_us["0-o"] == 770 - (120 + 200 + 300 + 60 + 70 + 10)
    assert self_us["0-r"] == 1000 - 30 - 770 - 20
    assert self_us["0-c"] == 200


@pytest.mark.parametrize("launches", [1, 3])
def test_the_seven_parts_sum_to_the_root(launches):
    whole, = program_spans.whole_fits(ring_of([fit(0, launches=launches)]))
    parts = program_spans.split_us(whole)
    assert set(parts) == set(program_spans.PARTS)
    assert sum(parts.values()) == 1000
    assert parts["launch"] == 60 * launches
    assert parts["fetch"] == 70 * launches
    assert parts["seam"] == 1000 - (640 + 130 * launches)
    assert parts["other"] == 20   # the health guard and what no span names


def test_a_fit_with_an_evicted_child_is_left_out():
    records = [r for k in range(12) for r in fit(k)]
    full = collections.deque(records, maxlen=len(records) - 1)
    assert full[0]["trace"] == "t0"   # its first child went
    kept = program_spans.whole_fits(full)
    assert len(kept) == 11
    assert all(s["trace"] != "t0" for f in kept for s in f)
    # a ring with room has evicted nothing: the oldest fit counts
    assert len(program_spans.whole_fits(ring_of(
        [fit(k) for k in range(12)], maxlen=10_000))) == 12


def test_orphans_open_fits_and_other_roots_are_left_out():
    open_fit = fit(1)[:-1]                      # the root has not closed
    orphan = [r for r in fit(2) if r["id"] != "2-o"]   # a parent is gone
    transform = [span("t3", "3-r", None, "X.transform", 10,
                      kind="transform")]
    assert program_spans.whole_fits(
        ring_of([open_fit, orphan, transform])) == []


def test_fewer_than_ten_whole_fits_is_no_reading():
    nine = ring_of([fit(k) for k in range(9)])
    assert program_spans.medians_ms(nine) is None
    ten = ring_of([fit(k, root_us=1000 + k) for k in range(10)])
    found = program_spans.medians_ms(ten)
    assert found["fits"] == 10
    assert found["root"] == pytest.approx(1.0045)
    assert found["carry"] == 0.2 and found["build"] == 0.3


def test_an_empty_ring_reads_none_through_every_reader(monkeypatch):
    from benchmarks.harness import readers

    monkeypatch.setattr(program_spans, "ring", lambda: ())
    for name in SEVEN:
        assert readers.load(name)({}) is None


# -- tools/program_gaps.py: the raw trace ------------------------------------

def test_innermost_cuts_nested_spans_into_disjoint_pieces():
    spans = [("bench.fit", 0, 100), ("LR.fit", 10, 90),
             ("sgd.optimize", 20, 80), ("sgd.launch", 30, 40),
             ("sgd.fetch", 50, 70), ("bench.model_data", 100, 110)]
    pieces = program_gaps.innermost(spans)
    assert pieces == [
        (0, 10, "bench.fit"), (10, 20, "LR.fit"), (20, 30, "sgd.optimize"),
        (30, 40, "sgd.launch"), (40, 50, "sgd.optimize"),
        (50, 70, "sgd.fetch"), (70, 80, "sgd.optimize"), (80, 90, "LR.fit"),
        (90, 100, "bench.fit"), (100, 110, "bench.model_data")]
    assert program_gaps.attribute([(35, 60), (105, 120)], pieces) == {
        "sgd.launch": 5, "sgd.optimize": 10, "sgd.fetch": 10,
        "bench.model_data": 5, "outside-spans": 10}


def _raw_trace():
    """Three fits of 1000 ns: the segment program runs 100 ns inside the
    launch-to-fetch stretch of each, a small program before it."""
    host, ops, modules = [], [], []
    for k in range(3):
        t = 1000 * k
        host += [["bench.fit", t, 900], ["LogisticRegression.fit", t + 10, 880],
                 ["sgd.optimize", t + 20, 800], ["sgd.launch", t + 300, 100],
                 ["sgd.fetch", t + 400, 300], ["bench.model_data", t + 900, 50],
                 ["bench.gap", t + 950, 50]]
        modules += [["jit_ones_rows(7)", t + 100, 20],
                    ["jit_sgd_segment(9)", t + 450, 100]]
        ops += [["%iota.1 = f32[8]", t + 100, 20],
                ["%while.2 = (f32[8])", t + 450, 100],
                ["%fusion.3 = f32[8]", t + 460, 80]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]}]}


def test_raw_reduction_names_the_innermost_span_of_every_gap():
    got = program_gaps.reduce_raw(_raw_trace())
    assert got["fits"] == 2 and got["busiest_device"] == 0
    assert got["bench_fit_median_ms"] == pytest.approx(900e-6)
    idle = got["idle_ms_per_fit_by_innermost_span"]
    busy = got["busy_ms_per_fit_by_innermost_span"]
    assert busy == {"sgd.fetch": pytest.approx(100e-6),
                    "sgd.optimize": pytest.approx(20e-6)}
    assert idle["sgd.fetch"] == pytest.approx(200e-6)
    assert idle["sgd.launch"] == pytest.approx(100e-6)
    assert idle["sgd.optimize"] == pytest.approx(380e-6)
    assert sum(idle.values()) + sum(busy.values()) == pytest.approx(1000e-6)
    assert got["modules_per_fit"]["jit_sgd_segment"][0] == 1
    assert got["segment_runs_inside_launch_to_fetch"] == 2
    assert got["segment_runs_outside"] == 0
    assert got["program_roots_inside_bench_fit"] == 3


def test_a_segment_that_runs_after_its_fetch_is_counted_outside():
    trace = _raw_trace()
    for ev in trace["planes"][1]["lines"][0]["events"]:
        if ev[0].startswith("%fusion") and ev[1] < 1000:
            ev[2] = 400          # runs on past the end of sgd.fetch
    got = program_gaps.reduce_raw(trace)
    assert got["segment_runs_outside"] == 1


# -- the rehearsal: both cells print the seven ----------------------------------

@pytest.fixture
def compile_cache(tmp_path_factory):
    """As on the chip, the persistent cache answers the one small compile
    the while-loop form asks for in every fit."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    cc.set_cache_dir(str(tmp_path_factory.getbasetemp() / "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    cc.reset_cache()
    if before:
        cc.set_cache_dir(before)


def drive(cell, trace, seconds):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(cell, SEED, seconds, trace, require_tpu=False,
                      overrides=CUTS[cell], peaks=PEAKS, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(CUTS))
def test_traced_rehearsal_prints_the_seven_and_they_sum_to_the_root(
        cell, compile_cache):
    ring = program_spans.ring()
    ring.clear()
    result = drive(cell, True, 1.5)
    listed = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert set(SEVEN) <= listed
    values = {name: result["metrics"][name]["value"] for name in SEVEN}
    assert all(result["metrics"][name]["unit"] == "ms" for name in SEVEN)
    # per fit the seven are the root exactly; their medians, on a shared
    # CPU, come within a few per cent of the root's median
    fits = program_spans.whole_fits(ring)
    assert len(fits) >= program_spans.MIN_FITS
    for one in fits:
        root = next(s for s in one if s["parent"] is None)
        assert sum(program_spans.split_us(one).values()) == root["dur_us"]
    found = program_spans.medians_ms(ring)
    assert values == {f"fit_span_{p}_ms": found[p]
                      for p in program_spans.PARTS}
    assert sum(values.values()) == pytest.approx(found["root"], rel=0.05)
    assert all(v >= 0 for v in values.values())
    # the metrics that time the same fits from outside are all still there
    assert {"window_compiles", "fit_mfu", "setup_compile_s",
            "setup_datagen_s"} <= set(result["metrics"])


def test_untraced_rehearsal_leaves_the_ring_empty(compile_cache):
    ring = program_spans.ring()
    ring.clear()
    result = drive("lr_fit_ref20", False, 0.3)
    assert len(ring) == 0
    assert not set(SEVEN) & set(result["metrics"])
