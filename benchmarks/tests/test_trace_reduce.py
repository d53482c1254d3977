"""The reduction from a trace to numbers: interval arithmetic, nested
operations, a hand-made two-device trace with a collective, the real trace
recorded on the chip (``fixtures/``), and the reading of an ``.xplane.pb``
that the profiler writes here."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import trace_reduce as tr

FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]
    assert tr.length([(0, 4), (5, 7)]) == 6
    assert tr.clip([(0, 4), (5, 7)], 3, 6) == [(3, 4), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]


def test_self_time_of_nested_operations():
    events = [["while.1", 0, 100], ["fusion.2", 10, 30],
              ["all-reduce.3", 50, 20], ["copy.4", 120, 10]]
    got = {name: (self_ns, leaf)
           for name, _, _, self_ns, leaf in tr.self_times(events)}
    assert got == {"while.1": (50, False), "fusion.2": (30, True),
                   "all-reduce.3": (20, True), "copy.4": (10, True)}


def test_op_name():
    assert tr.op_name("%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop") == \
        "fusion.3"
    assert tr.op_name("while.7") == "while.7"


def _hand_made():
    """Two devices, three fits of 100 us starting at 0, 200, 400 us; the
    window is the two whole cycles [0, 400) us. Device 0 is busy 60 us a
    cycle: a 40 us while holding a 10 us fusion and a 20 us all-reduce of
    which the fusion-overlapped part is none, then a 20 us copy. Device 1
    is busy 30 us a cycle."""
    us = 1000
    ops0, ops1, mods, spans = [], [], [], []
    for k in range(3):
        t = k * 200 * us
        spans += [["bench.fit", t, 100 * us],
                  ["bench.model_data", t + 100 * us, 20 * us],
                  ["bench.gap", t + 120 * us, 80 * us]]
        ops0 += [["%while.1 = while(...)", t + 10 * us, 40 * us],
                 ["%fusion.2 = fusion(...)", t + 12 * us, 10 * us],
                 ["%all-reduce.3 = all-reduce(...)", t + 25 * us, 20 * us],
                 ["%copy.4 = copy(...)", t + 130 * us, 20 * us]]
        ops1 += [["%fusion.2 = fusion(...)", t + 10 * us, 30 * us]]
        mods += [["jit_fit", t + 10 * us, 40 * us],
                 ["jit_copy", t + 130 * us, 20 * us]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": spans}]}]}


def test_hand_made_trace():
    r = tr.reduce(_hand_made())
    assert r["cycles"] == 2 and r["busiest_device"] == 0
    assert r["window_s"] == pytest.approx(400e-6)
    assert r["busy_s_busiest"] == pytest.approx(120e-6)
    assert r["busy_s_by_device"][1] == pytest.approx(60e-6)
    assert r["busy_s_mean"] == pytest.approx(90e-6)
    assert r["fit_s"] == pytest.approx([100e-6, 100e-6])
    assert r["fit_busy_s"] == pytest.approx([40e-6, 40e-6])
    assert r["programs"] == 4
    assert r["collective_s"] == pytest.approx(40e-6)
    assert r["collective_exposed_s"] == pytest.approx(40e-6)
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["all-reduce.3"] == pytest.approx(40e-6)
    assert ops["while.1"] == pytest.approx(20e-6)      # self time only
    idle = dict(map(tuple, r["idle_gaps"]))
    assert idle["bench.fit"] == pytest.approx(2 * 60e-6)
    assert idle["bench.gap"] == pytest.approx(2 * (10e-6 + 50e-6))
    assert idle["bench.model_data"] == pytest.approx(2 * 20e-6)
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s_busiest"])


def test_collective_covered_by_compute_is_not_exposed():
    trace = _hand_made()
    ops0 = trace["planes"][0]["lines"][0]["events"]
    for k in range(3):   # a second fusion over the first half of the reduce
        ops0.append(["%fusion.9 = fusion(...)", k * 200000 + 25000, 10000])
    assert tr.reduce(trace)["collective_exposed_s"] == pytest.approx(20e-6)


def test_too_little_to_read_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
    trace = _hand_made()
    trace["planes"][2]["lines"][0]["events"] = [["bench.fit", 0, 1000]]
    with pytest.raises(ValueError, match="whole fits"):
        tr.reduce(trace)


@pytest.mark.parametrize("path", sorted(FIXTURES.glob("*.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_trace_reduces_to_what_was_recorded_with_it(path):
    """A real trace of the chip, cut to a few fit cycles. The expected
    numbers were written by the same code on the day; this holds the
    reduction still while later PRs change the program."""
    expected = json.loads(
        path.with_name(path.name.replace(".json.gz", ".expected.json"))
        .read_text())
    got = tr.reduce(tr.load_json_gz(path))
    for key in ("cycles", "programs", "busiest_device"):
        assert got[key] == expected[key]
    for key in ("window_s", "busy_s_mean", "busy_s_busiest",
                "collective_s", "collective_exposed_s"):
        assert got[key] == pytest.approx(expected[key], rel=1e-9)
    assert got["fit_busy_s"] == pytest.approx(expected["fit_busy_s"])
    assert [n for n, _ in got["device_ops"]] == [
        n for n, _ in expected["device_ops"]]
    assert 0 < got["busy_s_busiest"] <= got["window_s"]
    assert sum(s for _, s in got["idle_gaps"]) <= got["window_s"]


def test_fixtures_are_there():
    assert len(sorted(FIXTURES.glob("*.json.gz"))) >= 2


def test_the_four_chip_trace_shows_its_collective():
    """The per-round reduction of the data-parallel fit is named ``psum.<n>``
    in the trace; the reader of ``collective_exposed_pct`` has to find it."""
    from benchmarks.harness.readers import collective_exposed_pct

    got = tr.reduce(tr.load_json_gz(
        FIXTURES / "fixture_lr_fit_ref20_dp4.json.gz"))
    assert got["devices"] == [0, 1, 2, 3]
    assert got["collective_s"] > 0
    assert any(name.startswith("psum") for name, _ in got["device_ops"])
    share = collective_exposed_pct.read({"trace": got})
    assert 0 < share < 100
    one_chip = tr.reduce(tr.load_json_gz(
        FIXTURES / "fixture_lr_fit_ref20.json.gz"))
    assert collective_exposed_pct.read({"trace": one_chip}) is None


def test_reads_the_profilers_own_file(tmp_path):
    """``load_xplane`` on a trace recorded here: the CPU has no TPU plane,
    so only the harness's spans survive the reading."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.fit"):
        jnp.ones(8).sum().block_until_ready()
    with jax.profiler.TraceAnnotation("other.span"):
        pass
    jax.profiler.stop_trace()
    plain = tr.load_xplane(tr.find_xplane(tmp_path))
    names = [s[0] for s in tr.host_spans(plain)]
    assert names == ["bench.fit"]
    assert tr.device_lines(plain, tr.OPS_LINE) == {}
    tr.dump_json_gz(plain, tmp_path / "t.json.gz")
    assert tr.load_json_gz(tmp_path / "t.json.gz") == plain
