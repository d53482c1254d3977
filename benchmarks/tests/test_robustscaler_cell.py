"""What ``robustscaler_fit_ref`` brought: the configuration against the
vendored source, the count at the cell's sizes, the plain sorting reference
against ``np.partition``, the control and the planted faults through the
comparison that decides ``correct`` (each also in the program's place in the
cell itself), the CPU rehearsal of the cell in both ``--trace`` modes, and
the five span readers on a hand-made ring."""

import collections
import io
import json

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import run_cell
from benchmarks.harness import check, counts, device, generators
from benchmarks.harness import readers, select_spans, spec
from benchmarks.harness.references import order_stats_sort

CELL = "robustscaler_fit_ref"
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4321
FOUR = tuple(f"select_span_{part}_ms" for part in select_spans.PARTS)
FIVE = FOUR + ("select_passes_per_fit",)


def sharded(devices):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("data",))
    return lambda ndim: NamedSharding(
        mesh, P("data", *([None] * (ndim - 1))))


# -- the configuration and the generator -------------------------------------

def test_the_configuration_keeps_every_shape_of_the_source():
    """Against the vendored copy, key for key: only ``numValues`` differs,
    and the class names are upstream's."""
    cell = spec.load_cell(CELL)
    with open(spec.ROOT / cell.config["source_vendored"]) as f:
        source = json.load(f)["robustscaler10000000"]
    assert cell.config["stage"] == source["stage"]
    assert cell.stage_params() == {"withCentering": True,
                                   "withScaling": True}
    ours = dict(cell.config["inputData"]["paramMap"])
    theirs = dict(source["inputData"]["paramMap"])
    scaled = cell.config["scaled"]["numValues"]
    assert ours.pop("numValues") == scaled["here"] == 12_000_000
    assert theirs.pop("numValues") == scaled["source"] == 10_000_000
    assert ours == theirs
    assert ours["vectorDim"] == 100 and ours["colNames"] == [["input"]]
    for block, name in (("stage", "RobustScaler"),
                        ("inputData", "DenseVectorGenerator")):
        ours_name = cell.config[block]["className"]
        assert ours_name.startswith("org.apache.flink.ml.")
        assert ours_name.rsplit(".", 1)[1] == name
        assert source[block]["className"] == ours_name
    assert "stage_seed_param" not in cell.config    # the stage has no seed
    assert cell.config["traffic_may_override"] == []
    assert cell.chips == 1 and cell.config["mesh"] == {"data": 1}
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == ["numValues"]
    assert entry["source"] == cell.config["source"]
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work["traffic"] == "fit_rounds_published"


def test_the_program_resolves_the_upstream_class_names():
    from flink_ml_tpu.benchmark.datagen import resolve_generator
    from flink_ml_tpu.benchmark.runner import resolve_stage

    config = spec.load_cell(CELL).config
    assert resolve_stage(config["stage"]["className"]).__name__ == (
        "RobustScaler")
    assert resolve_generator(config["inputData"]["className"]).__name__ == (
        "DenseVectorGenerator")


def test_the_generator_makes_the_kmeans_cell_s_table_under_another_name():
    data = spec.load_cell(CELL).config["inputData"]
    params = dict(data["paramMap"], numValues=4096)
    one = generators.make_columns(data["className"], params, SEED, sharded(1))
    assert list(one) == ["input"]
    x = np.asarray(one["input"])
    assert x.shape == (4096, 100) and x.dtype == np.float32
    assert 0 <= x.min() and x.max() < 1
    # jax's uniform float32 draws are multiples of 2^-23: the lowest bit of
    # an element's key is clear, which the one_round_short fault sets
    assert not (x * 2**23 % 1).any()
    again = generators.make_columns(data["className"], params, SEED,
                                    sharded(4))
    np.testing.assert_array_equal(np.asarray(again["input"]), x)


# -- the count ---------------------------------------------------------------

def test_one_read_and_three_compares_an_element_by_hand():
    cell = spec.load_cell(CELL)
    c = counts.per_fit(cell.config["counts"], cell.stage_params(),
                       cell.config["inputData"]["paramMap"])
    assert c["rows"] == 12_000_000
    assert c["bytes"] == 12_000_000 * 400 == 4_800_000_000
    assert c["flops"] == 12_000_000 * 100 * 3
    least = counts.least_seconds(c, device.peaks_for("TPU v5 lite"), 1)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(4.8e9 / 819e9)     # 5.9 ms


# -- the reference -----------------------------------------------------------

N, D = 6000, 7
PARAMS = {"withCentering": True, "withScaling": True}


def numpy_scaler(x, lower=0.25, upper=0.75):
    """The element of 1-based rank ``ceil(q n)`` by ``np.partition``, and
    the differences, in float64."""
    x = np.asarray(x)
    n = len(x)
    at = [max(1, int(np.ceil(q * n))) - 1 for q in (lower, 0.5, upper)]
    lo, med, hi = (np.partition(x, k, axis=0)[k].astype(np.float64)
                   for k in at)
    return {"medians": med, "ranges": hi - lo}


def make_table(devices, n=N, d=D, seed=11):
    return generators.make_columns(
        "DenseVectorGenerator",
        {"colNames": [["input"]], "numValues": n, "vectorDim": d}, seed,
        sharded(devices))


@pytest.fixture(scope="module")
def table():
    return make_table(4)


@pytest.fixture
def narrow_blocks(monkeypatch):
    """Blocks of three columns: three sorts and a last block that overlaps
    the one before it."""
    monkeypatch.setattr(order_stats_sort, "BLOCK_BYTES", 3 * 4 * N)


@pytest.mark.parametrize("tasks", [1, 4])
@pytest.mark.parametrize("quartiles", [(0.25, 0.75), (0.1, 0.9)])
def test_reference_is_the_sorted_column_at_its_ranks(quartiles, tasks,
                                                     narrow_blocks):
    columns = make_table(tasks)
    params = dict(PARAMS, lower=quartiles[0], upper=quartiles[1])
    got = order_stats_sort.run(columns, params, tasks)
    want = numpy_scaler(columns["input"], *quartiles)
    for name in ("medians", "ranges"):
        assert got[name].dtype == np.float64 and got[name].shape == (D,)
        np.testing.assert_array_equal(got[name], want[name])
    assert order_stats_sort.compare(
        {k: v[None] for k, v in want.items()}, got) == {
            "median_gap": 0.0, "range_gap": 0.0}


def test_the_two_rank_rules_agree_at_the_cell_s_size_and_not_everywhere():
    """Upstream's ``ceil(q n)``, 1-based, and the program's ``floor(q (n -
    1))``, 0-based, name the same elements at 12M rows (and wherever ``q
    n`` is whole); at 6 rows and the upper quartile they do not, which is
    why the rehearsals keep ``numValues`` a multiple of four."""
    def program(n, q):
        return int(np.floor(q * (n - 1)))

    for n in (12_000_000, 20_000, N):
        assert order_stats_sort.ranks_of((0.25, 0.5, 0.75), n) == tuple(
            program(n, q) for q in (0.25, 0.5, 0.75))
    assert order_stats_sort.ranks_of((0.75,), 6) == (4,) != (program(6, .75),)
    assert order_stats_sort.ranks_of((0.0, 1.0), 10) == (0, 9)


VARIANTS = [{"precision": "bfloat16"}, {"fault": "state_unchanged"},
            {"fault": "half_blocks"}, {"fault": "one_round_short"}]


@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_control_and_faults_are_not_correct(table, variant):
    """Each put in the program's place as the window's one answer and taken
    through ``check.decide`` with the cell's own limits."""
    assert set(order_stats_sort.FAULTS) == {
        "state_unchanged", "half_blocks", "one_round_short"}
    limits = spec.load_cell(CELL).config["correct"]["limits"]
    reference = order_stats_sort.run(table, PARAMS, 4)
    other = order_stats_sort.run(table, PARAMS, 4, **variant)
    correct, compared = check.decide([other], order_stats_sort, reference,
                                     limits)
    assert correct is False
    assert all(c["value"] > 10 * c["limit"] for c in compared.values())
    same, _ = check.decide([reference], order_stats_sort, reference, limits)
    assert same is True


def test_one_round_short_is_one_float32_step_up():
    """For the table's values (not negative, an even mantissa: multiples of
    2^-23) the lowest bit of the key is the float's own."""
    values = np.array([0.25, 0.5 - 2.0**-23, 0.5, 0.75, 0.0], np.float32)
    up = order_stats_sort._one_step_up(values)
    np.testing.assert_array_equal(
        up, np.nextafter(values, np.float32(np.inf)))
    # an odd key stays (a negative float of even mantissa has one): the
    # bit is set, never carried
    np.testing.assert_array_equal(order_stats_sort._one_step_up(up), up)
    assert order_stats_sort._one_step_up(np.float32([-0.5]))[0] == -0.5
    assert float(up[0]) - 0.25 == 2.0**-25 and float(up[2]) - 0.5 == 2.0**-24


def test_compare_of_a_wrong_shape_a_nan_or_a_missing_column_is_infinite():
    ref = {"medians": np.arange(3.0), "ranges": np.ones(3)}
    good = {k: v[None] for k, v in ref.items()}      # one row a column
    assert order_stats_sort.compare(good, ref) == {
        "median_gap": 0.0, "range_gap": 0.0}
    inf = float("inf")
    assert order_stats_sort.compare({}, ref) == {
        "median_gap": inf, "range_gap": inf}
    bad = dict(good, medians=np.zeros((1, 4)),
               ranges=np.array([[1.0, np.nan, 1.0]]))
    assert order_stats_sort.compare(bad, ref) == {
        "median_gap": inf, "range_gap": inf}
    off = dict(good, medians=good["medians"] + np.array([0, 0, 2.0**-24]))
    assert order_stats_sort.compare(off, ref) == {
        "median_gap": 2.0**-24, "range_gap": 0.0}


# -- the cell, rehearsed on the CPU ------------------------------------------

def drive(trace, seconds, system=None):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(CELL, SEED, seconds, trace, require_tpu=False,
                      overrides={"inputData": {"numValues": 20000},
                                 "traffic": {"trace_capture_s": 1.0}},
                      peaks=PEAKS, system=system, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[0]), json.loads(lines[-1]), err.getvalue()


def test_rehearsal_end_to_end():
    rc, info, result, err = drive(False, 0.3)
    assert rc == 0 and result["correct"] is True, err
    cell = spec.load_cell(CELL)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "fit_rows_per_s", "setup_s"}
    assert info["execution_paths"] == ["select-device"]
    assert info["rows_per_fit"] == 20000
    assert info["window_compiles"]["requests"] == 0
    assert result["compared"] == {
        "median_gap": {"value": 0.0, "limit": 1e-9},
        "range_gap": {"value": 0.0, "limit": 1e-9},
        "window_backend_compiles": {"value": 0, "limit": 0}}


class Planted:
    """The real system, but every model's data replaced by the reference's
    with a control or a fault planted: the cell has to say not correct."""

    def __init__(self, variant):
        from benchmarks.harness import system
        self._system, self._variant = system, variant
        self._columns = None

    def __getattr__(self, name):
        return getattr(self._system, name)

    def make_table(self, columns):
        self._columns = columns
        return self._system.make_table(columns)

    def model_to_host(self, stage, model):
        _, path = self._system.model_to_host(stage, model)
        other = order_stats_sort.run(self._columns, PARAMS, 1,
                                     **self._variant)
        return {k: v[None] for k, v in other.items()}, path


@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_the_cell_says_not_correct_of_the_control_and_each_fault(variant):
    rc, _, result, err = drive(False, 0.1, system=Planted(variant))
    assert rc == 0 and result["correct"] is False, err
    for name in ("median_gap", "range_gap"):
        assert (result["compared"][name]["value"]
                > 10 * result["compared"][name]["limit"])


def test_traced_rehearsal_reads_the_five():
    """No TPU plane on the CPU, so the device trace's readers leave their
    metrics out; everything else the cell lists is in the line, and the
    parts of an SGD, a Lloyd or a NaiveBayes fit are not the cell's."""
    rc, _, result, err = drive(True, 2.0)
    assert rc == 0 and result["correct"] is True, err
    listed = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(FIVE) <= listed
    assert not {n for n in listed
                if n.startswith(("fit_span_", "lloyd_span_", "nb_span_"))}
    assert listed >= {"fit_device_roofline", "fit_mfu", "fit_host_gap_ms",
                      "programs_per_fit", "window_compiles",
                      "device_idle_pct", "setup_compile_s",
                      "setup_datagen_s"}
    from_the_device = {"fit_device_roofline", "fit_host_gap_ms",
                       "programs_per_fit", "device_idle_pct"}
    assert set(result["metrics"]) == listed - from_the_device
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert all(result["metrics"][name]["value"] >= 0 for name in FOUR)
    assert result["metrics"]["select_span_fetch_ms"]["value"] > 0
    # 20,000 rows are their own sample: the one pass that proves it
    assert result["metrics"]["select_passes_per_fit"]["value"] == 1


def test_the_new_metrics_are_the_cell_s_alone_and_in_their_order():
    """Found by name, in their order among themselves: entries that later
    PRs append after them change nothing here."""
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    names = ["select_span_place_ms", "select_span_launch_ms",
             "select_span_fetch_ms", "select_span_other_ms",
             "select_passes_per_fit"]
    mine = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in mine] == names
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "fit_rows_per_s"
        assert m["source"] == "program_span"
    (work,) = (w for w in bench["workloads"] if w["name"] == CELL)
    assert work["config"] == "robustscaler-dense-100"
    assert "robustscaler-dense-100" in {c["name"] for c in bench["configs"]}


# -- the five readers --------------------------------------------------------

def span(trace, sid, parent, name, dur_us, **attrs):
    return {"type": "span", "trace": trace, "id": sid, "parent": parent,
            "name": name, "ts_us": 0, "dur_us": dur_us, "attrs": attrs}


def select_fit(k, root_us=60_000, passes=7, on="select.fetch"):
    """One RobustScaler fit's records in the ring's order: children first,
    a launch and a fetch a program (the head's four passes, then one a
    program). ``on``: the span that names the passes."""
    t = f"t{k}"
    made = [] if passes is None else [4] + [1] * (passes - 4)
    programs = max(1, len(made))
    records = [span(t, f"{k}-h", f"{k}-p", "collective.host", 40),
               span(t, f"{k}-p", f"{k}-r", "select.place_inputs", 120),
               span(t, f"{k}-b", f"{k}-r", "select.build_program", 20)]
    for i in range(programs):
        named = {on: {"passes": made[i]}} if made else {}
        records += [
            span(t, f"{k}-l{i}", f"{k}-r", "select.launch", 300 // programs,
                 **({"path": "select-device"} if i == 0 else {"ends": False}),
                 **named.get("select.launch", {})),
            span(t, f"{k}-f{i}", f"{k}-r", "select.fetch",
                 58_000 // programs, **named.get("select.fetch", {}))]
    return records + [
        span(t, f"{k}-m", f"{k}-r", "fit.model", 100),
        span(t, f"{k}-r", None, "RobustScaler.fit", root_us, kind="fit")]


def nb_fit(k):
    t = f"s{k}"
    return [span(t, f"s{k}-o", f"s{k}-r", "nb.fetch", 700),
            span(t, f"s{k}-r", None, "NaiveBayes.fit", 900, kind="fit")]


def test_the_four_parts_sum_to_the_root():
    parts = select_spans.split_us(select_fit(0, 61_000))
    assert tuple(parts) == select_spans.PARTS
    assert sum(parts.values()) == 61_000
    assert (parts["place"], parts["launch"], parts["fetch"]) == (
        120, 300, 58_000)
    assert parts["other"] == 61_000 - 58_420


def test_readers_give_medians_or_nothing(monkeypatch):
    def ring(records):
        monkeypatch.setattr(select_spans.program_spans, "ring",
                            lambda: collections.deque(records))

    def read_all():
        return {name: readers.load(
            spec.layer_metric_file(name)["reader"])({}) for name in FIVE}

    few = select_spans.MIN_FITS - 1
    ring([])                                      # a --trace 0 run
    assert set(read_all().values()) == {None}
    ring([r for k in range(few) for r in select_fit(k)])  # too few fits
    assert set(read_all().values()) == {None}
    # a program without these spans (the parent): nothing, no error
    ring([r for k in range(40) for r in nb_fit(k)])
    assert set(read_all().values()) == {None}
    roots = [59_000, 60_000, 60_000, 61_000, 70_000]
    passes = [6, 7, 7, 7, 8]
    ring([r for k, (us, p) in enumerate(zip(roots, passes))
          for r in select_fit(k, us, p)]
         + nb_fit(0) + select_fit(99)[:-1])       # + a fit still open
    assert read_all() == {
        "select_span_place_ms": 0.12, "select_span_launch_ms": 0.3,
        "select_span_fetch_ms": 58.0,
        "select_span_other_ms": pytest.approx(60 - 58.42),
        "select_passes_per_fit": 7}
    found = select_spans.medians_ms()
    assert found["fits"] == 5 and found["root"] == pytest.approx(60.0)
    # select.fetch is the one carrier: a count named anywhere else is not
    # read (and so never counted twice), and spans that name none (the
    # parent of a later form) leave that one metric out
    ring([r for k in range(6) for r in select_fit(k, passes=33,
                                                   on="select.launch")])
    assert read_all()["select_passes_per_fit"] is None
    ring([r for k in range(6) for r in select_fit(k, passes=None)])
    got = read_all()
    assert got["select_passes_per_fit"] is None
    assert got["select_span_fetch_ms"] == 58.0
