"""What ``anova_fit_ref`` brought: the configuration against the vendored
source, the count at the cell's sizes, the plain ANOVA reference against
``scipy.stats.f_oneway`` and a row-by-row float64 computation, the control
and the planted faults through the comparison that decides ``correct`` (each
also in the program's place in the cell itself), the CPU rehearsal of the
cell in both ``--trace`` modes and on continuous entries, and the seven span
readers on a hand-made ring. The cell's ``per_layer`` entries are found by
name, never by place: a later PR's entries stand after them."""

import collections
import io
import json

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from scipy import stats as sstats

from benchmarks import run_cell
from benchmarks.harness import anova_spans, check, counts, device, generators
from benchmarks.harness import readers, spec
from benchmarks.harness.references import anova_oneway

CELL = "anova_fit_ref"
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4040
SEVEN = tuple(f"anova_span_{part}_ms" for part in anova_spans.PARTS) + (
    "anova_passes_per_fit",)
COLUMNS = ("indices", "fValues", "pValues", "degreesOfFreedom")
PARAMS = {"featuresCol": "features", "labelCol": "label",
          "featureType": "continuous", "labelType": "categorical"}


def sharded(devices):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("data",))
    return lambda ndim: NamedSharding(
        mesh, P("data", *([None] * (ndim - 1))))


# -- the configuration -------------------------------------------------------

def test_the_configuration_keeps_every_shape_of_the_source():
    """Against the vendored copy, key for key: only ``numValues`` differs,
    and the class names are upstream's."""
    cell = spec.load_cell(CELL)
    with open(spec.ROOT / cell.config["source_vendored"]) as f:
        (source,) = (v for k, v in json.load(f).items() if k != "version")
    assert cell.config["stage"] == source["stage"]
    assert cell.stage_params() == PARAMS     # nothing else set: top 50
    ours = dict(cell.config["inputData"]["paramMap"])
    theirs = dict(source["inputData"]["paramMap"])
    scaled = cell.config["scaled"]["numValues"]
    assert ours.pop("numValues") == scaled["here"] == 12_000_000
    assert theirs.pop("numValues") == scaled["source"] == 10_000_000
    assert ours == theirs
    assert (ours["vectorDim"], ours["labelArity"]) == (100, 10)
    assert "featureArity" not in ours        # the generator's default, 2
    for block, name in (("stage", "UnivariateFeatureSelector"),
                        ("inputData", "LabeledPointWithWeightGenerator")):
        ours_name = cell.config[block]["className"]
        assert ours_name.startswith("org.apache.flink.ml.")
        assert ours_name.rsplit(".", 1)[1] == name
    assert cell.config["traffic_may_override"] == []
    assert (cell.chips, cell.config["mesh"]) == (1, {"data": 1})


def test_the_benchmark_s_entries_are_found_by_name():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"] if c["name"] == "anova-selector-100"]
    assert entry["reduced"] == ["numValues"]
    assert entry["source"] == spec.load_cell(CELL).config["source"]
    assert len(entry["source"]) <= 200
    work, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        "anova-selector-100", "fit_rounds_published", 1)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {"anova_span_place_ms": "map-reduce",
              "anova_span_test_ms": "entry and stage API"}
    for name in SEVEN:
        metric = by_name[name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "fit_rows_per_s"
        assert metric["source"] == "program_span"
        assert metric["layer"] == layers.get(name,
                                             "grouped-moments programs")
    # the seven stand together, in this order, wherever the list has them
    order = [m["name"] for m in bench["per_layer"] if m["name"] in SEVEN]
    assert set(order) == set(SEVEN) and len(order) == 7
    listed = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(SEVEN) <= listed
    for other in bench["workloads"]:
        if other["name"] != CELL:
            assert not set(SEVEN) & {
                m["name"] for m in spec.load_cell(other["name"]).per_layer}


def test_the_limits_are_the_file_s_and_the_tie_is_the_p_limit():
    config = spec.load_cell(CELL).config
    limits = config["correct"]["limits"]
    assert set(limits) == {"f_gap", "p_gap", "dof_gap", "selected_gap"}
    assert limits["f_gap"] <= 1e-6 and limits["p_gap"] <= 1e-6
    assert limits["dof_gap"] == limits["selected_gap"] == 0.0
    assert anova_oneway.SELECT_TIE == limits["p_gap"]
    assert len(config["limits_why"]) > 200 and len(config["guarantees"]) >= 3


# -- the count ---------------------------------------------------------------

def test_one_read_of_12m_rows_by_hand():
    cell = spec.load_cell(CELL)
    c = counts.per_fit(cell.config["counts"], cell.stage_params(),
                       cell.config["inputData"]["paramMap"])
    # every row once: 100 float32 features and a label; an add for the sum,
    # a multiply and an add for the squares
    assert c["rows"] == 12_000_000
    assert c["bytes"] == 12_000_000 * 404 == 4_848_000_000
    assert c["flops"] == 12_000_000 * 300
    least = counts.least_seconds(c, device.peaks_for("TPU v5 lite"), 1)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(4.848e9 / 819e9)   # 5.9 ms


# -- the reference -----------------------------------------------------------

N, D = 6000, 7


def make_table(f_arity, l_arity, devices, n=N, d=D, seed=11):
    return generators.make_columns(
        "LabeledPointWithWeightGenerator",
        {"colNames": [["features", "label", "weight"]], "numValues": n,
         "vectorDim": d, "featureArity": f_arity, "labelArity": l_arity},
        seed, sharded(devices))


def by_rows(x, y):
    """The equations, class by class, in float64."""
    x, y = np.asarray(x, np.float64), np.asarray(y)
    classes = np.unique(y)
    grand = x.mean(axis=0)
    ssb = sum((y == c).sum() * (x[y == c].mean(axis=0) - grand) ** 2
              for c in classes)
    ssw = sum(((x[y == c] - x[y == c].mean(axis=0)) ** 2).sum(axis=0)
              for c in classes)
    dfb, dfw = len(classes) - 1, len(x) - len(classes)
    f = (ssb / dfb) / (ssw / dfw)
    return f, sstats.f.sf(f, dfb, dfw), dfw


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 500 rows: three a device of four, so that the reference's
    sum over blocks, and the fault that leaves every second block out, are
    run."""
    monkeypatch.setattr(anova_oneway, "BLOCK_ROWS", 500)


@pytest.mark.parametrize("tasks", [1, 4])
@pytest.mark.parametrize("arities", [(2, 10), (0, 10), (0, 3), (2, 2)])
def test_reference_is_the_f_test_as_stated(arities, tasks, small_blocks):
    columns = make_table(*arities, tasks)
    got = anova_oneway.run(columns, dict(PARAMS, selectionThreshold=3),
                           tasks)
    x, y = np.asarray(columns["features"]), np.asarray(columns["label"])
    f, p, dfw = by_rows(x, y)
    assert got["_n"] == N and got["_counts"].sum() == N
    np.testing.assert_allclose(got["fValues"], f, rtol=1e-11)
    np.testing.assert_allclose(got["pValues"], p, rtol=1e-9, atol=1e-13)
    assert np.all(got["degreesOfFreedom"] == dfw)
    np.testing.assert_array_equal(
        got["indices"], np.sort(np.argsort(p, kind="stable")[:3]))
    for j in range(D):
        want = sstats.f_oneway(*[x[y == c, j].astype(np.float64)
                                 for c in np.unique(y)])
        assert got["fValues"][j] == pytest.approx(want[0], rel=1e-9)
        assert got["pValues"][j] == pytest.approx(want[1], rel=1e-8,
                                                  abs=1e-13)


def test_a_table_off_the_grid_is_refused_not_approximated():
    y = jax.numpy.zeros(10)
    for bad, match in ((jax.numpy.full((10, 2), 2.0), "grid"),
                       (jax.numpy.full((10, 2), 2.0 ** -24), "grid"),
                       (jax.numpy.full((10, 2), -1.0), "grid")):
        with pytest.raises(NotImplementedError, match=match):
            anova_oneway.run({"features": bad, "label": y}, PARAMS, 1)
    with pytest.raises(NotImplementedError, match="whole numbers"):
        anova_oneway.run({"features": jax.numpy.zeros((10, 2)),
                          "label": jax.numpy.full((10,), 0.5)}, PARAMS, 1)
    with pytest.raises(NotImplementedError, match="continuous"):
        anova_oneway.run({"features": jax.numpy.zeros((10, 2)), "label": y},
                         dict(PARAMS, featureType="categorical"), 1)


VARIANTS = [{"precision": "bfloat16"}] + [
    {"fault": fault} for fault in anova_oneway.FAULTS]


@pytest.mark.parametrize("f_arity", [2, 0], ids=["zeros-and-ones",
                                                 "continuous"])
@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_control_and_faults_are_not_correct(variant, f_arity, small_blocks):
    """Each put in the program's place as the window's one answer and taken
    through ``check.decide`` with the cell's own limits. One is no fault on
    a table of zeros and ones: a float32 accumulator holds every whole
    number under 2**24, so ``float32_chain`` reads 0 there (and is what the
    run on continuous entries is for)."""
    assert set(anova_oneway.FAULTS) == {
        "half_blocks", "one_row_short", "float32_chain",
        "labels_off_by_one_class"}
    limits = spec.load_cell(CELL).config["correct"]["limits"]
    table = make_table(f_arity, 10, 4, n=40_000)
    params = dict(PARAMS, selectionThreshold=3)
    reference = anova_oneway.run(table, params, 4)
    other = anova_oneway.run(table, params, 4, **variant)
    answer = {k: v for k, v in other.items() if not k.startswith("_")}
    correct, compared = check.decide([answer], anova_oneway, reference,
                                     limits)
    if variant == {"fault": "float32_chain"} and f_arity == 2:
        assert correct is True and compared["f_gap"]["value"] < 1e-12
        return
    assert correct is False
    assert any(c["value"] > 10 * c["limit"] for c in compared.values())
    same, _ = check.decide(
        [{k: v for k, v in reference.items() if not k.startswith("_")}],
        anova_oneway, reference, limits)
    assert same is True


def test_compare_of_a_missing_statistic_a_nan_or_another_selection():
    p = np.asarray([0.5, 0.01, 0.2, 0.2 + 5e-9, 0.9, np.nan])
    ref = {"indices": np.asarray([1.0, 2.0]), "fValues": np.arange(6.0),
           "pValues": p, "degreesOfFreedom": np.full(6, 90)}
    good = {k: np.asarray(v) for k, v in ref.items()}
    assert set(anova_oneway.compare(good, ref).values()) == {0.0}
    inf = float("inf")
    # the parent's model: indices alone
    assert anova_oneway.compare({"indices": ref["indices"]}, ref) == {
        "f_gap": inf, "p_gap": inf, "dof_gap": inf, "selected_gap": inf}
    # a swap among reference p-values within the tie of the last selected
    swapped = dict(good, indices=np.asarray([1.0, 3.0]))
    assert anova_oneway.compare(swapped, ref)["selected_gap"] == 0.0
    other = dict(good, indices=np.asarray([1.0, 0.0]))
    assert anova_oneway.compare(other, ref)["selected_gap"] == inf
    fewer = dict(good, indices=np.asarray([1.0]))
    assert anova_oneway.compare(fewer, ref)["selected_gap"] == inf
    # a NaN on one side only, another size, other degrees of freedom
    assert anova_oneway.compare(
        dict(good, pValues=np.where(p == 0.5, np.nan, p)), ref)[
            "p_gap"] == inf
    assert anova_oneway.compare(
        dict(good, fValues=np.arange(5.0)), ref)["f_gap"] == inf
    assert anova_oneway.compare(
        dict(good, degreesOfFreedom=np.full(6, 89)), ref)["dof_gap"] == inf
    moved = anova_oneway.compare(
        dict(good, fValues=np.arange(6.0) * (1 + 1e-3)), ref)
    assert moved["f_gap"] == pytest.approx(1e-3) and moved["p_gap"] == 0.0


# -- the cell, rehearsed on the CPU ------------------------------------------

def drive(trace, seconds, system=None, **data):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(CELL, SEED, seconds, trace, require_tpu=False,
                      overrides={"inputData": dict(
                          {"numValues": 40_000, "vectorDim": 60}, **data),
                          "traffic": {"trace_capture_s": 1.0}},
                      peaks=PEAKS, system=system, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[0]), json.loads(lines[-1]), err.getvalue()


@pytest.mark.parametrize("f_arity", [2, 0], ids=["zeros-and-ones",
                                                 "continuous"])
def test_rehearsal_end_to_end(f_arity):
    rc, info, result, err = drive(False, 0.3, featureArity=f_arity)
    assert rc == 0 and result["correct"] is True, err
    cell = spec.load_cell(CELL)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "fit_rows_per_s", "setup_s"}
    assert info["execution_paths"] == ["grouped-moments"]
    assert info["rows_per_fit"] == 40_000
    assert info["window_compiles"]["requests"] == 0
    assert set(result["compared"]) == {"f_gap", "p_gap", "dof_gap",
                                       "selected_gap",
                                       "window_backend_compiles"}
    for name, c in result["compared"].items():
        assert c["value"] <= c["limit"], name
    # (continuous: the float32 squares' rounding over the root of the rows)
    assert result["compared"]["f_gap"]["value"] < (1e-12 if f_arity else
                                                   1e-8)


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
        other = anova_oneway.run(self._columns, PARAMS, 1, **self._variant)
        return {k: v for k, v in other.items()
                if not k.startswith("_")}, path


@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_the_cell_says_not_correct_of_the_control_and_each_fault(
        variant, monkeypatch):
    """On continuous entries, where a float32 chain is a fault too. Four
    blocks, so that half of them is two."""
    monkeypatch.setattr(anova_oneway, "BLOCK_ROWS", 10_000)
    rc, _, result, err = drive(False, 0.1, system=Planted(variant),
                               featureArity=0)
    assert rc == 0 and result["correct"] is False, err
    assert any(c["value"] > c["limit"]
               for c in result["compared"].values())


def test_the_parent_s_model_is_not_correct_in_the_cell():
    """A model that hands out its indices alone (the parent's) cannot show
    what it selected by: every number reads infinite."""
    from benchmarks.harness import system

    class IndicesAlone:
        def __getattr__(self, name):
            return getattr(system, name)

        def model_to_host(self, stage, model):
            answer, path = system.model_to_host(stage, model)
            return {"indices": answer["indices"]}, path

    rc, _, result, err = drive(False, 0.1, system=IndicesAlone())
    assert rc == 0 and result["correct"] is False, err
    assert result["compared"]["selected_gap"]["value"] == 1e300


def test_traced_rehearsal_reads_the_seven():
    """No TPU plane on the CPU, so the device trace's readers leave their
    metrics out; everything else the cell lists is in the line, and the
    parts of an SGD, a Lloyd, a counting or a selection fit are not the
    cell's."""
    rc, _, result, err = drive(True, 2.0)
    assert rc == 0 and result["correct"] is True, err
    listed = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(SEVEN) <= listed
    assert not {n for n in listed if n.startswith(
        ("fit_span_", "lloyd_span_", "nb_span_", "select_"))}
    assert listed >= {"fit_device_roofline", "fit_mfu", "fit_host_gap_ms",
                      "programs_per_fit", "window_compiles",
                      "device_idle_pct", "setup_compile_s",
                      "setup_datagen_s", "setup_first_fit_s"}
    from_the_device = {"fit_device_roofline", "fit_host_gap_ms",
                       "programs_per_fit", "device_idle_pct"}
    assert set(result["metrics"]) == listed - from_the_device
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert all(result["metrics"][name]["value"] >= 0 for name in SEVEN)
    assert result["metrics"]["anova_span_fetch_ms"]["value"] > 0
    assert result["metrics"]["anova_passes_per_fit"]["value"] == 1


# -- the seven readers -------------------------------------------------------

def span(trace, sid, parent, name, dur_us, **attrs):
    return {"type": "span", "trace": trace, "id": sid, "parent": parent,
            "name": name, "ts_us": 0, "dur_us": dur_us, "attrs": attrs}


def anova_fit(k, root_us=15_000, again=False):
    """One selector fit's records in the ring's order: children first."""
    t = f"t{k}"
    out = [span(t, f"{k}-h", f"{k}-p", "collective.host", 40),
           span(t, f"{k}-p", f"{k}-r", "anova.place_inputs", 120),
           span(t, f"{k}-c", f"{k}-r", "anova.check", 300, rows=4096),
           span(t, f"{k}-b", f"{k}-r", "anova.build_program", 20),
           span(t, f"{k}-l", f"{k}-r", "anova.launch", 200,
                path="grouped-moments"),
           span(t, f"{k}-f", f"{k}-r", "anova.fetch", 11_000, passes=1)]
    if again:
        out += [span(t, f"{k}-l2", f"{k}-r", "anova.launch", 200),
                span(t, f"{k}-f2", f"{k}-r", "anova.fetch", 11_000,
                     passes=1)]
    out += [span(t, f"{k}-t", f"{k}-r", "anova.test", 400),
            span(t, f"{k}-s", f"{k}-r", "anova.test", 100),
            span(t, f"{k}-m", f"{k}-r", "fit.model", 100),
            span(t, f"{k}-r", None, "UnivariateFeatureSelector.fit",
                 root_us, kind="fit")]
    return out


def lloyd_fit(k):
    t = f"s{k}"
    return [span(t, f"s{k}-o", f"s{k}-r", "lloyd.fetch", 700),
            span(t, f"s{k}-r", None, "KMeans.fit", 900, kind="fit")]


@pytest.mark.parametrize("again", [False, True])
def test_the_six_parts_sum_to_the_root(again):
    root = 30_000 if again else 15_000
    fit = anova_fit(0, root, again)
    parts = anova_spans.split_us(fit)
    assert tuple(parts) == anova_spans.PARTS
    assert sum(parts.values()) == root
    times = 2 if again else 1
    assert parts["place"] == 120 and parts["check"] == 300
    assert parts["test"] == 500             # F and p, then the selection
    assert parts["launch"] == 200 * times
    assert parts["fetch"] == 11_000 * times
    assert anova_spans.passes_of(fit) == times


def test_readers_give_medians_or_nothing(monkeypatch):
    def ring(records):
        monkeypatch.setattr(anova_spans.program_spans, "ring",
                            lambda: collections.deque(records))

    def read_all():
        return {name: readers.load(
            spec.layer_metric_file(name)["reader"])({}) for name in SEVEN}

    few = anova_spans.MIN_FITS - 1
    ring([])                                      # a --trace 0 run
    assert set(read_all().values()) == {None}
    ring([r for k in range(few) for r in anova_fit(k)])   # too few fits
    assert set(read_all().values()) == {None}
    # a program without these spans (the parent): nothing, no error
    ring([r for k in range(40) for r in lloyd_fit(k)])
    assert set(read_all().values()) == {None}
    roots = [14_000, 15_000, 15_000, 16_000, 19_000] * 4 + [15_000]
    ring([r for k, us in enumerate(roots) for r in anova_fit(k, us)]
         + lloyd_fit(0) + anova_fit(99)[:-1])     # + a fit still open
    assert read_all() == {
        "anova_span_place_ms": 0.12, "anova_span_check_ms": 0.3,
        "anova_span_launch_ms": 0.2, "anova_span_fetch_ms": 11.0,
        "anova_span_test_ms": 0.5,
        "anova_span_other_ms": pytest.approx(2.88),
        "anova_passes_per_fit": 1}
