"""What ``nb_fit_ref`` brought: the generator at the configuration's
arities, the count at the cell's sizes, the plain contingency reference
against a row-by-row NumPy one, the control and the planted faults through
the comparison that decides ``correct`` (each also in the program's place in
the cell itself), the CPU rehearsal of the cell in both ``--trace`` modes,
and the six span readers on a hand-made ring."""

import collections
import io
import json

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import run_cell
from benchmarks.harness import check, counts, device, generators
from benchmarks.harness import nb_spans, readers, spec
from benchmarks.harness.references import contingency_nb

CELL = "nb_fit_ref"
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4321
SIX = tuple(f"nb_span_{part}_ms" for part in nb_spans.PARTS)
COLUMNS = ("theta", "values", "piArray", "labels", "floors")


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
        source = json.load(f)["NaiveBayes"]
    assert cell.config["stage"] == source["stage"]
    assert cell.stage_params() == {}        # every parameter its default
    ours = dict(cell.config["inputData"]["paramMap"])
    theirs = dict(source["inputData"]["paramMap"])
    scaled = cell.config["scaled"]["numValues"]
    assert ours.pop("numValues") == scaled["here"] == 12_000_000
    assert theirs.pop("numValues") == scaled["source"] == 2_000_000
    assert ours == theirs
    assert (ours["vectorDim"], ours["featureArity"],
            ours["labelArity"]) == (100, 20, 10)
    for block, name in (("stage", "NaiveBayes"),
                        ("inputData", "LabeledPointWithWeightGenerator")):
        theirs_name = source[block]["className"]
        ours_name = cell.config[block]["className"]
        assert ours_name.startswith("org.apache.flink.ml.")
        assert ours_name.rsplit(".", 1)[1] == theirs_name.rsplit(
            ".", 1)[1] == name
    assert cell.config["traffic_may_override"] == []
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == ["numValues"]
    assert entry["source"] == cell.config["source"]


def test_the_generator_makes_whole_numbers_under_the_arities():
    data = spec.load_cell(CELL).config["inputData"]
    params = dict(data["paramMap"], numValues=4096)
    one = generators.make_columns(data["className"], params, SEED, sharded(1))
    assert list(one) == ["features", "label", "weight"]
    x, y = np.asarray(one["features"]), np.asarray(one["label"])
    assert x.shape == (4096, 100) and x.dtype == np.float32
    assert set(np.unique(x)) == set(range(20))
    assert set(np.unique(y)) == set(range(10))
    again = generators.make_columns(data["className"], params, SEED,
                                    sharded(4))
    np.testing.assert_array_equal(np.asarray(again["features"]), x)


# -- the count ---------------------------------------------------------------

def test_one_pass_over_12m_rows_by_hand():
    cell = spec.load_cell(CELL)
    c = counts.per_fit(cell.config["counts"], cell.stage_params(),
                       cell.config["inputData"]["paramMap"])
    # every row once: 100 float32 features and a label; one increment a
    # value and one a label
    assert c["rows"] == 12_000_000
    assert c["bytes"] == 12_000_000 * 404 == 4_848_000_000
    assert c["flops"] == 12_000_000 * 101
    least = counts.least_seconds(c, device.peaks_for("TPU v5 lite"), 1)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(4.848e9 / 819e9)   # 5.9 ms


# -- the reference -----------------------------------------------------------

N, D = 3000, 7
PARAMS = {}


def numpy_nb(x, y, smoothing=1.0):
    """The equations, row by row, in float64."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    n, d = x.shape
    labels = np.unique(y)
    doc = np.array([(y == l).sum() for l in labels], np.float64)
    per_feature = [np.unique(x[:, j]) for j in range(d)]
    width = max(map(len, per_feature))
    values = np.full((d, width), np.nan)
    floors = np.zeros((len(labels), d))
    theta = np.zeros((len(labels), d, width))
    for j, vals in enumerate(per_feature):
        values[j, :len(vals)] = vals
        for li, l in enumerate(labels):
            denom = np.log(doc[li] + smoothing * len(vals))
            floors[li, j] = np.log(smoothing) - denom
            theta[li, j, :] = floors[li, j]
            for k, v in enumerate(vals):
                hits = np.sum((y == l) & (x[:, j] == v))
                theta[li, j, k] = np.log(hits + smoothing) - denom
    pi = np.log(doc * d + smoothing) - np.log(n * d + len(labels) * smoothing)
    return {"theta": theta, "values": values, "piArray": pi,
            "labels": labels, "floors": floors}


def make_table(f_arity, l_arity, devices, n=N, d=D):
    return generators.make_columns(
        "LabeledPointWithWeightGenerator",
        {"colNames": [["features", "label", "weight"]], "numValues": n,
         "vectorDim": d, "featureArity": f_arity, "labelArity": l_arity},
        11, sharded(devices))


@pytest.fixture(scope="module")
def table():
    """Two values and two labels: 750 rows a cell, past bfloat16's 256."""
    return make_table(2, 2, 4)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 250 rows: three a device, so that the reference's sum over
    blocks, and the fault that leaves every second block out, are run."""
    monkeypatch.setattr(contingency_nb, "BLOCK_ROWS", 250)


@pytest.mark.parametrize("tasks", [1, 4])
@pytest.mark.parametrize("arities", [(2, 2), (20, 10), (5, 3)])
def test_reference_is_naive_bayes_as_stated(arities, tasks, small_blocks):
    columns = make_table(*arities, tasks)
    got = contingency_nb.run(columns, PARAMS, tasks)
    want = numpy_nb(columns["features"], columns["label"])
    assert got["_n"] == N and got["_counts"].sum() == N * D
    for name in ("values", "labels"):
        np.testing.assert_array_equal(got[name], want[name])
    for name in ("theta", "piArray", "floors"):
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=1e-13, err_msg=name)
    assert contingency_nb.compare(
        {k: want[k] for k in COLUMNS}, got) == {
            "theta_gap": pytest.approx(0, abs=1e-13),
            "pi_gap": pytest.approx(0, abs=1e-13), "support_gap": 0.0}


def test_a_value_that_no_row_has_is_not_in_the_model():
    """Feature 0 never takes the value 1: its list is one shorter, padded
    with NaN, and theta holds the floor there."""
    x = np.floor(np.random.default_rng(0).random((600, 3)) * 3)
    x[x[:, 0] == 1, 0] = 2.0
    y = (x[:, 1] > 0).astype(np.float32)
    got = contingency_nb.run(
        {"features": jax.numpy.asarray(x, np.float32),
         "label": jax.numpy.asarray(y)}, PARAMS, 1)
    want = numpy_nb(x, y)
    np.testing.assert_array_equal(got["values"], want["values"])
    assert np.isnan(got["values"][0, 2])
    np.testing.assert_allclose(got["theta"], want["theta"], atol=1e-13)
    np.testing.assert_array_equal(got["theta"][:, 0, 2], got["floors"][:, 0])


def test_a_table_of_fractions_is_refused_not_approximated():
    x = jax.numpy.full((10, 2), 0.5)
    with pytest.raises(NotImplementedError, match="whole numbers"):
        contingency_nb.run({"features": x, "label": jax.numpy.zeros(10)},
                           PARAMS, 1)


VARIANTS = [{"precision": "bfloat16"}, {"fault": "state_unchanged"},
            {"fault": "half_blocks"}, {"fault": "one_row_short"}]


@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_control_and_faults_are_not_correct(table, variant, small_blocks):
    """Each put in the program's place as the window's one answer and taken
    through ``check.decide`` with the cell's own limits."""
    assert set(contingency_nb.FAULTS) == {
        "state_unchanged", "half_blocks", "one_row_short"}
    limits = spec.load_cell(CELL).config["correct"]["limits"]
    reference = contingency_nb.run(table, PARAMS, 4)
    other = contingency_nb.run(table, PARAMS, 4, **variant)
    answer = {k: v for k, v in other.items() if not k.startswith("_")}
    correct, compared = check.decide([answer], contingency_nb, reference,
                                     limits)
    assert correct is False
    assert any(c["value"] > 10 * c["limit"] for c in compared.values())
    same, _ = check.decide(
        [{k: v for k, v in reference.items() if not k.startswith("_")}],
        contingency_nb, reference, limits)
    assert same is True


def test_compare_of_a_wrong_shape_a_nan_or_another_support_is_infinite():
    ref = {"theta": np.zeros((2, 3, 4)), "floors": np.zeros((2, 3)),
           "piArray": np.zeros(2), "labels": np.arange(2.0),
           "values": np.tile(np.arange(4.0), (3, 1))}
    good = {k: v[None] for k, v in ref.items()}      # one row a column
    assert set(contingency_nb.compare(good, ref).values()) == {0.0}
    inf = float("inf")
    assert contingency_nb.compare({}, ref) == {
        "theta_gap": inf, "pi_gap": inf, "support_gap": inf}
    bad = dict(good, theta=np.zeros((1, 2, 3, 3)),
               piArray=np.full((1, 2), np.nan),
               labels=np.array([[0.0, 2.0]]))
    assert contingency_nb.compare(bad, ref) == {
        "theta_gap": inf, "pi_gap": inf, "support_gap": inf}
    padded = dict(ref, values=np.where(ref["values"] == 3, np.nan,
                                       ref["values"]))
    assert contingency_nb.compare(padded, padded)["support_gap"] == 0.0
    assert contingency_nb.compare(good, padded)["support_gap"] == inf
    low = dict(good, theta=np.full((1, 2, 3, 4), -np.inf))
    assert contingency_nb.compare(
        low, dict(ref, theta=low["theta"][0]))["theta_gap"] == 0.0


# -- the cell, rehearsed on the CPU ------------------------------------------

def drive(trace, seconds, system=None, **data):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(CELL, SEED, seconds, trace, require_tpu=False,
                      overrides={"inputData": dict(numValues=20000, **data),
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
    assert info["execution_paths"] == ["mxu-counts"]
    assert info["rows_per_fit"] == 20000
    assert info["window_compiles"]["requests"] == 0
    assert set(result["compared"]) == {"theta_gap", "pi_gap", "support_gap",
                                       "window_backend_compiles"}
    for name, c in result["compared"].items():
        assert c["value"] <= c["limit"], name
    assert result["compared"]["theta_gap"]["value"] < 1e-13


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
        other = contingency_nb.run(self._columns, {}, 1, **self._variant)
        return {k: v[None] for k, v in other.items()
                if not k.startswith("_")}, path


@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_the_cell_says_not_correct_of_the_control_and_each_fault(
        variant, monkeypatch):
    """At 20,000 rows, 20 values and 10 labels a count is some 100, which
    bfloat16 still holds whole: the control is planted on a table of two
    values and two labels, 5,000 rows a count, as the cell's own 60,000
    are past bfloat16's 256. Four blocks, so that half of them is two."""
    monkeypatch.setattr(contingency_nb, "BLOCK_ROWS", 5000)
    data = ({"featureArity": 2, "labelArity": 2}
            if "precision" in variant else {})
    rc, _, result, err = drive(False, 0.1, system=Planted(variant), **data)
    assert rc == 0 and result["correct"] is False, err
    assert any(c["value"] > c["limit"]
               for c in result["compared"].values())


def test_traced_rehearsal_reads_the_six_spans():
    """No TPU plane on the CPU, so the device trace's readers leave their
    metrics out; everything else the cell lists is in the line, and the
    parts of an SGD or a Lloyd fit are not the cell's."""
    rc, _, result, err = drive(True, 2.0)
    assert rc == 0 and result["correct"] is True, err
    listed = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(SIX) <= listed
    assert not {n for n in listed
                if n.startswith(("fit_span_", "lloyd_span_"))}
    assert listed >= {"fit_device_roofline", "fit_mfu", "fit_host_gap_ms",
                      "programs_per_fit", "window_compiles",
                      "device_idle_pct", "setup_compile_s",
                      "setup_datagen_s"}
    from_the_device = {"fit_device_roofline", "fit_host_gap_ms",
                       "programs_per_fit", "device_idle_pct"}
    assert set(result["metrics"]) == listed - from_the_device
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert all(result["metrics"][name]["value"] >= 0 for name in SIX)
    assert result["metrics"]["nb_span_fetch_ms"]["value"] > 0


# -- the six readers ---------------------------------------------------------

def span(trace, sid, parent, name, dur_us, **attrs):
    return {"type": "span", "trace": trace, "id": sid, "parent": parent,
            "name": name, "ts_us": 0, "dur_us": dur_us, "attrs": attrs}


def nb_fit(k, root_us=15_000, recount=False):
    """One NaiveBayes fit's records in the ring's order: children first."""
    t = f"t{k}"
    out = [span(t, f"{k}-h", f"{k}-p", "collective.host", 40),
           span(t, f"{k}-p", f"{k}-r", "nb.place_inputs", 120),
           span(t, f"{k}-c", f"{k}-r", "nb.check", 300, rows=4096),
           span(t, f"{k}-b", f"{k}-r", "nb.build_program", 20),
           span(t, f"{k}-l", f"{k}-r", "nb.launch", 200, path="mxu-counts"),
           span(t, f"{k}-f", f"{k}-r", "nb.fetch", 12_000)]
    if recount:
        out += [span(t, f"{k}-c2", f"{k}-r", "nb.check", 14_000),
                span(t, f"{k}-b2", f"{k}-r", "nb.build_program", 20),
                span(t, f"{k}-l2", f"{k}-r", "nb.launch", 200),
                span(t, f"{k}-f2", f"{k}-r", "nb.fetch", 12_000)]
    out += [span(t, f"{k}-z", f"{k}-r", "nb.finalize", 900),
            span(t, f"{k}-m", f"{k}-r", "fit.model", 100),
            span(t, f"{k}-r", None, "NaiveBayes.fit", root_us, kind="fit")]
    return out


def lloyd_fit(k):
    t = f"s{k}"
    return [span(t, f"s{k}-o", f"s{k}-r", "lloyd.fetch", 700),
            span(t, f"s{k}-r", None, "KMeans.fit", 900, kind="fit")]


@pytest.mark.parametrize("recount", [False, True])
def test_the_six_parts_sum_to_the_root(recount):
    root = 45_000 if recount else 15_000
    parts = nb_spans.split_us(nb_fit(0, root, recount))
    assert tuple(parts) == nb_spans.PARTS
    assert sum(parts.values()) == root
    times = 2 if recount else 1
    assert parts["place"] == 120 and parts["finalize"] == 900
    assert parts["launch"] == 200 * times
    assert parts["fetch"] == 12_000 * times
    assert parts["check"] == 300 + (14_000 if recount else 0)
    assert parts["other"] == root - sum(
        v for k, v in parts.items() if k != "other")


def test_readers_give_medians_or_nothing(monkeypatch):
    def ring(records):
        monkeypatch.setattr(nb_spans.program_spans, "ring",
                            lambda: collections.deque(records))

    def read_all():
        return {name: readers.load(
            spec.layer_metric_file(name)["reader"])({}) for name in SIX}

    few = nb_spans.MIN_FITS - 1
    ring([])                                      # a --trace 0 run
    assert set(read_all().values()) == {None}
    ring([r for k in range(few) for r in nb_fit(k)])     # too few fits
    assert set(read_all().values()) == {None}
    # a program without these spans (the parent): nothing, no error
    ring([r for k in range(40) for r in lloyd_fit(k)])
    assert set(read_all().values()) == {None}
    roots = [14_000, 15_000, 15_000, 16_000, 19_000] * 4 + [15_000]
    ring([r for k, us in enumerate(roots) for r in nb_fit(k, us)]
         + lloyd_fit(0) + nb_fit(99)[:-1])        # + a fit still open
    got = read_all()
    assert got == {"nb_span_place_ms": 0.12, "nb_span_check_ms": 0.3,
                   "nb_span_launch_ms": 0.2, "nb_span_fetch_ms": 12.0,
                   "nb_span_finalize_ms": 0.9,
                   "nb_span_other_ms": pytest.approx(15 - 13.52)}
    found = nb_spans.medians_ms()
    assert found["fits"] == 21 and found["root"] == pytest.approx(15.0)
