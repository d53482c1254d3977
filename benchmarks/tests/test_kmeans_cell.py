"""What ``kmeans_fit_ref10`` brought: the generator against upstream's
semantics, the count at the cell's sizes, the plain Lloyd reference against
a float64 NumPy Lloyd, the control and the planted faults through the
comparison that decides ``correct``, the CPU rehearsal of the cell in both
``--trace`` modes, and the five span readers on a hand-made ring."""

import collections
import io
import json

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import run_cell
from benchmarks.harness import check, counts, device, generators
from benchmarks.harness import lloyd_spans, readers, spec
from benchmarks.harness.references import lloyd_kmeans

CELL = "kmeans_fit_ref10"
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
#: a seed whose 20,000-row table has no row within float32 rounding of a tie
#: (2**31 + 2929 has one: at this size a single row that goes the other way
#: reads 8e-3 ten rounds later, over the cell's limit, which is set for 12M)
SEED = 2**31 + 4321
FIVE = tuple(f"lloyd_span_{part}_ms" for part in lloyd_spans.PARTS)


def sharded(devices):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("data",))
    return lambda ndim: NamedSharding(
        mesh, P("data", *([None] * (ndim - 1))))


# -- the generator -----------------------------------------------------------

def test_dense_vector_generator_follows_upstream():
    """``DenseVectorGenerator.java``: one column, ``numValues`` vectors of
    ``vectorDim`` uniform [0, 1) values; the same seed the same table; the
    published ``seed`` parameter is replaced by the run's."""
    data = spec.load_cell(CELL).config["inputData"]
    params = dict(data["paramMap"], numValues=4096)
    one = generators.make_columns(data["className"], params, SEED, sharded(1))
    assert list(one) == ["features"]
    x = np.asarray(one["features"])
    assert x.shape == (4096, 100) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() < 1.0
    assert abs(x.mean() - 0.5) < 0.01 and abs(x.var() - 1 / 12) < 0.005
    again = generators.make_columns(data["className"], params, SEED,
                                    sharded(4))
    assert len(again["features"].addressable_shards) == 4
    np.testing.assert_array_equal(np.asarray(again["features"]), x)
    other = generators.make_columns(data["className"], params, SEED + 1,
                                    sharded(1))
    assert not np.array_equal(np.asarray(other["features"]), x)


# -- the count ---------------------------------------------------------------

def test_ten_lloyd_rounds_over_12m_rows_by_hand():
    cell = spec.load_cell(CELL)
    c = counts.per_fit(cell.config["counts"], cell.stage_params(),
                       cell.config["inputData"]["paramMap"])
    # 10 rounds x 12M rows; a row is 100 float32 features; 10 dot products
    # of length 100 and one add of the row into its sum
    assert c["rows"] == 120_000_000
    assert c["bytes"] == 120_000_000 * 400 == 48_000_000_000
    assert c["flops"] == 120_000_000 * 2100 == 252_000_000_000
    least = counts.least_seconds(c, device.peaks_for("TPU v5 lite"), 1)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(48e9 / 819e9)   # 58.6 ms


# -- the reference -----------------------------------------------------------

N, D, K, ROUNDS = 3000, 100, 10, 10
PARAMS = {"k": K, "maxIter": ROUNDS, "seed": 11}


def numpy_lloyd(x, params):
    """Lloyd in float64, row by row what the configuration states."""
    x = np.asarray(x, np.float64)
    index = np.random.default_rng(params["seed"]).choice(
        len(x), params["k"], replace=False)
    c, counts_ = x[index].copy(), np.zeros(params["k"])
    for _ in range(params["maxIter"]):
        d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        nearest = d2.argmin(1)            # the first smallest on ties
        counts_ = np.bincount(nearest, minlength=params["k"]).astype(float)
        for j in range(params["k"]):
            if counts_[j]:                # an empty cluster keeps its place
                c[j] = x[nearest == j].mean(0)
    return c, counts_


@pytest.fixture(scope="module")
def table():
    return {"features": generators.make_columns(
        "DenseVectorGenerator",
        {"colNames": [["features"]], "numValues": N, "vectorDim": D},
        11, sharded(4))["features"]}


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 250 rows: three a device, so that the reference's sum over
    blocks, and the fault that leaves every second block out, are run."""
    monkeypatch.setattr(lloyd_kmeans, "BLOCK_ROWS", 250)


@pytest.mark.parametrize("tasks", [1, 4])
def test_reference_is_lloyd_as_stated(table, tasks, small_blocks):
    columns = table if tasks == 4 else {
        "features": jax.device_put(table["features"], jax.devices()[0])}
    got = lloyd_kmeans.run(columns, PARAMS, tasks)
    want_c, want_w = numpy_lloyd(columns["features"], PARAMS)
    assert got["_rounds"] == ROUNDS and got["_n"] == N
    np.testing.assert_array_equal(got["weight"], want_w)
    np.testing.assert_allclose(got["centroid"], want_c, rtol=0, atol=1e-6)
    assert got["weight"].sum() == N


def test_an_empty_cluster_keeps_its_centroid():
    x = np.zeros((8, 2), np.float32)
    x[4:] = 1.0
    got = lloyd_kmeans.run({"features": jax.numpy.asarray(x)},
                           {"k": 3, "maxIter": 2, "seed": 0}, 1)
    assert sorted(got["weight"]) == [0.0, 4.0, 4.0]   # ties go to the first
    assert np.isfinite(got["centroid"]).all()


@pytest.mark.parametrize("variant", [
    {"precision": "bfloat16"}, {"fault": "state_unchanged"},
    {"fault": "half_batch"}, {"fault": "one_round_short"}],
    ids=lambda v: next(iter(v.values())))
def test_control_and_faults_are_not_correct(table, variant, small_blocks):
    """Each put in the program's place as the window's one answer and taken
    through ``check.decide`` with the cell's own limits."""
    assert set(lloyd_kmeans.FAULTS) == {
        "state_unchanged", "half_batch", "one_round_short"}
    limits = spec.load_cell(CELL).config["correct"]["limits"]
    reference = lloyd_kmeans.run(table, PARAMS, 4)
    other = lloyd_kmeans.run(table, PARAMS, 4, **variant)
    answer = {k: v for k, v in other.items() if not k.startswith("_")}
    correct, compared = check.decide([answer], lloyd_kmeans, reference,
                                     limits)
    assert correct is False
    assert any(c["value"] > 10 * c["limit"] for c in compared.values())
    same, _ = check.decide(
        [{k: v for k, v in reference.items() if not k.startswith("_")}],
        lloyd_kmeans, reference, limits)
    assert same is True


def test_compare_of_a_wrong_shape_or_a_nan_is_infinite():
    ref = {"centroid": np.ones((2, 3)), "weight": np.ones(2), "_n": 2,
           "_centroid_before_last": np.zeros((2, 3))}
    bad = lloyd_kmeans.compare({"centroid": np.ones((2, 2)),
                                "weight": np.full(2, np.nan)}, ref)
    assert set(bad.values()) == {float("inf")}
    assert lloyd_kmeans.compare({}, ref)["centroid_gap"] == float("inf")


def test_round_gap_tells_the_last_round_from_the_one_before(table,
                                                            small_blocks):
    """Nearer the state after ``maxIter`` rounds than the state after
    ``maxIter - 1``: under 1 for a sound answer with float32's noise on it,
    far over for one that stopped a round early, 0 where the last round
    moved nothing."""
    reference = lloyd_kmeans.run(table, PARAMS, 4)
    last, before = reference["centroid"], reference["_centroid_before_last"]
    moved = np.abs(last - before).max()
    assert moved > 1e-4
    noise = np.random.default_rng(0).normal(size=last.shape) * moved / 50

    def gap(centroid):
        return lloyd_kmeans.compare(
            {"centroid": centroid, "weight": reference["weight"]},
            reference)["round_gap"]

    assert gap(last) == 0.0
    assert 0.0 < gap(last + noise) < 0.2
    assert gap(before + noise) > 5.0
    assert gap(before) == float("inf")
    short = lloyd_kmeans.run(table, PARAMS, 4, fault="one_round_short")
    np.testing.assert_array_equal(short["centroid"], before)
    settled = dict(reference, _centroid_before_last=last)
    assert lloyd_kmeans.compare(
        {"centroid": last + noise, "weight": reference["weight"]},
        settled)["round_gap"] == 0.0


# -- the cell, rehearsed on the CPU ------------------------------------------

def drive(trace, seconds):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(CELL, SEED, seconds, trace, require_tpu=False,
                      overrides={"inputData": {"numValues": 20000},
                                 "traffic": {"trace_capture_s": 1.0}},
                      peaks=PEAKS, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[0]), json.loads(lines[-1]), err.getvalue()


def test_rehearsal_end_to_end():
    rc, info, result, err = drive(False, 0.3)
    assert rc == 0 and result["correct"] is True, err
    cell = spec.load_cell(CELL)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "fit_rows_per_s", "setup_s"}
    assert info["execution_paths"] == ["xla-lloyd"]   # the CPU's default
    assert info["rows_per_fit"] == 10 * 20000
    assert info["window_compiles"]["requests"] == 0
    assert set(result["compared"]) == {"centroid_gap", "weight_gap",
                                       "round_gap",
                                       "window_backend_compiles"}
    for name, c in result["compared"].items():
        assert c["value"] <= c["limit"], name


def test_traced_rehearsal_reads_the_five_spans():
    """No TPU plane on the CPU, so the device trace's readers leave their
    metrics out; everything else the cell lists is in the line, and the
    seven parts of an SGD fit are not the cell's."""
    rc, _, result, err = drive(True, 2.0)
    assert rc == 0 and result["correct"] is True, err
    listed = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(FIVE) <= listed
    assert not {n for n in listed if n.startswith("fit_span_")}
    assert listed >= {"fit_device_roofline", "fit_mfu", "fit_host_gap_ms",
                      "programs_per_fit", "window_compiles",
                      "device_idle_pct", "setup_compile_s",
                      "setup_datagen_s"}
    from_the_device = {"fit_device_roofline", "fit_host_gap_ms",
                       "programs_per_fit", "device_idle_pct"}
    assert set(result["metrics"]) == listed - from_the_device
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert all(result["metrics"][name]["value"] >= 0 for name in FIVE)
    assert result["metrics"]["lloyd_span_fetch_ms"]["value"] > 0


# -- the five readers --------------------------------------------------------

def span(trace, sid, parent, name, dur_us, **attrs):
    return {"type": "span", "trace": trace, "id": sid, "parent": parent,
            "name": name, "ts_us": 0, "dur_us": dur_us, "attrs": attrs}


def lloyd_fit(k, root_us=100_000, segments=0):
    """One Lloyd fit's records in the ring's order: children first."""
    t = f"t{k}"
    out = [span(t, f"{k}-h", f"{k}-p", "collective.host", 40),
           span(t, f"{k}-p", f"{k}-r", "lloyd.place_inputs", 120),
           span(t, f"{k}-i", f"{k}-r", "lloyd.init", 900, rounds=10, k=10,
                path="pallas-lloyd"),
           span(t, f"{k}-b", f"{k}-r", "lloyd.build_program", 20)]
    out += [span(t, f"{k}-s{i}", f"{k}-l", "segment", 100)
            for i in range(segments)]
    out += [span(t, f"{k}-l", f"{k}-r", "lloyd.launch", 400),
            span(t, f"{k}-f", f"{k}-r", "lloyd.fetch", 97_000),
            span(t, f"{k}-g", f"{k}-r", "lloyd.health", 30),
            span(t, f"{k}-m", f"{k}-r", "fit.model", 200),
            span(t, f"{k}-r", None, "KMeans.fit", root_us, kind="fit")]
    return out


def sgd_fit(k):
    t = f"s{k}"
    return [span(t, f"s{k}-o", f"s{k}-r", "sgd.optimize", 700),
            span(t, f"s{k}-r", None, "LogisticRegression.fit", 900,
                 kind="fit")]


@pytest.mark.parametrize("segments", [0, 3])
def test_the_five_parts_sum_to_the_root(segments):
    whole, = [f for f in [lloyd_fit(0, segments=segments)]]
    parts = lloyd_spans.split_us(whole)
    assert tuple(parts) == lloyd_spans.PARTS
    assert sum(parts.values()) == 100_000
    assert parts == {"init": 900, "place": 120, "launch": 400,
                     "fetch": 97_000,
                     "other": 100_000 - 900 - 120 - 400 - 97_000}


def test_readers_give_medians_or_nothing(monkeypatch):
    def ring(records):
        monkeypatch.setattr(lloyd_spans.program_spans, "ring",
                            lambda: collections.deque(records))

    def read_all():
        return {name: readers.load(
            spec.layer_metric_file(name)["reader"])({}) for name in FIVE}

    ring([])                                      # a --trace 0 run
    assert set(read_all().values()) == {None}
    ring([r for k in range(4) for r in lloyd_fit(k)])    # too few fits
    assert set(read_all().values()) == {None}
    # a program without the Lloyd spans (the parent): nothing, no error
    ring([r for k in range(9) for r in sgd_fit(k)])
    assert set(read_all().values()) == {None}
    roots = [90_000, 100_000, 100_000, 110_000, 130_000, 100_000]
    ring([r for k, us in enumerate(roots) for r in lloyd_fit(k, us)]
         + sgd_fit(0) + lloyd_fit(99)[:-1])       # + a fit still open
    got = read_all()
    assert got == {"lloyd_span_init_ms": 0.9, "lloyd_span_place_ms": 0.12,
                   "lloyd_span_launch_ms": 0.4, "lloyd_span_fetch_ms": 97.0,
                   "lloyd_span_other_ms": pytest.approx(100 - 98.42)}
    found = lloyd_spans.medians_ms()
    assert found["fits"] == 6 and found["root"] == pytest.approx(100.0)
