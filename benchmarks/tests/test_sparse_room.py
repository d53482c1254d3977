"""Room for a sparse click-through fit, ahead of the program: a configuration
sourced from a public benchmark of the field, ``CriteoHashedGenerator``'s
sparse column, its handoff to the program (or the refusal where the program
has no entry for it), the reference ``sgd_logistic_sparse`` against scipy
and against the program's own host CSR path, and the count
``sgd_sparse``."""

import io
import json
import shutil
import time

import numpy as np
import pytest
import scipy.sparse

from benchmarks import run_cell
from benchmarks.harness import check, counts, generators, spec
from benchmarks.harness.generators.CriteoHashedGenerator import mix32
from benchmarks.harness.references import sgd_logistic_sparse
from benchmarks.tests.test_spec import SPARSE_INPUT, public_config

CELL = "criteo_fit_ref20"
M = SPARSE_INPUT["numFeatures"]
K = SPARSE_INPUT["numericFields"] + SPARSE_INPUT["categoricalFields"]
SEED = 2**33 + 2**31 + 17
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
#: a batch that wraps round the 20,000 rows and clips at the end of them
SMALL = {"globalBatchSize": 3000, "maxIter": 20}
LIMITS = {"coef_gap": 1e-4}


def sparse_config():
    config = public_config()
    config.update(
        mesh={"data": 1}, dtype="float32", counts="sgd_sparse",
        correct={"reference": "sgd_logistic_sparse", "limits": LIMITS},
        traffic_may_override=["maxIter"])
    return config


def columns_on(devices, n=20_000, seed=SEED):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(devices), ("data",))

    def sharding(ndim):
        if ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))

    return generators.make_columns("CriteoHashedGenerator",
                                   dict(SPARSE_INPUT, numValues=n), seed,
                                   sharding)


@pytest.fixture(scope="module")
def table():
    import jax

    return columns_on(jax.devices()[:1])


@pytest.fixture(scope="module")
def table4():
    import jax

    return columns_on(jax.devices()[:4])


# -- the generator -----------------------------------------------------------

def test_the_column_is_the_contract(table, table4):
    import jax
    from jax.sharding import PartitionSpec as P

    for cols, chips in ((table, 1), (table4, 4)):
        ids, vals = cols["features"]["ids"], cols["features"]["values"]
        assert isinstance(ids, jax.Array) and isinstance(vals, jax.Array)
        assert ids.shape == vals.shape == (20_000, K)
        assert ids.dtype == np.int32 and vals.dtype == np.float32
        assert len(ids.addressable_shards) == chips
        assert ids.sharding.spec == vals.sharding.spec == P("data", None)
        assert int(cols["features"]["size"]) == M
        assert cols["label"].shape == (20_000,)
    # the same seed makes the same table on one device and on four
    for a, b in ((table["features"]["ids"], table4["features"]["ids"]),
                 (table["features"]["values"],
                  table4["features"]["values"]),
                 (table["label"], table4["label"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fields_land_where_feature_hasher_puts_them(table):
    ids = np.asarray(table["features"]["ids"])
    vals = np.asarray(table["features"]["values"])
    numeric = SPARSE_INPUT["numericFields"]
    fixed = mix32(np.arange(numeric, dtype=np.uint32)) % np.uint32(M)
    assert (ids[:, :numeric] == fixed.astype(np.int32)).all()
    assert (vals[:, :numeric] >= 0).all() and (vals[:, :numeric] < 1).all()
    assert vals[:, :numeric].std() == pytest.approx(12 ** -0.5, rel=0.02)
    assert (vals[:, numeric:] == 1.0).all()
    assert ((ids >= 0) & (ids < M)).all()
    assert set(np.unique(np.asarray(table["label"]))) == {0.0, 1.0}
    s = SPARSE_INPUT["zipfExponent"]
    for f, card in enumerate(SPARSE_INPUT["cardinalities"]):
        col = ids[:, numeric + f]
        field = np.full(1, numeric + f, np.uint32)
        buckets = mix32(field, np.arange(1, min(card, 20_000) + 1,
                                         dtype=np.uint32)) % np.uint32(M)
        # the power law's head: rank 1's share is the law's
        p1 = (1 - 2 ** (1 - s)) / (1 - (card + 1) ** (1 - s))
        assert np.mean(col == buckets[0]) == pytest.approx(p1, abs=0.015)
        if card <= 20_000:      # every value is one of the field's ranks
            assert np.isin(col, buckets.astype(np.int32)).all()
        if card <= 30:          # and every rank is drawn
            assert len(np.unique(col)) == len(np.unique(buckets))


def test_another_seed_another_table(table):
    import jax

    other = columns_on(jax.devices()[:1], seed=SEED + 1)
    assert not np.array_equal(np.asarray(other["features"]["ids"]),
                              np.asarray(table["features"]["ids"]))


# -- the count -----------------------------------------------------------------

def test_the_count_is_the_least_any_implementation_moves():
    c = counts.per_fit("sgd_sparse", {"maxIter": 20,
                                      "globalBatchSize": 100_000},
                       dict(SPARSE_INPUT, numValues=23_000_000))
    # 2M rows of 39 ids and values and a label; 262,144 coefficients read
    # and written a round; a multiply and an add an entry for each product
    assert c["rows"] == 2_000_000
    assert c["bytes"] == 2_000_000 * (39 * 8 + 4) + 20 * 2 * 4 * 262_144
    assert c["flops"] == 2_000_000 * 4 * 39


# -- the room: a public-source cell in a copy of the tree --------------------

@pytest.fixture
def checkout(tmp_path):
    """This tree's benchmark with one more configuration and cell."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    config = sparse_config()
    (root / "benchmarks/configs/criteo-hashed-lr.json").write_text(
        json.dumps(config))
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": config["name"], "source": config["source"],
        "file": "benchmarks/configs/criteo-hashed-lr.json",
        "reduced": ["numValues"], "why": "a test's copy"})
    bench["workloads"].append({
        "name": CELL, "config": config["name"],
        "traffic": "fit_rounds_published", "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_public_source_cell_resolves(checkout):
    cell = spec.load_cell(CELL, checkout)
    spec.check_source(cell.config)
    assert cell.stage_params()["maxIter"] == 20
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                     "fit_rows_per_s"}
    assert counts.per_fit(cell.config["counts"], cell.stage_params(),
                          cell.config["inputData"]["paramMap"])["rows"] > 0


@pytest.fixture
def no_entry(monkeypatch):
    """The program as this tree has it: no ``device_sparse_column``."""
    from flink_ml_tpu.linalg import sparse

    monkeypatch.delattr(sparse, "device_sparse_column", raising=False)


def test_a_program_without_the_entry_refuses_at_once(checkout, no_entry):
    out, err = io.StringIO(), io.StringIO()
    t = time.perf_counter()
    rc = run_cell.run(CELL, SEED, 1.0, False, root=checkout,
                      require_tpu=False, peaks=PEAKS, out=out, err=err)
    assert time.perf_counter() - t < 60
    assert rc != 0
    assert "device_sparse_column" in err.getvalue()
    assert out.getvalue() == ""


class _Handed:
    """What the stub entry hands the table: it keeps the two arrays, and a
    copy to the host is an error."""
    dtype = np.dtype(object)
    ndim = 1

    def __init__(self, ids, values, size):
        self.ids, self.values, self.size = ids, values, size

    def __len__(self):
        return self.ids.shape[0]

    def __array__(self, *args, **kwargs):
        raise AssertionError("the sparse column was copied to the host")


def test_the_column_reaches_the_entry_as_the_device_arrays(checkout,
                                                           monkeypatch):
    import jax

    from benchmarks.harness import system
    from flink_ml_tpu.linalg import sparse

    monkeypatch.setattr(sparse, "device_sparse_column", _Handed,
                        raising=False)
    cell = spec.load_cell(CELL, checkout)
    columns, _ = run_cell.make_inputs(cell, SEED, system, jax.devices()[:1])
    made = columns["features"]
    table = system.make_table(columns)
    handed = table.column("features")
    assert isinstance(handed, _Handed) and handed.size == M
    assert handed.ids is made["ids"] and handed.values is made["values"]
    assert table.column("label") is columns["label"]


# -- the reference -----------------------------------------------------------

def scipy_fit(cols, tasks, params):
    """The same schedule over a scipy CSR matrix, float64: task ``s``'s next
    rows, clipped at the end of its rows, starting again after it."""
    ids = np.asarray(cols["features"]["ids"])
    vals = np.asarray(cols["features"]["values"], np.float64)
    y = np.asarray(cols["label"], np.float64)
    n, k = ids.shape
    x = scipy.sparse.csr_matrix(
        (vals.ravel(), ids.ravel(), np.arange(0, n * k + 1, k)), (n, M))
    local, gb = n // tasks, params["globalBatchSize"]
    w, offsets = np.zeros(M), [0] * tasks
    for _ in range(params["maxIter"]):
        rows = []
        for s in range(tasks):
            lb = min(gb // tasks + (s < gb % tasks), local)
            rows.append(s * local + np.arange(offsets[s],
                                              min(offsets[s] + lb, local)))
            offsets[s] = 0 if offsets[s] + lb >= local else offsets[s] + lb
        r = np.concatenate(rows)
        sign = 2 * y[r] - 1
        margins = (x[r] @ w) * sign
        w = w - params["learningRate"] / len(r) * (
            x[r].T @ (-sign / (np.exp(margins) + 1)))
    return w[None]


def stage_params():
    return dict(sparse_config()["stage"]["paramMap"], **SMALL)


def _reference(cols, tasks, **kw):
    return sgd_logistic_sparse.run(cols, stage_params(), tasks, **kw)


@pytest.mark.parametrize("tasks", [1, 4])
def test_the_reference_is_scipy_s_and_the_program_s_host_path(
        table, table4, tasks):
    import jax

    from flink_ml_tpu.ops.losses import BinaryLogisticLoss
    from flink_ml_tpu.ops.optimizer import SGD, SGDParams
    from flink_ml_tpu.parallel.mesh import create_mesh

    cols = table if tasks == 1 else table4
    params = stage_params()
    ref = _reference(cols, tasks)
    assert ref["_rounds"] == 20
    plain = scipy_fit(cols, tasks, params)
    own_gap = sgd_logistic_sparse.compare({"coefficient": plain},
                                          ref)["coef_gap"]
    assert own_gap < 1e-12
    ids = np.asarray(cols["features"]["ids"])
    x = scipy.sparse.csr_matrix(
        (np.asarray(cols["features"]["values"], np.float64).ravel(),
         ids.ravel(), np.arange(0, ids.size + 1, K)), (ids.shape[0], M))
    coeffs, _ = SGD(SGDParams(
        learning_rate=params["learningRate"],
        global_batch_size=params["globalBatchSize"],
        max_iter=params["maxIter"], tol=params["tol"])).optimize_csr(
        BinaryLogisticLoss(), np.zeros(M), x, np.asarray(cols["label"]),
        mesh=create_mesh(devices=jax.devices()[:tasks]))
    assert sgd_logistic_sparse.compare(
        {"coefficient": np.asarray(coeffs)[None]}, ref)["coef_gap"] < 1e-12


@pytest.mark.parametrize("variant", [{"precision": "bfloat16"}] + [
    {"fault": f} for f in sgd_logistic_sparse.FAULTS])
def test_the_control_and_every_fault_are_not_correct(table, variant):
    """Each put in the program's place and taken through ``check.decide``
    under a placeholder limit of 1e-4: not correct, and at least 100 times
    the reference's own gap to scipy over it."""
    ref = _reference(table, 1)
    own_gap = max(sgd_logistic_sparse.compare(
        {"coefficient": scipy_fit(table, 1, stage_params())},
        ref)["coef_gap"], 1e-300)
    other = _reference(table, 1, **variant)
    correct, compared = check.decide(
        [{"coefficient": other["coefficient"]}], sgd_logistic_sparse, ref,
        LIMITS)
    assert correct is False
    assert compared["coef_gap"]["value"] >= 100 * own_gap
    assert compared["coef_gap"]["value"] > LIMITS["coef_gap"]
    same, _ = check.decide([{"coefficient": ref["coefficient"]}],
                           sgd_logistic_sparse, ref, LIMITS)
    assert same is True


def test_a_penalty_is_not_covered(table):
    with pytest.raises(NotImplementedError):
        sgd_logistic_sparse.run(table, dict(stage_params(), reg=0.1), 1)


# -- the table on a v5e, ahead of time ----------------------------------------

@pytest.fixture(scope="module")
def v5e():
    """One device of a v5e as the TPU compiler sees it (no chip needed)."""
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e can be described here: {exc}")


def test_the_published_size_lies_in_sublanes_not_lanes(v5e):
    """23M rows at 2^18 buckets on one v5e: ids and values column-major, 39
    entries on 40 sublanes (324 B a row with the label), never 128 lanes;
    and the reference's window of a round is a slice, no copy of the
    table."""
    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness.generators import CriteoHashedGenerator

    n = 23_000_000
    mesh = jax.sharding.Mesh(np.array([v5e]), ("data",))
    gen, ranks = CriteoHashedGenerator.build(dict(SPARSE_INPUT, numValues=n))
    shardings = jax.tree.map(
        lambda r: NamedSharding(mesh, P(*["data", *[None] * (r - 1)][:r])),
        ranks)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(gen, out_shardings=shardings).lower(key).compile()
    memory = compiled.memory_analysis()
    assert n * 324 <= memory.output_size_in_bytes <= n * 324 + 2 ** 16
    assert memory.temp_size_in_bytes < 2e9
    root = re.findall(r"ROOT .*", compiled.as_text())[-1]
    for dtype in ("s32", "f32"):
        assert f"{dtype}[{n},39]{{0,1:T(8,128)}}" in root
    table = jax.ShapeDtypeStruct((n, K), np.int32,
                                 sharding=NamedSharding(mesh, P("data", None)))
    start = jax.ShapeDtypeStruct((), np.int32,
                                 sharding=NamedSharding(mesh, P()))
    window = sgd_logistic_sparse._window_program(100_000).lower(
        table, start).compile().memory_analysis()
    assert window.temp_size_in_bytes == 0
    assert window.output_size_in_bytes <= 40 * 100_352 * 4
