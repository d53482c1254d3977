"""LogisticRegression / SGD over a device sparse column
(``linalg/sparse.py::device_sparse_column``, ``SGD.optimize_sparse``): the
dense fit's schedule, programs and carries, with a gather and a scatter-add
in each round in place of the two dense products (``ops/sparse_window.py``).

Held to two references that share no code with the program, on seeded
tables: ``SGD.optimize_csr`` (scipy CSR on the host, float64) and the plain
NumPy float64 loop below (SGD.java:206-213, 231-243, 262-284 over a task's
contiguous rows). The dense fits' programs keep the parent's text
(``fixtures/sgd_programs/onchip_lowered.json``, written from commit
38ab75f by running this file as a script there).
"""

import dataclasses
import hashlib
import json
import os
import sys

if __name__ == "__main__":  # the fixture writer: the mesh conftest.py gives
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.linalg import sparse
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import optimizer as opt_mod, sparse_window
from flink_ml_tpu.ops.losses import BinaryLogisticLoss
from flink_ml_tpu.ops.optimizer import SGD, SGDParams
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh
from test_sgd_programs import UNIT_PRM, _carry_shapes, _shape, _unit_mesh

ONCHIP_LOWERED = os.path.join(os.path.dirname(__file__), "fixtures",
                              "sgd_programs", "onchip_lowered.json")
#: the split-scatter program of a sparse fit as commit 3f9dcd2 lowers it
#: (``sparse_lowered_text``), written by running this file as a script there
SPARSE_LOWERED = os.path.join(os.path.dirname(__file__), "fixtures",
                              "sgd_programs", "sparse_lowered.json")
N, K = 20_000, 39
#: hot entry positions: one bucket on every row, as FeatureHasher puts a
#: numeric field
HOT = 3
#: float32 against float64, as a share of the largest coefficient: a
#: bucket's gradient is a float32 sum of its terms in the scatter's order,
#: some 20,000 of them a round in a Zipf head (the CPU reads 2.2e-7; a
#: float32 ``np.add.at`` in the reference's place 1.5e-7). A bfloat16
#: computation reads 5.7e-4..7.0e-3, a scatter that drops duplicates ~1
TOL = 1e-5
#: the moments carry each round's rounding into every later round: after 12
#: rounds momentum reads 6.6e-5 and adam 8.0e-6 on the CPU, as a float32
#: ``np.add.at`` in the reference's place reads 6.5e-5 and 7.2e-7; bfloat16
#: reads 3.4e-3 and 4.0e-3 there
MOMENT_TOL = 2e-4


@dataclasses.dataclass(frozen=True)
class Case:
    size: int = 1 << 10
    batch: int = 2_000
    rounds: int = 12
    tol: float = 0.0
    reg: float = 0.0
    elastic_net: float = 0.0
    method: str = "sgd"
    table: str = "zipf"
    #: every position the index finds narrow left to the gather and the
    #: scatter (``device_fit``)
    wide: bool = False

    @property
    def tolerance(self):
        return TOL if self.method == "sgd" else MOMENT_TOL

    def params(self):
        return SGDParams(learning_rate=0.1, global_batch_size=self.batch,
                         max_iter=self.rounds, tol=self.tol, reg=self.reg,
                         elastic_net=self.elastic_net, method=self.method)


CASES = {
    "zipf-hot-and-duplicates": Case(),
    "zipf-2^18-buckets": Case(size=1 << 18),
    # one task: 0, 6000, 12000, then 18000 clipped at 20000, then 0; four
    # tasks of 5000 rows: 1500 a round, the fourth clipped, the fifth at 0
    "wrap-and-clip": Case(batch=6_000, rounds=6),
    # the mean loss starts at ln 2 and falls under the tol in a few rounds
    "tol-stop": Case(tol=0.6928, rounds=40),
    "l2": Case(reg=0.05),
    "elastic-net": Case(reg=0.05, elastic_net=0.5),
    "momentum": Case(method="momentum"),
    "adam": Case(method="adam"),
    # hot, narrow and wide positions in one window (``mixed_table``)
    "hot-narrow-and-wide": Case(size=1 << 18, table="mixed"),
}
#: the Zipf table's 20,000 rows hold under ``NARROW_MAX`` ids at every
#: position, so the index takes every position that is not hot to the
#: dictionary form: each such case again with them gathered and scattered
CASES.update({f"{name}-wide": dataclasses.replace(case, wide=True)
              for name, case in list(CASES.items())
              if case.table == "zipf"})


@pytest.fixture(autouse=True)
def restore_state():
    tracer.recent.clear()
    yield
    set_default_mesh(None)
    tracer.recent.clear()


def sparse_table(size, seed=0):
    """``(ids, values, labels)``: ``HOT`` entries at one bucket every row
    holds, values uniform; the rest Zipf ranks hashed into ``size``, value
    1; entries 10 and 11 of a row in one bucket; labels from a planted
    model."""
    rng = np.random.default_rng(seed)
    ids = (rng.zipf(1.3, (N, K)) * 2654435761 % size).astype(np.int32)
    ids[:, :HOT] = [7, 11, 7]           # two hot entries share a bucket
    ids[:, 11] = ids[:, 10]
    vals = np.ones((N, K), np.float32)
    vals[:, :HOT] = rng.random((N, HOT), dtype=np.float32)
    planted = rng.normal(size=size) * 0.5
    dots = np.sum(planted[ids] * vals, axis=1)
    y = (rng.random(N) < 1 / (1 + np.exp(-dots))).astype(np.float32)
    return ids, vals, y


#: the narrow positions of ``mixed_table`` and the most ranks each holds
NARROW_CARDS = (3, 4, 10, 18, 24, 105, 305, 583, 633)
#: its first wide position, and the narrow one half of whose ids it repeats
SHARED, SHARED_WITH = 14, 3


def mixed_table(size, seed=0):
    """``(ids, values, labels)`` as the click-through column lies: ``HOT``
    positions as ``sparse_table``'s; positions 3-11 narrow, Zipf ranks of at
    most ``NARROW_CARDS`` hashed into ``size`` (3 to 633 buckets), values
    uniform; position 12 repeats 11 (two narrow entries of a row in one
    bucket); the rest wide (uniform over ``size``, value 1), the first of
    them holding position 3's bucket on every second row and position 16
    repeating 15 (two wide entries of a row in one bucket); labels from a
    planted model."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, size, (N, K)).astype(np.int32)
    ids[:, :HOT] = [7, 11, 7]
    for i, card in enumerate(NARROW_CARDS):
        rank = np.minimum(rng.zipf(1.3, N), card)
        ids[:, HOT + i] = (rank * 2654435761 + i) % size
    narrow_end = HOT + len(NARROW_CARDS)
    ids[:, narrow_end] = ids[:, narrow_end - 1]
    ids[::2, SHARED] = ids[::2, SHARED_WITH]
    ids[:, 16] = ids[:, 15]
    vals = np.ones((N, K), np.float32)
    vals[:, :narrow_end] = rng.random((N, narrow_end), dtype=np.float32)
    planted = rng.normal(size=size) * 0.5
    dots = np.sum(planted[ids] * vals, axis=1)
    y = (rng.random(N) < 1 / (1 + np.exp(-dots))).astype(np.float32)
    return ids, vals, y


TABLES = {"zipf": sparse_table, "mixed": mixed_table}


def on_mesh(devices):
    mesh = create_mesh(devices=jax.devices()[:devices])
    set_default_mesh(mesh)
    return mesh


def column_on(mesh, ids, vals, size):
    rows = NamedSharding(mesh, P("data", None))
    return sparse.device_sparse_column(jax.device_put(ids, rows),
                                       jax.device_put(vals, rows), size)


def labels_on(mesh, y):
    return jax.device_put(y, NamedSharding(mesh, P("data")))


def csr_of(ids, vals, size):
    n, k = ids.shape
    return sp.csr_matrix((vals.astype(np.float64).ravel(), ids.ravel(),
                          np.arange(0, n * k + 1, k)), shape=(n, size))


def _regularize(w, prm):
    if prm.reg == 0.0:
        return w
    l1 = prm.elastic_net * prm.reg * np.sign(w)
    return w - prm.learning_rate * (l1 + (1.0 - prm.elastic_net)
                                    * prm.reg * w)


def reference_fit(prm, ids, vals, y, tasks, size, variant=None):
    """``(coeffs, rounds)`` of SGD.java's schedule over ``tasks`` shards of
    ``n / tasks`` rows, in float64. ``variant`` plants what the tolerance
    must catch: ``"bfloat16"`` rounds the values, margins, multipliers,
    gradient and state to bfloat16; ``"duplicates-dropped"`` keeps one
    term a bucket (the last write of a scatter that does not add)."""
    def r(a):
        a = np.asarray(a, np.float64)
        return (a.astype(jnp.bfloat16).astype(np.float64)
                if variant == "bfloat16" else a)

    n = len(y)
    shard = n // tasks
    share = [min(prm.global_batch_size // tasks
                 + (t < prm.global_batch_size % tasks), shard)
             for t in range(tasks)]
    offsets = [0] * tasks
    w = np.zeros(size)
    m, v, step = np.zeros(size), np.zeros(size), 0
    mean_loss, rounds = np.inf, 0
    while rounds < prm.max_iter and not mean_loss < prm.tol:
        rows = []
        for t in range(tasks):
            rows.append(t * shard + np.arange(
                offsets[t], min(offsets[t] + share[t], shard)))
            offsets[t] = (0 if offsets[t] + share[t] >= shard
                          else offsets[t] + share[t])
        rows = np.concatenate(rows)
        idx, val, s = ids[rows], r(vals[rows]), 2.0 * y[rows] - 1.0
        dots = r(np.sum(w[idx] * val, axis=1))
        mean_loss = np.sum(np.logaddexp(0.0, -s * dots)) / len(rows)
        terms = (r(-s / (np.exp(s * dots) + 1.0))[:, None] * val).ravel()
        if variant == "duplicates-dropped":
            grad = np.zeros(size)
            grad[idx.ravel()] = terms
        else:
            grad = np.bincount(idx.ravel(), terms, minlength=size)
        grad, total = r(grad), float(len(rows))
        if prm.method == "sgd":
            w = w - prm.learning_rate / total * grad
        elif prm.method == "momentum":
            m = prm.momentum * m + grad / total
            w = w - prm.learning_rate * m
        else:
            g, step = grad / total, step + 1
            m = prm.beta1 * m + (1 - prm.beta1) * g
            v = prm.beta2 * v + (1 - prm.beta2) * g * g
            w = w - prm.learning_rate * (m / (1 - prm.beta1 ** step)) / (
                np.sqrt(v / (1 - prm.beta2 ** step)) + prm.eps)
        w = r(_regularize(w, prm))
        rounds += 1
    return w, rounds


def gap(a, b):
    return float(np.abs(np.asarray(a) - b).max() / np.abs(b).max())


def device_fit(prm, mesh, ids, vals, y, size, wide=False, **kw):
    """The fit over the column's index as found, or ``wide``: with its
    narrow positions gathered and scattered as every other."""
    column = column_on(mesh, ids, vals, size)
    if wide:
        column = sparse.DeviceSparseColumn(column.ids, column.values, size,
                                           column.hot)
    sgd = SGD(prm)
    coeffs, loss = sgd.optimize_sparse(
        BinaryLogisticLoss(), np.zeros(size), column, labels_on(mesh, y),
        mesh=mesh, **kw)
    return sgd, coeffs, loss


# -- the fit against two references -------------------------------------------

@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", CASES)
def test_the_device_fit_is_the_references_fit(name, devices):
    case = CASES[name]
    prm = case.params()
    mesh = on_mesh(devices)
    ids, vals, y = TABLES[case.table](case.size)
    sgd, coeffs, _ = device_fit(prm, mesh, ids, vals, y, case.size,
                                wide=case.wide)
    assert sgd.last_execution_path == "sparse-device"
    # the dictionary form takes entries where the index found narrow ones:
    # every table has some
    assert (sgd.last_entries[1] > 0) == (not case.wide)
    want, rounds = reference_fit(prm, ids, vals, y, devices, case.size)
    assert (rounds < case.rounds) == name.startswith("tol-stop")
    assert gap(coeffs, want) < case.tolerance
    host, _ = SGD(prm).optimize_csr(BinaryLogisticLoss(), np.zeros(case.size),
                                    csr_of(ids, vals, case.size), y,
                                    mesh=mesh)
    assert gap(host, want) < 1e-12
    assert gap(coeffs, host) < case.tolerance


@pytest.mark.parametrize("variant", ["bfloat16", "duplicates-dropped"])
@pytest.mark.parametrize("name", ["zipf-hot-and-duplicates", "momentum",
                                  "adam"])
def test_what_the_tolerance_refuses(name, variant):
    case = CASES[name]
    ids, vals, y = sparse_table(case.size)
    want, _ = reference_fit(case.params(), ids, vals, y, 1, case.size)
    other, _ = reference_fit(case.params(), ids, vals, y, 1, case.size,
                             variant)
    assert gap(other, want) > 10 * case.tolerance


@pytest.mark.parametrize("devices", [1, 4])
def test_a_dense_table_as_ids_is_the_dense_fit(devices):
    """``ids = arange(d)`` on every row: the sparse fit of a dense table is
    the dense fit to float32 rounding (every entry is hot here, so the
    products are column sums; the dense program's are matrix products)."""
    mesh = on_mesh(devices)
    rng = np.random.default_rng(5)
    d = 12
    x = rng.normal(size=(N, d)).astype(np.float32)
    y = (x @ rng.normal(size=d) > 0).astype(np.float32)
    prm = SGDParams(learning_rate=0.1, global_batch_size=3_000, max_iter=9,
                    tol=0.0)
    ids = np.tile(np.arange(d, dtype=np.int32), (N, 1))
    _, coeffs, loss = device_fit(prm, mesh, ids, x, y, d)
    dense, dense_loss = SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(d),
                                          x, y, mesh=mesh)
    np.testing.assert_allclose(coeffs, dense, rtol=1e-5, atol=1e-7)
    assert loss == pytest.approx(dense_loss, rel=1e-5)


@pytest.mark.parametrize("config", ["segments", "host-rounds",
                                    "segments-wide", "host-rounds-wide"])
def test_a_carry_that_crosses_the_host_answers_as_the_plain_fit(config,
                                                                tmp_path):
    """Over the dictionary form and (``-wide``) the gather and scatter."""
    prm = CASES["momentum"].params()
    mesh = on_mesh(4)
    ids, vals, y = sparse_table(1 << 10)
    wide = config.endswith("-wide")
    _, plain, _ = device_fit(prm, mesh, ids, vals, y, 1 << 10, wide=wide)
    iteration = (IterationConfig(mode="host")
                 if config.startswith("host-rounds")
                 else IterationConfig(checkpoint_interval=5,
                                      checkpoint_manager=CheckpointManager(
                                          str(tmp_path))))
    sgd, coeffs, _ = device_fit(prm, mesh, ids, vals, y, 1 << 10,
                                wide=wide, config=iteration)
    assert (sgd.last_entries[1] > 0) != wide
    assert sgd.last_execution_path == {
        "segments": "sparse-device-segments",
        "host-rounds": "sparse-host-rounds"}[config.removesuffix("-wide")]
    np.testing.assert_allclose(coeffs, plain, rtol=1e-6, atol=1e-9)


# -- the index and the dictionary form ----------------------------------------

def test_the_index_names_hot_narrow_and_wide_positions():
    """Over the whole table: the hot positions, the narrow ones with their
    dictionaries (each its position's distinct ids, ascending, padded with
    -1), and the wide ones, the position that shares a narrow bucket
    among them."""
    size = 1 << 18
    ids, vals, _ = mixed_table(size)
    col = column_on(on_mesh(1), ids, vals, size)
    assert col.hot == ((0, 7), (1, 11), (2, 7))
    narrow_end = HOT + len(NARROW_CARDS) + 1
    assert [j for j, _ in col.narrow] == list(range(HOT, narrow_end))
    dicts = np.asarray(col.dicts)
    assert dicts.shape[0] == K
    assert dicts.shape[1] >= sparse_window.NARROW_MAX
    for j, slots in col.narrow:
        found = np.unique(ids[:, j])
        assert slots == sparse.dict_slots(len(found)) >= len(found)
        np.testing.assert_array_equal(dicts[j, :len(found)], found)
        assert (dicts[j, len(found):] == -1).all()
    assert np.isin(ids[::2, SHARED], dicts[SHARED_WITH]).all()


@pytest.mark.parametrize("index", ["column", "hot-alone", "none"])
def test_a_window_s_products_are_float64_s(index):
    """One window's margins and gradient in each form the index allows
    (``column``: hot, narrow and wide; ``hot-alone``: the split-scatter
    form; ``none``: every entry gathered and scattered) against a float64
    fancy index and ``np.bincount``: every entry counts once, two entries
    of a row in one bucket both add (narrow and wide), and a bucket a
    narrow dictionary and a wide position's entries share takes both."""
    size, rows = 1 << 18, 3_000
    ids, vals, _ = mixed_table(size)
    col = column_on(on_mesh(1), ids, vals, size)
    hot, narrow = {"column": (col.hot, col.narrow),
                   "hot-alone": (col.hot, ()), "none": ((), ())}[index]
    window_ids, window_vals = ids[:rows].T, vals[:rows].T
    rng = np.random.default_rng(1)
    w = rng.normal(size=size).astype(np.float32)
    mult = rng.uniform(-1.0, 1.0, rows).astype(np.float32)
    margins, gradient = sparse_window.products(
        jnp.asarray(window_ids), jnp.asarray(window_vals), size, hot,
        narrow, col.dicts if narrow else None)
    got = np.asarray(jax.jit(margins)(jnp.asarray(w)), np.float64)
    want = np.sum(w.astype(np.float64)[window_ids] * window_vals, axis=0)
    assert gap(got, want) < 1e-6
    got = np.asarray(jax.jit(gradient)(jnp.asarray(mult)), np.float64)
    want = np.bincount(window_ids.ravel(), (
        window_vals * mult.astype(np.float64)[None, :]).ravel(),
        minlength=size)
    assert gap(got, want) < 1e-6


@pytest.fixture
def small_sample(monkeypatch):
    """The index proposes each dictionary from the column's first 1,000
    rows."""
    monkeypatch.setattr(sparse, "SAMPLE_ROWS", 1_000)
    sparse._index_program.cache_clear()
    yield
    sparse._index_program.cache_clear()


#: a bucket the sample missed: the devices, the row that first holds it, the
#: rounds of each fit over the column (a task's batch 2,000 / devices rows),
#: and whether its position is wide after each
MISSED = {
    # 5 rounds read rows [0, 10,000)
    "inside-the-rows-read": (1, 5_000, (5,), (True,)),
    # ... and not row 15,000, which 12 rounds (the whole table) read
    "past-the-rows-read": (1, 15_000, (5, 12), (False, True)),
    # shards of 5,000 rows, 5 rounds of 500 on each: row 11,000 is the
    # third shard's 1,000th
    "on-one-shard-of-four": (4, 11_000, (5,), (True,)),
}


@pytest.mark.parametrize("where", MISSED)
def test_an_id_the_sample_missed_keeps_its_position_wide(where,
                                                         small_sample):
    """A bucket first held after the sample: a fit that weights the row
    holding it finds it and takes its position wide, for that fit and
    every later one (on a mesh, on every shard); a fit that does not keeps
    the position narrow; every fit is the reference's."""
    devices, row, rounds, wide_after = MISSED[where]
    size = 1 << 18
    ids, vals, y = mixed_table(size)
    late = HOT + 1                       # 4 buckets in the first rows
    ids[row, late] = 12_345
    assert 12_345 not in ids[:1_000, late]
    mesh = on_mesh(devices)
    col = column_on(mesh, ids, vals, size)
    assert {HOT, late} <= {j for j, _ in col.narrow}    # proposed
    for fit_rounds, wide in zip(rounds, wide_after):
        prm = dataclasses.replace(CASES["zipf-hot-and-duplicates"],
                                  rounds=fit_rounds).params()
        sgd = SGD(prm)
        coeffs, _ = sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(size),
                                        col, labels_on(mesh, y), mesh=mesh)
        narrow_at = [j for j, _ in col.narrow]
        assert (late in sgd.last_check[1]) == wide == (late not in narrow_at)
        assert HOT in narrow_at
        want, _ = reference_fit(prm, ids, vals, y, devices, size)
        assert gap(coeffs, want) < TOL


def test_a_row_subset_keeps_the_proposals_and_checks_its_own_rows():
    """A fit checks the rows it reads once; a row subset of the column
    keeps its proposals and dictionaries and starts with no row checked."""
    mesh = on_mesh(1)
    ids, vals, y = sparse_table(1 << 10)
    col, labels = column_on(mesh, ids, vals, 1 << 10), labels_on(mesh, y)
    sgd = SGD(CASES["l2"].params())         # 12 rounds of 2,000: every row
    group = metrics.group(ML_GROUP, "sgd")
    for checked in (N, 0):
        before = group.get_counter("dictRowsChecked")
        sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(1 << 10), col,
                            labels, mesh=mesh)
        assert sgd.last_check == (checked, ())
        # rows times the positions checked: the 36 that are not hot
        assert group.get_counter("dictRowsChecked") - before == \
            checked * (K - HOT)
    part = col[4_000:14_000]
    assert part.narrow == col.narrow and part.dicts is col.dicts
    sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(1 << 10), part,
                        labels_on(mesh, y[4_000:14_000]), mesh=mesh)
    assert sgd.last_check == (10_000, ())


@pytest.mark.parametrize("devices", [1, 4])
def test_the_fit_is_the_benchmark_reference_s_fit(devices):
    """The hot, narrow and wide column against the click-through cell's own
    reference (``benchmarks/harness/references/sgd_logistic_sparse.py``:
    the rows a round touches on the host, float64), within the tolerance
    ``reference_fit`` holds the fit to."""
    from benchmarks.harness.references import sgd_logistic_sparse

    size = 1 << 18
    mesh = on_mesh(devices)
    ids, vals, y = mixed_table(size)
    rows = NamedSharding(mesh, P("data", None))
    column = {"ids": jax.device_put(ids, rows),
              "values": jax.device_put(vals, rows), "size": size}
    want = sgd_logistic_sparse.run(
        {"features": column, "label": labels_on(mesh, y)},
        {"maxIter": 12, "globalBatchSize": 2_000, "learningRate": 0.1,
         "tol": 0.0, "reg": 0.0}, devices)["coefficient"][0]
    sgd, coeffs, _ = device_fit(CASES["hot-narrow-and-wide"].params(), mesh,
                                ids, vals, y, size)
    assert sgd.last_execution_path == "sparse-device"
    assert gap(coeffs, want) < TOL


def sparse_lowered_text(n=400, k=6, size=1 << 14):
    """The lowered text of a plain sparse fit's program on one device with
    a hot index, ``(0, 3)`` and ``(2, 5)``, and no narrow one: the
    split-scatter form."""
    mesh = _unit_mesh("one-device")
    prog = opt_mod._build_sgd_segment_program(
        BinaryLogisticLoss, mesh, UNIT_PRM, fused=True, weighted=False,
        fresh=True, sparse=sparse_window.Layout(size, ((0, 3), (2, 5))))
    table = P("data", None)
    return prog.lower(
        (_shape(mesh, (n, k), table, jnp.int32), _shape(mesh, (n, k), table)),
        _shape(mesh, (n,), P("data")), None,
        _shape(mesh, (size,), P())).as_text()


def test_a_column_with_no_narrow_position_keeps_the_split_scatter_program():
    """Every position but the hot ones wide (some 14,000 buckets each): no
    dictionary is made, the form is ``split-scatter`` and the program's
    text is the parent's."""
    size = 1 << 14
    ids = np.random.default_rng(3).integers(0, size, (N, 6)).astype(np.int32)
    ids[:, [0, 2]] = [3, 5]
    col = column_on(on_mesh(1), ids, np.ones((N, 6), np.float32), size)
    assert col.hot == ((0, 3), (2, 5))
    assert col.narrow == () and col.dicts is None
    assert sparse_window.form(col.hot, col.narrow) == "split-scatter"
    assert sparse_window.Layout(col.size, col.hot, col.narrow) == \
        sparse_window.Layout(size, ((0, 3), (2, 5)))
    with open(SPARSE_LOWERED) as f:
        want = json.load(f)["programs"]["split-scatter-one-device"]
    text = sparse_lowered_text()
    assert len(text) == want["characters"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]


# -- the estimator, the model, the column -------------------------------------

def lr_table(mesh, size=1 << 10):
    ids, vals, y = sparse_table(size)
    return Table.from_columns(features=column_on(mesh, ids, vals, size),
                              label=labels_on(mesh, y)), (ids, vals, y)


def estimator():
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression)

    return LogisticRegression().set_max_iter(8).set_global_batch_size(2_000)


def test_logistic_regression_trains_and_predicts_on_the_device():
    mesh = on_mesh(4)
    table, (ids, vals, y) = lr_table(mesh)
    lr = estimator()
    model = lr.fit(table)
    assert lr.last_execution_path == "sparse-device"
    want, _ = reference_fit(SGDParams(learning_rate=0.1,
                                      global_batch_size=2_000, max_iter=8,
                                      tol=1e-6), ids, vals, y, 4, 1 << 10)
    assert gap(model.coefficients, want) < TOL
    out, = model.transform(table)
    assert isinstance(out.column("prediction"), jax.Array)
    assert isinstance(out.column("features"), sparse.DeviceSparseColumn)
    dots = csr_of(ids, vals, 1 << 10) @ model.coefficients
    raw = np.asarray(out.column("rawPrediction"))
    np.testing.assert_allclose(raw[:, 1], 1 / (1 + np.exp(-dots)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.column("prediction")),
                                  (dots >= 0).astype(np.float32))


def test_a_host_csr_column_keeps_the_host_path():
    ids, vals, y = sparse_table(1 << 10)
    table = Table.from_columns(
        features=sparse.CsrVectorColumn(csr_of(ids, vals, 1 << 10)), label=y)
    lr = estimator()
    lr.fit(table)
    assert lr.last_execution_path == "csr-host"


@pytest.mark.parametrize("capture", ["drift", "quality"])
def test_a_baseline_capture_reads_a_sample_of_the_column(capture,
                                                         monkeypatch):
    """Armed capture takes the column's first rows to the host as CSR (a
    few thousand rows) and never the column itself, nor a dense copy."""
    from flink_ml_tpu.observability import drift, evaluation

    monkeypatch.setenv({"drift": drift.DRIFT_ENV,
                        "quality": evaluation.QUALITY_ENV}[capture], "1")
    module = drift if capture == "drift" else evaluation
    seen = []
    real = module.capture_fit_baseline

    def spy(model, algo, **kw):
        seen.append(kw.get("features", kw.get("scores")))
        return real(model, algo, **kw)

    monkeypatch.setattr(module, "capture_fit_baseline", spy)
    monkeypatch.setenv(drift.SAMPLE_ROWS_ENV, "500")
    table, _ = lr_table(on_mesh(1))
    model = estimator().fit(table)
    baseline = getattr(model, f"{capture}_baseline")
    assert baseline is not None
    sample, = seen
    if capture == "drift":
        assert sp.issparse(sample) and sample.shape == (500, 1 << 10)
    else:
        assert len(sample) == 500


@pytest.mark.parametrize("devices", [1, 4])
def test_table_operations_keep_the_column_on_the_device(devices):
    mesh = on_mesh(devices)
    ids, vals, y = sparse_table(1 << 10)
    table = Table.from_columns(features=column_on(mesh, ids, vals, 1 << 10),
                               label=labels_on(mesh, y))
    col = table.column("features")
    assert len(table) == N and col.shape == (N, 1 << 10)
    assert col.hot == ((0, 7), (1, 11), (2, 7))
    # 1,024 buckets: every other position is narrow
    assert [j for j, _ in col.narrow] == list(range(HOT, K))
    for other in (table.select("features", "label"),
                  table.with_columns(extra=labels_on(mesh, y)),
                  table.take(slice(4_000, 6_000)), table.head(100),
                  table.take(np.array([5, 3, 19_999]))):
        kept = other.column("features")
        assert isinstance(kept, sparse.DeviceSparseColumn)
        assert isinstance(kept.ids, jax.Array) and kept.hot == col.hot
        assert kept.narrow == col.narrow and kept.dicts is col.dicts
    assert table.select("features").column("features") is col
    window = table.take(slice(4_000, 6_000)).column("features")
    np.testing.assert_array_equal(np.asarray(window.ids), ids[4_000:6_000])
    with pytest.raises(TypeError, match="to_csr"):
        np.asarray(col)
    assert sparse.is_sparse_column(col)
    assert sparse.features_matrix(table, "features",
                                  device_sparse=True) is col
    host = sparse.features_matrix(table, "features")
    assert (abs(host - csr_of(ids, vals, 1 << 10)) > 1e-6).nnz == 0
    first = table.head(2).rows()[1][0]      # the host view: SparseVectors
    np.testing.assert_allclose(first.to_array(),
                               csr_of(ids, vals, 1 << 10)[1].toarray()[0],
                               rtol=1e-6)


def test_the_entry_refuses_what_it_cannot_keep():
    mesh = on_mesh(1)
    ids, vals, _ = sparse_table(1 << 10)
    with pytest.raises(ValueError, match="outside"):
        column_on(mesh, ids, vals, 1 << 9)
    bad = ids.copy()
    bad[5, 20] = -1
    with pytest.raises(ValueError, match="outside"):
        column_on(mesh, bad, vals, 1 << 10)
    with pytest.raises(TypeError):
        sparse.device_sparse_column(ids, vals, 1 << 10)  # host arrays
    with pytest.raises(TypeError):
        sparse.device_sparse_column(jnp.asarray(ids),
                                    jnp.asarray(vals, jnp.bfloat16), 1 << 10)
    assert column_on(mesh, ids[:, HOT:], vals[:, HOT:], 1 << 10).hot == ()


# -- programs, spans, counters ------------------------------------------------

def test_a_warm_fit_builds_nothing(monkeypatch):
    from test_kmeans_warm_fit import BUILDS
    from test_optimizer_warm_fit import Watch

    mesh = on_mesh(4)
    ids, vals, y = sparse_table(1 << 10)
    column, labels = column_on(mesh, ids, vals, 1 << 10), labels_on(mesh, y)
    sgd = SGD(CASES["l2"].params())
    first, _ = sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(1 << 10),
                                   column, labels, mesh=mesh)
    assert sgd.last_check == (N, ())        # the first fit checks its rows
    watch = Watch(monkeypatch, events=BUILDS)
    with watch():
        again, _ = sgd.optimize_sparse(BinaryLogisticLoss(),
                                       np.zeros(1 << 10), column, labels,
                                       mesh=mesh)
    assert watch.jits == [] and watch.requests == 0
    assert sgd.last_check == (0, ())       # ... and the second none
    # the ids, the values and the label are where they are: puts that move
    # nothing, under placement alone
    assert {span for span, _ in watch.puts} <= {"sgd.place_inputs"}
    assert len(watch.puts) <= 3
    np.testing.assert_array_equal(again, first)


def test_a_traced_fit_compiles_nothing_more(monkeypatch):
    """Naming the gradient's operations on a recording ``sgd.launch`` reads
    the executable the warm fit compiled: no backend compile, once a program
    and signature."""
    from jax import monitoring

    mesh = on_mesh(1)
    size = 1 << 18
    ids, vals, y = mixed_table(size)
    column, labels = column_on(mesh, ids, vals, size), labels_on(mesh, y)
    sgd = SGD(CASES["hot-narrow-and-wide"].params())
    sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(size), column, labels,
                        mesh=mesh)
    compiles, armed = [], [True]
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: armed[0] and compiles.append(event))
    monkeypatch.setattr(tracer, "keep_recent", True)
    try:
        for _ in range(2):
            sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(size), column,
                                labels, mesh=mesh)
    finally:
        armed[0] = False    # jax keeps the listener; it counts no more
    assert "/jax/core/compile/backend_compile_duration" not in compiles
    first, again = [r["attrs"]["gradient_ops"] for r in tracer.recent
                    if r["name"] == "sgd.launch"]
    assert first == again and first


def test_the_index_and_a_fit_s_check_are_cold_spans(monkeypatch):
    """Recorded with nothing armed: ``sparse:index`` as the column is made,
    ``sparse:check`` as its first fit checks the rows it reads, and nothing
    as a second fit reads the same rows; the ring stays empty."""
    monkeypatch.setattr(tracer, "cold", [])
    assert not tracer.active
    mesh = on_mesh(4)
    ids, vals, y = sparse_table(1 << 10)
    col, labels = column_on(mesh, ids, vals, 1 << 10), labels_on(mesh, y)
    def attrs(record, *names):
        # a span inside which a program compiles also carries its compile
        # times: whether one does depends on what the process built before
        return {name: record["attrs"][name] for name in names}

    index, = [r for r in tracer.cold if r["name"] == "sparse:index"]
    assert attrs(index, "rows", "entries", "proposed") == {
        "rows": N, "entries": K, "proposed": K - HOT}
    sgd = SGD(dataclasses.replace(CASES["l2"], rounds=2).params())
    for _ in range(2):
        sgd.optimize_sparse(BinaryLogisticLoss(), np.zeros(1 << 10), col,
                            labels, mesh=mesh)
    check, = [r for r in tracer.cold if r["name"] == "sparse:check"]
    # two rounds of 500 rows on each of four shards
    assert attrs(check, "rows", "positions", "demoted") == {
        "rows": 4_000, "positions": K - HOT, "demoted": 0}
    assert len(tracer.recent) == 0


@pytest.mark.parametrize("devices", [1, 4])
def test_the_spans_and_counters_of_a_fit(devices, monkeypatch):
    mesh = on_mesh(devices)
    table, _ = lr_table(mesh)
    lr = estimator()
    lr.fit(table)                            # warm, nobody looking
    assert len(tracer.recent) == 0
    group = metrics.group(ML_GROUP, "sgd")
    before = {k: group.get_counter(k) for k in ("batchReads",
                                                "sparseEntries",
                                                "dictEntries",
                                                "dictRowsChecked",
                                                "dictDemoted")}
    monkeypatch.setattr(tracer, "keep_recent", True)
    lr.fit(table)
    records = list(tracer.recent)
    root, = [r for r in records if r["parent"] is None]
    assert root["name"] == "LogisticRegression.fit"
    names = [r["name"] for r in records if r["parent"] == root["id"]]
    assert sorted(names) == ["fit.extract", "fit.model", "sgd.optimize"]
    opt, = [r for r in records if r["name"] == "sgd.optimize"]
    under = sorted(r["name"] for r in records if r["parent"] == opt["id"])
    assert under == ["sgd.build_program", "sgd.fetch", "sgd.health",
                     "sgd.init_carry", "sgd.launch", "sgd.place_inputs"]
    # 1,024 buckets: the 36 positions that are not hot are narrow, and the
    # warm fit checked the rows this one reads
    assert opt["attrs"] == {
        "rounds": 8, "shards": devices, "weights": "unit", "batch": "onchip",
        "form": "split-dict-scatter", "narrow": K - HOT,
        "checked_rows": 0, "demoted": 0,
        "path": "sparse-device", "batch_reads": 8,
        "entries": 8 * 2_000 * K, "dict_entries": 8 * 2_000 * (K - HOT)}
    assert not any(r["name"].startswith("sparse:") for r in records)
    launch, = [r for r in records if r["name"] == "sgd.launch"]
    # the compiled program's operations under the gradient's scope, its
    # scatter-adds among them
    named = launch["attrs"].pop("gradient_ops")
    assert any("scatter" in op for op in named)
    assert launch["attrs"] == {"start": "fresh", "batch": "onchip"}
    moved = {k: group.get_counter(k) - v for k, v in before.items()}
    # a task's window is 2000 / devices rows of 39 entries, every round
    assert moved == {"batchReads": 8, "sparseEntries": 8 * 2_000 * K,
                     "dictEntries": 8 * 2_000 * (K - HOT),
                     "dictRowsChecked": 0, "dictDemoted": 0}


# -- the dense programs keep their text ---------------------------------------

ONCHIP_CASES = [("segment-fresh", "one-device"), ("segment-fresh", "data-4"),
                ("segment-carried", "data-4"), ("round", "one-device")]


def onchip_lowered_text(program, mesh_name, n=400, d=8):
    """The lowered text of a dense fit's program with its window read once
    on chip, as the LR cells run it: the plain fit's fresh start with no
    weight column, a carried adam segment, a host-driven round."""
    mesh = _unit_mesh(mesh_name)
    if program == "round":
        prog = jax.jit(opt_mod._build_sgd_round_program(
            BinaryLogisticLoss, mesh, UNIT_PRM, weighted=False))
        return prog.lower(*_carry_shapes(mesh, "sgd", False, n, d,
                                         False)).as_text()
    method = "adam" if program == "segment-carried" else "sgd"
    prm = dataclasses.replace(UNIT_PRM, method=method)
    fresh = program == "segment-fresh"
    prog = opt_mod._build_sgd_segment_program(
        BinaryLogisticLoss, mesh, prm, fused=True, weighted=False,
        fresh=fresh)
    operands = _carry_shapes(mesh, method, False, n, d, False)
    operands = (operands[:4] if fresh
                else operands + [np.int32(0), np.int32(5)])
    return prog.lower(*operands).as_text()


@pytest.mark.parametrize("program,mesh_name", ONCHIP_CASES,
                         ids=map("-".join, ONCHIP_CASES))
def test_a_dense_fit_lowers_to_the_parents_text(program, mesh_name):
    with open(ONCHIP_LOWERED) as f:
        want = json.load(f)["programs"]["-".join((program, mesh_name))]
    text = onchip_lowered_text(program, mesh_name)
    assert len(text) == want["characters"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]


# -- the cell's size on a v5e, ahead of time ----------------------------------

@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e can be described here: {exc}")


#: the click-through column's narrow index as ``device_sparse_column``
#: proposes it in the cell's table (``CriteoHashedGenerator``, 2^18
#: buckets): C1, C2, C5, C6, C8, C9, C11, C13, C14, C17, C18, C19, C20, C22,
#: C23 and C25, each field's cardinality to 8 (its sample holds at most as
#: many buckets)
CELL_NARROW = ((13, 1464), (14, 584), (17, 304), (18, 24), (20, 632),
               (21, 8), (23, 5688), (25, 3200), (26, 32), (29, 16),
               (30, 5656), (31, 2176), (32, 8), (34, 24), (35, 16),
               (37, 112))


def test_the_cell_s_fit_keeps_its_temporaries_small_on_a_v5e(v5e):
    """23M rows of 39 entries in 2^18 buckets, the published 20 rounds of
    100,000 rows: the table stays where it lies (no ``(n, k)`` copy, 3.68 GB
    an array), the window's 10 wide positions are made on chip, the narrow
    ones' ``(slots, rows)`` compares stay inside their reductions, and the
    program's temporaries are under 0.5 GB."""
    n, size = 23_000_000, 1 << 18
    mesh = create_mesh(devices=[v5e])
    prog = opt_mod._build_sgd_segment_program(
        BinaryLogisticLoss, mesh, SGDParams(
            learning_rate=0.1, global_batch_size=100_000, max_iter=20,
            tol=1e-6),
        fused=True, weighted=False, fresh=True,
        sparse=sparse_window.Layout(
            size, tuple((j, 5 + j) for j in range(13)), CELL_NARROW))
    compiled = prog.lower(
        (_shape(mesh, (n, K), P("data", None), jnp.int32),
         _shape(mesh, (n, K), P("data", None)),
         _shape(mesh, (K, sparse_window.NARROW_MAX), P(), jnp.int32)),
        _shape(mesh, (n,), P("data")), None,
        _shape(mesh, (size,), P())).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    for dtype in ("s32", "f32"):
        assert f"{dtype}[{n},39]{{0,1:T(8,128)}}" in text
    assert "[10,100000]{1,0:T(8,128)S(1)}" in text  # the wide window on chip
    # ``benchmarks/harness/readers/sparse_gradient_device_ms.py`` times the
    # operations the program names under the gradient's scope: among them
    # the wide entries' scatter-add and the hot and dictionary sums' add,
    # each into the 2^18 buckets, and nothing of the margins'
    named = sparse_window.gradient_ops(text)
    lines = {ln.split(" = ")[0].split("%")[-1]: ln
             for ln in text.splitlines() if " = " in ln}
    adds = [op for op in named if lines[op].split(" = ")[1].startswith(
        "f32[262144]") and "sgd.sparse_gradient/scatter-add" in lines[op]]
    assert len(adds) == 2
    assert all("sgd.sparse_margins" not in lines[op] for op in named)
    # ``benchmarks/harness/readers/sparse_grad_device_ms.py`` times these
    # two names: each is a gradient's add into the 2^18 buckets (the wide
    # entries' scatter, the hot and dictionary sums' add) or absent, never
    # another operation
    for op in ("fusion.20", "fusion.21"):
        for line in (ln for ln in text.splitlines() if f" %{op} = " in ln):
            assert line.split(" = ")[1].startswith("f32[262144]")
            assert "sgd.sparse_gradient/scatter-add" in line


def test_the_cell_s_index_and_check_keep_their_temporaries_small_on_a_v5e(
        v5e):
    """The index over the cell's 23M rows keeps one position's sample, not a
    ``(k, n)`` or ``(k, SAMPLE_ROWS)`` copy; the first fit's check of the 2M
    rows the published 20 rounds of 100,000 read, against the 16
    dictionaries, keeps its compares inside their reductions. Each under
    0.05 GB of temporaries beside the 3.68 GB ids."""
    n = 23_000_000
    mesh = create_mesh(devices=[v5e])
    ids = _shape(mesh, (n, K), P("data", None), jnp.int32)
    index = sparse._index_program().lower(ids).compile()
    assert index.memory_analysis().temp_size_in_bytes < 0.05e9
    check = sparse._check_program(mesh, CELL_NARROW, 2_000_000).lower(
        ids, _shape(mesh, (K, sparse_window.NARROW_MAX), P(), jnp.int32),
        np.int32(0), np.int32(n)).compile()
    assert check.memory_analysis().temp_size_in_bytes < 0.05e9
    assert f"s32[{n},39]{{0,1:T(8,128)}}" in check.as_text()


def write_sparse_lowered(path, commit):
    text = sparse_lowered_text()
    with open(path, "w") as f:
        json.dump({"commit": commit, "programs": {
            "split-scatter-one-device": {
                "characters": len(text),
                "sha256": hashlib.sha256(text.encode()).hexdigest()}}},
            f, indent=1)
        f.write("\n")


def write_onchip_lowered(path, commit):
    out = {"commit": commit, "programs": {}}
    for case in ONCHIP_CASES:
        text = onchip_lowered_text(*case)
        out["programs"]["-".join(case)] = {
            "characters": len(text),
            "sha256": hashlib.sha256(text.encode()).hexdigest()}
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    writer = (write_sparse_lowered
              if os.path.basename(sys.argv[1]) == "sparse_lowered.json"
              else write_onchip_lowered)
    writer(sys.argv[1], sys.argv[2])
