"""The NaiveBayes fit's counting: the device path (``ops/contingency.py``,
path ``mxu-counts``) against plain NumPy on seeded tables — ragged row
counts, a width that is no multiple of 8, arities 2, 20 and 300, labels and
values that no row has, one device and four, the XLA form and the Pallas
kernel (interpreted): the counts equal exactly, theta and pi to 1e-12, and
the host path (``host-counts``) the same model; a table the device path
cannot count (a value that is not whole, a negative one) goes to the host
with the same answer, and one whose first rows do not show its range is
looked at whole and counted again; the counting program holds no scatter
and no ``(n, d)`` integer array; a warm fit builds nothing; and the spans
of a fit are one tree under ``NaiveBayes.fit``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.classification import naivebayes as nb
from flink_ml_tpu.models.classification.naivebayes import (
    NaiveBayes, NaiveBayesModel)
from flink_ml_tpu.observability import tracing
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import contingency
from flink_ml_tpu.ops import pallas_kernels as pk
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh
from test_kmeans_lowering import eqns
from test_kmeans_warm_fit import BUILDS
from test_optimizer_warm_fit import Watch

ARRAYS = ("theta", "values", "pi", "labels", "floors")


@pytest.fixture(autouse=True)
def restore_state(monkeypatch):
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    tracer.recent.clear()
    yield
    set_default_mesh(None)
    tracer.recent.clear()


def on_mesh(devices):
    set_default_mesh(create_mesh(devices=jax.devices()[:devices]))


def make(n, d, f_arity, l_arity, seed=0, skip_value=None, skip_label=None):
    """A seeded table of whole numbers; ``skip_value`` and ``skip_label``
    are in range and in no row."""
    rng = np.random.default_rng(seed)
    x = np.floor(rng.random((n, d)) * f_arity)
    y = np.floor(rng.random(n) * l_arity)
    if skip_value is not None:
        x[x == skip_value] = 0.0
        x[0, 0] = f_arity - 1           # the range is still the arity's
    if skip_label is not None:
        y[y == skip_label] = l_arity - 1
    return x, y


def numpy_model(x, y, smoothing=1.0):
    """The equations of the module's docstring, row by row, in float64."""
    n, d = x.shape
    labels = np.unique(y)
    doc = np.array([(y == l).sum() for l in labels], np.float64)
    per_feature = [np.unique(x[:, j]) for j in range(d)]
    width = max(map(len, per_feature))
    values = np.full((d, width), np.nan)
    counts = np.zeros((d, len(labels), width))
    for j, vals in enumerate(per_feature):
        values[j, :len(vals)] = vals
        for li, l in enumerate(labels):
            for k, v in enumerate(vals):
                counts[j, li, k] = np.sum((y == l) & (x[:, j] == v))
    distinct = np.array([len(v) for v in per_feature], np.float64)
    denom = np.log(doc[:, None] + smoothing * distinct[None, :])
    theta = np.log(counts.transpose(1, 0, 2) + smoothing) - denom[:, :, None]
    return {"counts": counts, "theta": theta, "values": values,
            "pi": (np.log(doc * d + smoothing)
                   - np.log(n * d + len(labels) * smoothing)),
            "labels": labels, "floors": np.log(smoothing) - denom}


def device_table(x, y):
    return Table.from_columns(features=jnp.asarray(x, jnp.float32),
                              label=jnp.asarray(y, jnp.float32))


def fit_device(x, y, **params):
    est = NaiveBayes(**params)
    model = est.fit(device_table(x, y))
    return model, est.last_execution_path


def assert_same_model(got, want, rtol=1e-12):
    for name in ARRAYS:
        a = getattr(got, name) if not isinstance(got, dict) else got[name]
        b = getattr(want, name) if not isinstance(want, dict) else want[name]
        np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-12,
                                   equal_nan=True, err_msg=name)


#: (rows, features, feature arity, label arity, value absent, label absent)
TABLES = [(403, 6, 5, 3, None, None), (1000, 100, 20, 10, None, None),
          (257, 7, 2, 2, None, None), (900, 3, 300, 4, None, None),
          (611, 9, 20, 10, 7, 4), (130, 2, 6, 5, 3, 0)]


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("table", TABLES, ids=lambda t: "-".join(map(str, t)))
def test_the_device_path_counts_as_numpy_does(table, devices):
    n, d, f_arity, l_arity, skip_value, skip_label = table
    on_mesh(devices)
    x, y = make(n, d, f_arity, l_arity, seed=n, skip_value=skip_value,
                skip_label=skip_label)
    want = numpy_model(x, y)
    model, path = fit_device(x, y)
    assert path == "mxu-counts"
    assert_same_model(model, want)
    host_est = NaiveBayes()
    host = host_est.fit(Table.from_columns(features=x, label=y))
    assert host_est.last_execution_path == "host-counts"
    assert_same_model(host, want)
    assert_same_model(model, host, rtol=1e-13)
    if skip_value is not None:
        assert skip_value not in model.values[1:]
    if skip_label is not None:
        assert skip_label not in model.labels
    t = Table.from_columns(features=x)
    np.testing.assert_array_equal(model.transform(t)[0]["prediction"],
                                  host.transform(t)[0]["prediction"])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("program", ["xla", "pallas"])
@pytest.mark.parametrize("shape", [(403, 6, 3, 5), (2500, 100, 10, 20),
                                   (77, 7, 2, 2), (300, 3, 4, 301)],
                         ids=lambda s: "-".join(map(str, s)))
def test_the_counting_programs_count_exactly(shape, program, devices,
                                             request):
    """Both forms, entry for entry, against a loop: the ragged rows past
    ``n_valid`` and the entries out of range counted nowhere."""
    if program == "pallas":
        request.getfixturevalue("interpreted_kernels")
    n, d, labels, values = shape
    x, y = make(n, d, values, labels, seed=d)
    x, y = x.astype(np.float32), y.astype(np.float32)
    x[3, 1], x[4, 0], x[5, 1], x[6, 1] = 0.5, -1.0, values, np.nan
    y[7], y[8] = labels, 0.25
    n_valid = n - 5
    pad = (-n) % devices
    mesh = create_mesh(devices=jax.devices()[:devices])
    got = np.asarray(contingency.counts_program(
        mesh, labels, values, program == "pallas")(
            jnp.pad(jnp.asarray(x), ((0, pad), (0, 0))),
            jnp.pad(jnp.asarray(y), (0, pad)), np.int32(n_valid)))
    want = np.zeros((values, labels, d), np.int64)
    for i in range(n_valid):
        if y[i] in range(labels):
            for j in range(d):
                if x[i, j] in range(values):
                    want[int(x[i, j]), int(y[i]), j] += 1
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert got.sum() < n_valid * d      # what the fit's check reads


def test_a_tile_of_one_cell_packs_two_exact_counts(interpreted_kernels):
    """The kernel's packed sum at its limit: a whole tile (2048 rows) of
    one label and one value, even or odd, and a tile half and half."""
    tile = pk.COUNTS_TILES_N[0]
    assert tile * pk.COUNTS_ODD_WEIGHT < 2 ** 24 and tile < pk.COUNTS_ODD_WEIGHT
    for fill in ([0.0], [1.0], [0.0, 1.0], [2.0, 3.0]):
        x = np.resize(np.asarray(fill, np.float32), (3 * tile, 2))
        x[:, 1] = x[::-1, 0]
        got = np.asarray(pk.category_counts(
            x, np.zeros(3 * tile, np.float32), 3 * tile, 1, 4))
        for v in range(4):
            assert got[v, 0, 0] == np.sum(x[:, 0] == v)
            assert got[v, 0, 1] == np.sum(x[:, 1] == v)
        assert got.sum() == 3 * tile * 2


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("spoil", ["fraction", "negative", "nan-free-wide"])
def test_a_table_the_device_cannot_count_goes_to_the_host(spoil, devices):
    """A value that is not whole, a negative one, a range past the device
    path's: ``host-counts``, and the answer the host gives for the table."""
    on_mesh(devices)
    x, y = make(403, 6, 5, 3)
    x[300, 2] = {"fraction": 2.5, "negative": -1.0,
                 "nan-free-wide": float(nb._MAX_DEVICE_ARITY)}[spoil]
    model, path = fit_device(x, y)
    assert path == "host-counts"
    assert_same_model(model, numpy_model(
        x.astype(np.float32).astype(np.float64), y))


@pytest.mark.parametrize("devices", [1, 4])
def test_a_range_the_first_rows_do_not_show_is_found_and_counted(
        devices, monkeypatch):
    """The guess at ``L`` and ``V`` comes from the first rows of every
    shard; the counts themselves say when it was short, and the table is
    then looked at whole and counted again: one more look, one more pass,
    the same model."""
    on_mesh(devices)
    monkeypatch.setattr(nb, "_LOOK_ROWS", 16)
    x, y = make(403, 6, 5, 3)
    x[90, 2], y[95] = 11.0, 6.0         # past the first 16 rows of a shard
    monkeypatch.setattr(tracer, "keep_recent", True)
    model, path = fit_device(x, y)
    assert path == "mxu-counts"
    assert_same_model(model, numpy_model(x, y))
    names = [r["name"] for r in tracer.recent]
    assert names.count("nb.check") == names.count("nb.launch") == 2
    launches = [r["attrs"] for r in tracer.recent if r["name"] == "nb.launch"]
    assert [(a["labels"], a["values"], a["passes"]) for a in launches] == [
        (3, 5, 1), (7, 12, 3)]


def test_smoothing_zero_gives_infinite_floors_and_no_warning():
    x, y = make(200, 3, 4, 2)
    x[y == 0, 0] = 1.0                  # value 1 of feature 0: label 0 only
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        model, _ = fit_device(x, y, smoothing=0.0)
    assert np.isneginf(model.floors).all()
    assert_same_model(model, NaiveBayes(smoothing=0.0).fit(
        Table.from_columns(features=x, label=y)))


# -- the programs, as traced and lowered --------------------------------------

@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "pallas"])
def test_the_counting_program_scatters_nothing_and_keys_nothing(
        use_kernel, devices, request):
    """No scatter in the lowered text, and in the traced program no integer
    array with as many rows as the table: the counts come from compares
    and products, tile by tile, where the table lies."""
    if use_kernel:
        request.getfixturevalue("interpreted_kernels")
    n, d, labels, values = 40_000, 100, 10, 20
    mesh = create_mesh(devices=jax.devices()[:devices])
    program = contingency.counts_program(mesh, labels, values, use_kernel)
    args = (jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))
    assert "scatter" not in program.lower(*args).as_text()
    traced = jax.make_jaxpr(program)(*args)
    products = 0
    for eqn in eqns(traced.jaxpr):
        assert "scatter" not in eqn.primitive.name
        products += eqn.primitive.name == "dot_general"
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if (jnp.issubdtype(var.aval.dtype, jnp.integer)
                    and len(shape) >= 2):
                assert n // devices not in shape, (eqn.primitive, shape)
    assert products >= 1
    contingency.counts_program.cache_clear()    # built under the patch


# -- a warm fit builds nothing -------------------------------------------------

@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("program", ["xla", "pallas"])
def test_a_warm_fit_builds_nothing(program, devices, monkeypatch, request):
    if program == "pallas":
        request.getfixturevalue("interpreted_kernels")
    on_mesh(devices)
    watch = Watch(monkeypatch, module=nb, events=BUILDS)
    x, y = make(403, 6, 5, 3)
    table = device_table(x, y)
    first = NaiveBayes().fit(table)
    with watch():
        again = NaiveBayes().fit(table)
    watch.armed = False
    assert watch.jits == [] and watch.requests == 0
    # nothing is placed but the inputs (a ragged table is padded by a
    # cached program in place of a put); the row count rides the call
    assert {span for span, _ in watch.puts} <= {"nb.place_inputs"}
    assert_same_model(again, first, rtol=0)


# -- the fit's own spans -------------------------------------------------------

#: span -> how often under the root, on the device path and on the host's
TREE = {"mxu-counts": {"nb.place_inputs": 1, "nb.check": 1,
                       "nb.build_program": 1, "nb.launch": 1, "nb.fetch": 1,
                       "nb.finalize": 1, "fit.model": 1},
        "host-counts": {"nb.launch": 1, "nb.finalize": 1, "fit.model": 1}}


@pytest.mark.parametrize("devices,path", [(1, "mxu-counts"),
                                          (4, "mxu-counts"),
                                          (1, "host-counts")])
def test_the_spans_of_a_fit_are_one_tree_under_its_root(devices, path,
                                                        monkeypatch):
    on_mesh(devices)
    x, y = make(403, 6, 5, 3)
    table = (device_table(x, y) if path == "mxu-counts"
             else Table.from_columns(features=x, label=y))
    est = NaiveBayes()
    est.fit(table)                          # warm, and nobody looking:
    assert len(tracer.recent) == 0          # nothing recorded
    before = metrics.group(ML_GROUP, "iteration").snapshot()["counters"]
    monkeypatch.setattr(tracer, "keep_recent", True)
    est.fit(table)
    assert est.last_execution_path == path
    records = list(tracer.recent)
    assert len({r["trace"] for r in records}) == 1
    root, = [r for r in records if r["parent"] is None]
    assert root["name"] == "NaiveBayes.fit"
    assert root["attrs"]["kind"] == "fit"
    children = [r for r in records if r["parent"] == root["id"]]
    names = [r["name"] for r in children]
    assert {n: names.count(n) for n in set(names)} == TREE[path]
    assert sum(r["dur_us"] for r in children) <= root["dur_us"]
    launch = next(r for r in children if r["name"] == "nb.launch")
    if path == "mxu-counts":
        assert launch["attrs"] == {
            "path": "mxu-counts", "rows": 403, "d": 6, "labels": 3,
            "values": 5, "passes": 1, "program": "xla"}
        after = metrics.group(ML_GROUP, "iteration").snapshot()["counters"]
        # the look's four numbers, then the counts: a leaf a wait, the
        # second program's shape waits for the first read
        assert [after[name] - before.get(name, 0) for name in
                ("boundaryFetches", "boundaryWaits")] == [2, 2]
        state = metrics.group(ML_GROUP, "update").snapshot()["gauges"]
        assert any("NaiveBayes" in key and value == 5 * 3 * 6 * 4
                   for key, value in state.items()), state
    else:
        assert launch["attrs"] == {"path": "host-counts", "rows": 403,
                                   "d": 6, "passes": 1}


# -- the model data ------------------------------------------------------------

def test_model_data_is_numeric_columns_and_round_trips(tmp_path):
    x, y = make(403, 6, 5, 3)
    x[x[:, 0] == 2, 0] = 0.0                    # feature 0 has four values
    model, _ = fit_device(x, y)
    (data,) = model.get_model_data()
    assert data.num_rows == 1
    shapes = {name: np.asarray(data.column(name)).shape
              for name in data.column_names}
    assert shapes == {"theta": (1, 3, 6, 5), "values": (1, 6, 5),
                      "piArray": (1, 3), "labels": (1, 3),
                      "floors": (1, 3, 6)}
    for name in data.column_names:
        assert np.asarray(data.column(name)).dtype == np.float64
    assert np.isnan(model.values).sum() > 0     # a feature with fewer values
    # where a feature has fewer values, theta holds the floor
    pad = np.isnan(model.values)
    np.testing.assert_array_equal(
        model.theta[:, pad], np.broadcast_to(
            model.floors[:, :, None], model.theta.shape)[:, pad])
    t = Table.from_columns(features=np.vstack([x, [[9.0] * 6]]))
    want = model.transform(t)[0]["prediction"]
    fresh = NaiveBayesModel().set_model_data(data)
    np.testing.assert_array_equal(fresh.transform(t)[0]["prediction"], want)
    model.save(str(tmp_path / "nb"))
    loaded = NaiveBayesModel.load(str(tmp_path / "nb"))
    assert_same_model(loaded, model, rtol=0)
    np.testing.assert_array_equal(loaded.transform(t)[0]["prediction"], want)
