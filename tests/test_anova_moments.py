"""The ANOVA F-test's grouped moments (``ops/stats.py``,
``ops/fixedpoint.py``, ``pallas_kernels.grouped_moments``) and the selector
that fits by them: the program against a float64 two-pass computation and
against ``scipy.stats.f_oneway`` on seeded tables; one device against a
four-device mesh bit for bit; the host path where the table does not
qualify; a warm fit that builds nothing; the lowered program's text; a
process's first fit inside its budget of programs; the model's statistics
through ``get_model_data``, ``set_model_data`` and save / load."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats as sstats

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.feature.selectors import (
    UnivariateFeatureSelector, UnivariateFeatureSelectorModel)
from flink_ml_tpu.models.stats.tests import ANOVATest
from flink_ml_tpu.observability import tracing
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import fixedpoint, pallas_kernels, stats
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh
from test_kmeans_warm_fit import BUILDS
from test_optimizer_warm_fit import Watch

ROOT = os.path.join(os.path.dirname(__file__), "..")
CLASSES = 5


def drop_programs():
    stats.moments_look_program.cache_clear()
    stats.moments_program.cache_clear()


@pytest.fixture(autouse=True)
def restore_state(monkeypatch):
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    drop_programs()
    tracer.recent.clear()
    yield
    set_default_mesh(None)
    drop_programs()
    tracer.recent.clear()


def on_mesh(devices):
    mesh = create_mesh(devices=jax.devices()[:devices])
    set_default_mesh(mesh)
    return mesh


def labels_of(n, rng, classes=CLASSES):
    return rng.integers(0, classes, n).astype(np.float32)


#: name -> (rows, features): the tables of the satellite's list
def table_of(name, rng):
    n, d = {"ragged": (4099, 7), "blocks": (20_003, 5)}.get(name, (6000, 6))
    y = labels_of(n, rng)
    x = {
        "zeros-and-ones": lambda: rng.integers(0, 2, (n, d)),
        "continuous": lambda: rng.random((n, d)),
        "normal": lambda: rng.normal(size=(n, d)) * [1, 10, 0.1, 3, 1, 100],
        # a column whose mean dwarfs its spread, a constant one, and one
        # that the label does say something about
        "shifted": lambda: np.stack([
            1e4 + rng.normal(size=n), np.full(n, 3.25), y + rng.normal(
                size=n), rng.random(n), -1e4 + rng.random(n),
            1e-3 * rng.normal(size=n)], axis=1),
        "heavy-tail": lambda: rng.lognormal(sigma=2.0, size=(n, d)),
        "ragged": lambda: rng.random((n, d)),
        "blocks": lambda: rng.normal(size=(n, d)),
    }[name]().astype(np.float32)
    if name == "shifted":           # and a class with no rows
        y = np.where(y == 2, 4, y).astype(np.float32)
    if name == "heavy-tail":        # past what the look's 4,096 rows show
        x[5000] *= 1e4
    return x, y


TABLES = ("zeros-and-ones", "continuous", "normal", "shifted", "heavy-tail",
          "ragged", "blocks")


def two_pass(x, y):
    """F, p and the within-class degrees of freedom by the textbook two-pass
    form in float64 over the float32 values."""
    x = np.asarray(x, np.float64)
    classes = np.unique(y)
    grand = x.mean(axis=0)
    ssb = np.zeros(x.shape[1])
    ssw = np.zeros(x.shape[1])
    for c in classes:
        rows = x[y == c]
        mean = rows.mean(axis=0)
        ssb += len(rows) * (mean - grand) ** 2
        ssw += ((rows - mean) ** 2).sum(axis=0)
    dfb, dfw = len(classes) - 1, len(x) - len(classes)
    with np.errstate(invalid="ignore", divide="ignore"):
        f = (ssb / dfb) / (ssw / dfw)
    return f, sstats.f.sf(f, dfb, dfw), dfw


def device_test(x, y, report=None):
    return stats.anova_f_test(jnp.asarray(x), jnp.asarray(y), report)


def gaps(found, wanted):
    f, p, _ = found
    ok = np.isfinite(wanted[0])
    assert np.array_equal(np.isnan(f), np.isnan(wanted[0]))
    return (np.max(np.abs(f[ok] - wanted[0][ok]) / wanted[0][ok]),
            np.max(np.abs(p[ok] - wanted[1][ok])))


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", TABLES)
def test_the_program_tests_what_two_passes_in_float64_do(name, devices):
    on_mesh(devices)
    x, y = table_of(name, np.random.default_rng(3))
    report = {}
    found = device_test(x, y, report)
    assert report["path"] == "grouped-moments"
    assert report["passes"] == (2 if name == "heavy-tail" else 1)
    f_gap, p_gap = gaps(found, two_pass(x, y))
    # a table on a grid of its own (whole numbers, multiples of 2**-24) is
    # added exactly and reads float64's own rounding; elsewhere an element
    # is held to 2**-33 of its column's reach
    # (the heavy tail's outlier is 10**7 of its column's spread)
    assert f_gap < {"zeros-and-ones": 1e-11, "heavy-tail": 1e-6}.get(
        name, 1e-8)
    assert p_gap < {"heavy-tail": 1e-6}.get(name, 1e-8)
    assert np.all(found[2] == len(x) - len(np.unique(y)))
    if name == "shifted":           # the constant column: 0 / 0, as scipy
        assert np.isnan(found[0][1]) and np.isnan(found[1][1])


@pytest.mark.parametrize("name", ["zeros-and-ones", "continuous", "shifted"])
def test_the_program_tests_what_scipy_does(name):
    on_mesh(1)
    x, y = table_of(name, np.random.default_rng(4))
    f, p, _ = device_test(x, y)
    x64 = x.astype(np.float64)
    for j in range(x.shape[1]):
        if name == "shifted" and j == 1:
            continue                # scipy warns on a constant column
        want = sstats.f_oneway(*[x64[y == c, j] for c in np.unique(y)])
        # (scipy's own one-pass form loses six digits on the columns whose
        # mean dwarfs their spread: the two-pass test above holds those)
        loose = name == "shifted" and j in (0, 4)
        assert f[j] == pytest.approx(want[0], rel=1e-4 if loose else 1e-8)
        assert p[j] == pytest.approx(want[1], rel=1e-4 if loose else 1e-7,
                                     abs=1e-12)


@pytest.mark.parametrize("name", TABLES)
def test_one_device_and_four_agree_bit_for_bit(name):
    x, y = table_of(name, np.random.default_rng(5))
    on_mesh(1)
    one = device_test(x, y)
    drop_programs()
    on_mesh(4)
    four = device_test(x, y)
    for a, b in zip(one, four):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fault,why", [
    ("fraction", "a label that is not a whole number"),
    ("negative", "a label under 0"),
    ("wide", "a label past the one-hot's width"),
    ("nan", "an entry that is not finite"),
    ("host", "a table on the host"),
])
def test_a_table_that_does_not_qualify_is_tested_on_the_host(fault, why):
    on_mesh(1)
    x, y = table_of("continuous", np.random.default_rng(6))
    if fault == "fraction":
        y[17] = 1.5
    elif fault == "negative":
        y[17] = -1.0
    elif fault == "wide":
        y[17] = float(stats._MAX_DEVICE_LABELS)
    elif fault == "nan":
        x[17, 2] = np.inf
    report = {}
    if fault == "host":
        found = stats.anova_f_test(x.astype(np.float64), y, report)
    else:
        found = device_test(x, y, report)
    assert report == {"path": "host-anova", "passes": 1}, why
    if fault != "nan":
        f_gap, p_gap = gaps(found, two_pass(x, y))
        assert f_gap < 1e-9 and p_gap < 1e-9


def test_host_labels_beside_a_device_table_are_placed_not_fetched():
    on_mesh(1)
    x, y = table_of("continuous", np.random.default_rng(7))
    report = {}
    found = stats.anova_f_test(jnp.asarray(x), y, report)
    assert report["path"] == "grouped-moments"
    for a, b in zip(found, device_test(x, y)):
        np.testing.assert_array_equal(a, b)


def test_the_digits_of_a_value_add_up_to_it():
    rng = np.random.default_rng(8)
    w = np.concatenate([rng.uniform(-1, 1, 4000), [1.0, -1.0, 0.0, 2.0 ** -30,
                                                   0.5 + 2.0 ** -24]]
                       ).astype(np.float32)
    # (the rounding by scale, round and scale back: XLA folds the magic
    # constants, which are the kernel's)
    parts = [np.asarray(p, np.float64) for p in jax.jit(
        lambda v: fixedpoint.fixed_digits(v, 4))(w)]
    for k, part in enumerate(parts, 1):
        units = part * 2.0 ** (8 * k)
        assert np.all(units == np.round(units))
        assert np.abs(units).max() <= (256 if k == 1 else 128)
    assert np.abs(sum(parts) - w).max() <= 2.0 ** -33


def test_units_add_up_with_their_carry():
    lo = hi = jnp.zeros((3,), jnp.int32)
    total = np.zeros(3, np.int64)
    rng = np.random.default_rng(9)
    for _ in range(200):
        units = rng.integers(-2 ** 20, 2 ** 20, 3).astype(np.int32)
        lo, hi = fixedpoint.add_units(lo, hi, jnp.asarray(units))
        total += units
        assert np.all((np.asarray(lo) >= 0) & (np.asarray(lo) < 2 ** 20))
    np.testing.assert_array_equal(
        np.asarray(lo, np.int64) + (np.asarray(hi, np.int64) << 20), total)


@pytest.mark.parametrize("n,d,valid", [(128 * 9, 12, 128 * 9),
                                       (128 * 9 + 50, 12, 1100),
                                       (2048 + 7, 3, 2048 + 7)])
def test_the_kernel_adds_what_the_xla_form_adds(n, d, valid):
    """Interpreted on the CPU (the chip's run is ``scripts/anova_forms.py``'s
    and ``scripts/tpu_kernel_check.py``'s): the same integers."""
    rng = np.random.default_rng(10)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = labels_of(n, rng)
    pivot = rng.normal(size=d).astype(np.float32)
    inv = np.full(d, 1 / 8, np.float32)
    want = stats.grouped_moments_xla(jnp.asarray(x), jnp.asarray(y), valid,
                                     pivot, inv, CLASSES, jnp.float32)
    got = pallas_kernels.grouped_moments(jnp.asarray(x), jnp.asarray(y),
                                         valid, pivot, inv, CLASSES,
                                         interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(got[2]).sum()) == valid


def test_pivots_and_scales_suit_their_columns():
    # columns within twice their reach of 0 keep their entries whole; a
    # far column gets the pivot beside it, few bits at the spread's height
    lo = np.asarray([0.0, 0.0, -4.0, 9996.0])
    hi = np.asarray([1.0, 0.9999, 4.2, 10004.0])
    places = np.asarray([0.5, 0.5, 0.49, 0.5]) * 4096 * stats._LOOK_STEPS
    pivot, scale = stats.pivot_and_scale(lo, hi, places, 4096)
    np.testing.assert_array_equal(pivot, [0.0, 0.0, 0.0, 10000.0])
    np.testing.assert_array_equal(scale, [2.0, 2.0, 16.0, 8.0])
    # a constant column, and one the look saw nothing finite of
    pivot, scale = stats.pivot_and_scale([3.25, np.nan], [3.25, np.nan],
                                         [0.0, np.nan], 4096)
    assert pivot[0] == 3.25 and np.all(np.isfinite(scale))


@pytest.mark.parametrize("devices", [1, 4])
def test_a_warm_fit_builds_nothing(devices, monkeypatch):
    on_mesh(devices)
    watch = Watch(monkeypatch, module=stats, events=BUILDS)
    x, y = table_of("ragged", np.random.default_rng(11))
    table = Table.from_columns(features=jnp.asarray(x), label=jnp.asarray(y))
    est = UnivariateFeatureSelector(
        feature_type="continuous", label_type="categorical",
        selection_threshold=3)
    first = est.fit(table)
    with watch():
        again = est.fit(table)
    watch.armed = False
    assert est.last_execution_path == "grouped-moments"
    assert watch.jits == [] and watch.requests == 0
    # nothing is placed but the two columns (a ragged table is padded by a
    # cached program in place of a put)
    assert {span for span, _ in watch.puts} <= {"anova.place_inputs"}
    assert len(watch.puts) <= 2
    np.testing.assert_array_equal(again.indices, first.indices)
    np.testing.assert_array_equal(again.p_values, first.p_values)


@pytest.mark.parametrize("devices", [1, 4])
def test_the_lowered_program_reads_the_table_where_it_lies(devices):
    """No bfloat16-operand product of the feature values (a digit is, the
    value never), no ``(n, L)`` or ``(n, d)`` temporary: what the table's
    length multiplies is the arguments alone."""
    mesh = on_mesh(devices)
    n, d = 8192 * 4 * devices, 6
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    column = jax.ShapeDtypeStruct((d,), jnp.float32)
    program = stats.moments_program(mesh, CLASSES, False)
    text = program.lower(x, y, jax.ShapeDtypeStruct((), jnp.int32), column,
                         column).as_text()
    local = n // devices
    assert f"tensor<{local}x{CLASSES}x" not in text
    assert f"tensor<{local}x{d}xbf16>" not in text
    # the only arrays a shard's rows long are the two arguments
    rows_long = {line.split("tensor<")[1].split(">")[0]
                 for line in text.splitlines()
                 if f"tensor<{local}x" in line or f"tensor<{local}>" in line
                 for _ in [0]}
    assert rows_long <= {f"{local}x{d}xf32", f"{local}xf32"}, rows_long
    # every product contracts a block's rows and takes whole digits
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == sum(fixedpoint.MOMENTS_DIGITS)
    assert all(f"{stats.XLA_BLOCK_ROWS}x" in line for line in dots)


#: what a process's first device-path fit may build: the look and the
#: moments program (the kernel's own ``jit`` where the backend takes it)
FIRST_FIT_PROGRAMS = 3

FIRST_FIT = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
made = []
real_jit = jax.jit
def jit(fn, *a, **k):
    out = real_jit(fn, *a, **k)
    made.append(out)
    return out
jax.jit = jit
import flink_ml_tpu
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.feature.selectors import UnivariateFeatureSelector
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh
set_default_mesh(create_mesh(devices=jax.devices()[:1]))
rng = np.random.default_rng(0)
n, d = 5000, 7
x = jnp.asarray(rng.random((n, d), dtype=np.float32))
y = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
before = len(made)
est = UnivariateFeatureSelector(feature_type="continuous",
                                label_type="categorical")
model = est.fit(Table.from_columns(features=x, label=y))
from flink_ml_tpu.observability.tracing import tracer
print(json.dumps({
    "path": est.last_execution_path, "jits": len(made) - before,
    "pallas": "jax.experimental.pallas" in sys.modules,
    "cold": [r["name"] for r in tracer.cold],
    "selected": len(model.indices)}))
"""


def test_a_process_s_first_fit_stays_inside_its_budget():
    """No clock: in a fresh process off the chip, one device-path fit
    imports no Pallas (the kernel is the path on a TPU alone) and makes at
    most ``FIRST_FIT_PROGRAMS`` ``jax.jit`` programs, each built once under
    its cold span. The seconds are the chip's (PERF.md section 6, PR 40)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", FIRST_FIT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    seen = json.loads(done.stdout.strip().splitlines()[-1])
    assert seen["path"] == "grouped-moments" and seen["selected"] == 7
    assert seen["pallas"] is False
    assert 1 <= seen["jits"] <= FIRST_FIT_PROGRAMS
    assert [n for n in seen["cold"] if n.startswith("build:")] == [
        "build:anova_look", "build:anova_moments"]
    assert "import:pallas" not in seen["cold"]


TREE = {"grouped-moments": {
            "anova.place_inputs": 1, "anova.check": 1,
            "anova.build_program": 1, "anova.launch": 1, "anova.fetch": 1,
            "anova.test": 2, "fit.model": 1},
        "host-anova": {"anova.launch": 1, "anova.test": 1, "fit.model": 1}}


@pytest.mark.parametrize("devices,path", [(1, "grouped-moments"),
                                          (4, "grouped-moments"),
                                          (1, "host-anova")])
def test_the_spans_of_a_fit_are_one_tree_under_its_root(devices, path,
                                                        monkeypatch):
    on_mesh(devices)
    x, y = table_of("ragged", np.random.default_rng(12))
    table = (Table.from_columns(features=jnp.asarray(x), label=jnp.asarray(y))
             if path == "grouped-moments" else
             Table.from_columns(features=x.astype(np.float64), label=y))
    est = UnivariateFeatureSelector(
        feature_type="continuous", label_type="categorical",
        selection_threshold=3)
    est.fit(table)                          # warm, and nobody looking:
    assert len(tracer.recent) == 0          # nothing recorded
    groups = ("iteration", "anova")
    before = {g: metrics.group(ML_GROUP, g).snapshot()["counters"]
              for g in groups}
    monkeypatch.setattr(tracer, "keep_recent", True)
    est.fit(table)
    assert est.last_execution_path == path
    records = list(tracer.recent)
    assert len({r["trace"] for r in records}) == 1
    root, = [r for r in records if r["parent"] is None]
    assert root["name"] == "UnivariateFeatureSelector.fit"
    assert root["attrs"]["kind"] == "fit"
    children = [r for r in records if r["parent"] == root["id"]]
    names = [r["name"] for r in children]
    assert sum(r["dur_us"] for r in children) <= root["dur_us"]
    assert {n: names.count(n) for n in set(names)} == TREE[path]
    launch, = [r for r in children if r["name"] == "anova.launch"]
    if path == "host-anova":
        assert launch["attrs"] == {"path": "host-anova", "rows": 4099,
                                   "d": 7, "passes": 1}
        return
    assert launch["attrs"] == {"path": "grouped-moments", "rows": 4099,
                               "d": 7, "labels": CLASSES, "program": "xla"}
    fetch, = [r for r in children if r["name"] == "anova.fetch"]
    assert fetch["attrs"] == {"passes": 1}
    after = {g: metrics.group(ML_GROUP, g).snapshot()["counters"]
             for g in groups}
    moved = {g: {k: v - before[g].get(k, 0) for k, v in after[g].items()}
             for g in groups}
    # two waits: the look's numbers alone, then the pass's four leaves
    # under one
    assert [moved["iteration"][name] for name in
            ("boundaryFetches", "boundaryWaits")] == [5, 2]
    assert moved["anova"] == {"passes": 1, "classes": CLASSES}


def selector(**kw):
    return UnivariateFeatureSelector(
        feature_type="continuous", label_type="categorical", **kw)


def test_the_model_keeps_what_it_selected_by(tmp_path):
    on_mesh(1)
    x, y = table_of("shifted", np.random.default_rng(13))
    table = Table.from_columns(features=jnp.asarray(x), label=jnp.asarray(y))
    model = selector(selection_threshold=2).fit(table)
    f, p, dfw = two_pass(x, y)
    # the column the label speaks of, then whichever is next; NaN last
    assert 2 in model.indices and 1 not in model.indices
    picked, tested = model.get_model_data()
    np.testing.assert_array_equal(picked.column("indices"), model.indices)
    assert tested.column_names == ["fValues", "pValues", "degreesOfFreedom"]
    ok = np.isfinite(f)
    np.testing.assert_allclose(np.asarray(tested.column("fValues"))[ok],
                               f[ok], rtol=1e-7)
    np.testing.assert_allclose(np.asarray(tested.column("pValues"))[ok],
                               p[ok], rtol=1e-6, atol=1e-12)
    np.testing.assert_array_equal(tested.column("degreesOfFreedom"),
                                  np.full(6, dfw))
    # both tables back in, or the indices alone as upstream publishes them
    both = UnivariateFeatureSelectorModel().set_model_data(picked, tested)
    np.testing.assert_array_equal(both.p_values, model.p_values)
    alone = UnivariateFeatureSelectorModel().set_model_data(picked)
    np.testing.assert_array_equal(alone.indices, model.indices)
    assert alone.p_values is None and len(alone.get_model_data()) == 1
    for m in (both, alone):
        m.set_features_col("features").set_output_col("out")
        out = m.transform(table)[0].column("out")
        np.testing.assert_array_equal(np.asarray(out),
                                      x[:, model.indices])
    model.save(str(tmp_path / "m"))
    loaded = UnivariateFeatureSelectorModel.load(str(tmp_path / "m"))
    np.testing.assert_array_equal(loaded.indices, model.indices)
    np.testing.assert_array_equal(loaded.f_values, model.f_values)
    np.testing.assert_array_equal(loaded.degrees_of_freedom,
                                  model.degrees_of_freedom)
    alone.save(str(tmp_path / "a"))
    bare = UnivariateFeatureSelectorModel.load(str(tmp_path / "a"))
    np.testing.assert_array_equal(bare.indices, model.indices)
    assert bare.p_values is None


def test_host_and_device_fits_select_the_same(tmp_path):
    on_mesh(1)
    x, y = table_of("normal", np.random.default_rng(14))
    dev = selector(selection_threshold=3)
    on_device = dev.fit(Table.from_columns(features=jnp.asarray(x),
                                           label=jnp.asarray(y)))
    host = selector(selection_threshold=3)
    on_host = host.fit(Table.from_columns(features=x.astype(np.float64),
                                          label=y))
    assert (dev.last_execution_path, host.last_execution_path) == (
        "grouped-moments", "host-anova")
    np.testing.assert_array_equal(on_device.indices, on_host.indices)
    np.testing.assert_allclose(on_device.f_values, on_host.f_values,
                               rtol=1e-7)


@pytest.mark.parametrize("flatten", [False, True])
def test_the_anova_operator_reads_a_device_table_on_the_device(flatten,
                                                               monkeypatch):
    on_mesh(1)
    x, y = table_of("continuous", np.random.default_rng(15))
    op = ANOVATest(flatten=flatten)
    host = op.transform(Table.from_columns(features=x.astype(np.float64),
                                           label=y))[0]
    # the table must not come to the host: its off-ramp is barred
    monkeypatch.setattr(Table, "vectors", lambda *a, **k: pytest.fail(
        "the table was copied to the host"))
    dev = op.transform(Table.from_columns(features=jnp.asarray(x),
                                          label=jnp.asarray(y)))[0]
    assert dev.column_names == host.column_names
    for name in host.column_names:
        a = np.asarray(dev.column(name).tolist(), np.float64)
        b = np.asarray(host.column(name).tolist(), np.float64)
        np.testing.assert_allclose(a, b, rtol=1e-7, atol=1e-12)
