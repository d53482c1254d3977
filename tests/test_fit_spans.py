"""The fit's own spans (docs/observability.md, span catalogue): recorded
exactly while someone is looking — a trace dir, the live ring, or any
``jax.profiler`` capture, into whose ``.xplane.pb`` they go on the device
trace's clock — as one trace a fit, the same tree on every SGD execution
path; nothing at all otherwise. And the stable names the device side of the
same trace carries: module names and scopes.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import (
    LogisticRegression,
)
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.observability import compilestats, tracing
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import optimizer as opt_mod
from flink_ml_tpu.ops.losses import BinaryLogisticLoss
from flink_ml_tpu.ops.optimizer import SGDParams

#: span -> its parent, for a fit on the one-program paths
TREE = {
    "fit.extract": "ROOT",
    "sgd.optimize": "ROOT",
    "sgd.place_inputs": "sgd.optimize",
    "collective.host": "sgd.place_inputs",
    "sgd.init_carry": "sgd.optimize",
    "sgd.build_program": "sgd.optimize",
    "sgd.launch": "sgd.optimize",
    "sgd.fetch": "sgd.optimize",
    "sgd.health": "sgd.optimize",
    "fit.model": "ROOT",
}


@pytest.fixture(autouse=True)
def clean_tracer(monkeypatch):
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    tracer.recent.clear()
    yield
    tracer.recent.clear()


@pytest.fixture
def table(rng):
    x = rng.random((2000, 6)).astype(np.float32)
    y = (x @ rng.normal(size=6) > 0).astype(np.float32)
    return Table.from_columns(features=x, label=y)


class Capture:
    """A ``jax.profiler`` capture around the block, nothing else armed."""

    def __init__(self, path):
        self.dir = str(path)

    def __enter__(self):
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def events(self):
        """Every event of the capture's host planes."""
        from jax.profiler import ProfileData

        path, = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return [ev for plane in ProfileData.from_file(path).planes
                if not plane.name.startswith("/device:")
                for line in plane.lines for ev in line.events]

    def host_events(self, names):
        """``[(name, start, end)]`` of the capture's host events so named,
        sorted outer before inner."""
        events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for ev in self.events() if ev.name in names]
        return sorted(events, key=lambda e: (e[1], -e[2]))


def estimator(cls=LogisticRegression):
    return cls().set_max_iter(4).set_global_batch_size(160)


def one_trace(root_name):
    """The ring's records as ``{name: [record]}``, asserted to be one
    trace under one root of that name. Where the fit was its class's
    first of the process its cold spans are in the ring beside it (an
    active tracer rings them too), a tree of their own in a trace of
    their own: set aside, the fit's tree is what it was."""
    cold = {r["id"] for r in tracer.cold}
    records = [r for r in tracer.recent if r["id"] not in cold]
    assert len({r["trace"] for r in records}) == 1
    assert all(r["trace"] != records[0]["trace"] for r in tracer.recent
               if r["id"] in cold)
    roots = [r for r in records if r["parent"] is None]
    assert [r["name"] for r in roots] == [root_name]
    assert roots[0]["attrs"]["kind"] == "fit"
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r)
    return by_name, {r["id"]: r for r in records}


def assert_tree(by_name, by_id, tree):
    for name, parent in tree.items():
        assert name in by_name, f"no {name} span"
        for rec in by_name[name]:
            got = by_id[rec["parent"]]
            want = by_id[by_name[parent][0]["id"]] if parent != "ROOT" \
                else next(r for r in by_id.values() if r["parent"] is None)
            assert got["name"] == want["name"], (name, got["name"])


# -- armed exactly while someone is looking ----------------------------------

def test_no_capture_no_dir_records_nothing(table):
    assert not tracer.active
    assert tracer.span("anything") is tracing._NOOP
    estimator().fit(table)
    assert len(tracer.recent) == 0


def test_a_capture_arms_the_tracer_and_its_end_disarms_it(tmp_path):
    assert not tracer.active
    with Capture(tmp_path):
        assert tracer.active and not tracer.enabled
        with tracer.span("x") as sp:
            assert isinstance(sp, tracing.Span)
    assert not tracer.active
    assert [r["name"] for r in tracer.recent] == ["x"]
    assert tracer.span("x") is tracing._NOOP


def test_capture_alone_writes_nothing_and_arms_no_heavier_telemetry(
        table, tmp_path, monkeypatch):
    called = []
    monkeypatch.setattr(compilestats, "install",
                        lambda *a, **k: called.append("install"))
    monkeypatch.setattr(compilestats, "sample_memory",
                        lambda *a, **k: called.append("sample_memory"))
    monkeypatch.setattr(tracing, "maybe_dump_root_metrics",
                        lambda: called.append("dump"))
    monkeypatch.chdir(tmp_path)
    capture_dir = tmp_path / "capture"
    with Capture(capture_dir):
        estimator().fit(table)
    assert len(tracer.recent) > 0
    assert called in ([], ["dump"])   # the dump is its own guard
    assert tracer.span_file() is None
    assert sorted(os.listdir(tmp_path)) == ["capture"]


def test_ring_default_holds_a_capture_and_the_env_still_overrides(
        monkeypatch):
    monkeypatch.delenv(tracing.RING_ENV, raising=False)
    assert tracing.RECENT_SPANS == 2048
    assert tracing.Tracer().recent.maxlen == 2048
    monkeypatch.setenv(tracing.RING_ENV, "32")
    assert tracing.Tracer().recent.maxlen == 32


# -- one trace a fit, the same tree on every path ------------------------------

def test_fit_under_capture_is_one_trace_in_the_ring_and_in_the_xplane(
        table, tmp_path):
    with Capture(tmp_path) as cap:
        est = estimator()
        est.fit(table)
    assert est.last_execution_path == "xla-while"
    by_name, by_id = one_trace("LogisticRegression.fit")
    assert set(by_name) == set(TREE) | {"LogisticRegression.fit"}
    assert_tree(by_name, by_id, TREE)
    # a plain fit waits for its results once: boundary, coefficients and
    # loss cross under one read
    assert len(by_name["sgd.fetch"]) == 1
    optimize = by_name["sgd.optimize"][0]
    assert optimize["attrs"]["path"] == "xla-while"
    assert optimize["attrs"]["rounds"] == 4
    assert optimize["attrs"]["shards"] == 8
    assert optimize["attrs"]["weights"] == "unit"

    # the same spans in the profiler's own file, nested the same way
    events = cap.host_events(set(by_name))
    assert sorted(e[0] for e in events) == sorted(
        r["name"] for r in tracer.recent)
    stack = []
    for name, start, end in events:
        while stack and stack[-1][2] <= start:
            stack.pop()
        if name != "LogisticRegression.fit":
            parent = stack[-1][0].replace("LogisticRegression.fit", "ROOT")
            assert parent == TREE[name], (name, parent)
            assert end <= stack[-1][2]
        stack.append((name, start, end))


def _while(monkeypatch, est, tmp_path):
    return {}  # the plain fit: nothing to arrange


def _segments(monkeypatch, est, tmp_path):
    est.set_iteration_config(IterationConfig(
        mode="device", checkpoint_interval=2,
        checkpoint_manager=CheckpointManager(str(tmp_path / "ckpt"))))
    # a launch and a fetch a segment, under the runtime's own span
    return {"segment": "sgd.optimize", "sgd.launch": "segment",
            "checkpoint.restore": "sgd.optimize",
            "checkpoint.save": "segment"}


def _host_rounds(monkeypatch, est, tmp_path):
    est.set_iteration_config(IterationConfig(mode="host"))
    # the runtime enqueues the rounds, one ``epoch`` span each
    return {"epoch": "sgd.launch"}


@pytest.mark.parametrize("path, arrange", [
    ("xla-while", _while),
    ("xla-while-segments", _segments),
    ("host-rounds", _host_rounds),
])
def test_the_tree_is_the_same_on_every_execution_path(
        table, tmp_path, monkeypatch, path, arrange):
    est = estimator()
    extra = arrange(monkeypatch, est, tmp_path)
    with Capture(tmp_path / "capture"):
        est.fit(table)
    assert est.last_execution_path == path
    by_name, by_id = one_trace("LogisticRegression.fit")
    assert set(by_name) - (set(extra) - set(TREE)) == set(TREE) | {
        "LogisticRegression.fit"}
    tree = dict(TREE, **extra)
    if path == "xla-while-segments":
        # the boundary fetches sit under their segment, one each, the
        # final state's one read under the optimizer
        parents = sorted(by_id[r["parent"]]["name"]
                         for r in by_name.pop("sgd.fetch"))
        assert parents == ["segment", "segment", "sgd.optimize"]
        del tree["sgd.fetch"]
        assert len(by_name["sgd.launch"]) == len(by_name["segment"]) == 2
    else:
        assert len(by_name["sgd.fetch"]) == 1
    assert_tree(by_name, by_id, tree)
    assert by_name["sgd.optimize"][0]["attrs"]["path"] == path
    # which entry of the segment program each launch took: a plain fit's
    # start is made on the device, a checkpointed fit's carry is placed
    assert {r.get("attrs", {}).get("start")
            for r in by_name["sgd.launch"]} == {
        {"xla-while": "fresh", "xla-while-segments": "carry"}.get(path)}


@pytest.mark.parametrize("cls", [LinearSVC, LinearRegression])
def test_the_other_linear_estimators_leave_the_same_tree(
        table, tmp_path, cls):
    with Capture(tmp_path):
        estimator(cls).fit(table)
    by_name, by_id = one_trace(f"{cls.__name__}.fit")
    assert set(by_name) == set(TREE) | {f"{cls.__name__}.fit"}
    assert_tree(by_name, by_id, TREE)
    assert len(by_name["sgd.fetch"]) == 1


def test_sparse_fit_opens_launch_and_fetch_too(rng, tmp_path):
    from scipy import sparse

    x = sparse.random(400, 50, density=0.1, format="csr", random_state=3,
                      dtype=np.float64)
    y = (rng.random(400) > 0.5).astype(np.float64)
    with Capture(tmp_path):
        opt_mod.SGD(SGDParams(max_iter=3, global_batch_size=80)).optimize_csr(
            BinaryLogisticLoss(), np.zeros(50), x, y)
    names = [r["name"] for r in tracer.recent]
    assert names.count("sgd.optimize") == 1
    assert {"sgd.launch", "sgd.fetch", "sgd.health"} <= set(names)
    optimize = next(r for r in tracer.recent if r["name"] == "sgd.optimize")
    assert optimize["attrs"]["path"] == "csr-host"


# -- stable names on the device side -------------------------------------------

def _program_args(mesh, n=1600, d=6):
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    put = jax.device_put
    return (put(jnp.ones((n, d), jnp.float32), rows),
            put(jnp.ones((n,), jnp.float32), rows),
            put(jnp.ones((n,), jnp.float32), rows),
            put(jnp.zeros((d,), jnp.float32), repl),
            put(jnp.zeros((8,), jnp.int32), rows), ())


@pytest.mark.parametrize("build, module, extra", [
    (lambda mesh, prm: opt_mod._build_sgd_segment_program(
        BinaryLogisticLoss, mesh, prm), "jit_sgd_segment",
     (jnp.int32(0), jnp.int32(4))),
    (lambda mesh, prm: opt_mod._build_sgd_segment_program(
        BinaryLogisticLoss, mesh, prm, fresh=True), "jit_sgd_segment",
     None),
    (lambda mesh, prm: jax.jit(opt_mod._build_sgd_round_program(
        BinaryLogisticLoss, mesh, prm)), "jit_sgd_round", ()),
], ids=["segment", "segment-fresh", "round"])
def test_programs_lower_under_stable_names_with_the_round_scoped(
        mesh8, build, module, extra):
    prm = SGDParams(max_iter=4, global_batch_size=160)
    args = _program_args(mesh8)
    if extra is None:  # the fresh form: the table and the coefficients
        args, extra = args[:4], ()
    lowered = build(mesh8, prm).lower(*args, *extra)
    text = lowered.as_text(debug_info=True)
    assert f"module @{module} " in text
    for scope in ("sgd.round", "sgd.margins", "sgd.gradient",
                  "sgd.grad_allreduce"):
        assert scope in text, scope


@pytest.fixture
def built(monkeypatch):
    """The names of the functions ``jax.jit`` is given, as they come."""
    names = []
    real_jit = jax.jit
    monkeypatch.setattr(
        jax, "jit", lambda fn, *a, **k: (names.append(fn.__name__),
                                         real_jit(fn, *a, **k))[1])
    return names


def test_the_small_programs_of_a_fit_are_named(table, built, monkeypatch):
    """``sgd_health_hist`` is what the device trace showed as
    ``jit__unknown``. The history exists with health armed alone, and its
    program is built once a process. A fit with no weight column has no
    third small program: it builds no column."""
    from flink_ml_tpu.observability import health
    from flink_ml_tpu.parallel import collective

    monkeypatch.setenv(health.HEALTH_ENV, "1")
    opt_mod._health_hist_program.cache_clear()
    collective._prepare_program.cache_clear()
    # a device-resident table whose rows do not divide over the mesh is
    # padded on the device
    x = jnp.asarray(table.column("features"))[:1999]
    y = np.asarray(table.column("label"))[:1999]
    opt_mod.SGD(SGDParams(max_iter=4, global_batch_size=160)).optimize(
        BinaryLogisticLoss(), np.zeros(6), x, y, None)
    collective._prepare_program.cache_clear()
    assert {"sgd_health_hist", "prepare_rows"} <= set(built)
    assert set(built) <= {"sgd_health_hist", "prepare_rows", "sgd_segment"}


# -- a fit with no weight column builds none -----------------------------------

def _resident(mesh, table, weights):
    """The table's columns where a resident table holds them: on the mesh,
    rows over the data axis (a placement ``ensure_on_mesh`` leaves be)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = NamedSharding(mesh, P("data"))
    x = jax.device_put(np.asarray(table.column("features")), rows)
    y = jax.device_put(np.asarray(table.column("label")), rows)
    w = (jax.device_put(np.ones(x.shape[0], np.float32), rows)
         if weights == "column" else None)
    return x, y, w


@pytest.mark.parametrize("weights", ["unit", "column"])
def test_a_fit_builds_and_runs_its_segment_program_and_no_other(
        table, tmp_path, mesh8, built, weights):
    """Cold, a plain fit builds one program, ``sgd_segment``; warm it
    builds none and dispatches that one: with no weight column nothing
    writes one, and ``sgd.optimize`` says which of the two the fit was."""
    x, y, w = _resident(mesh8, table, weights)
    opt_mod._build_sgd_segment_program.cache_clear()

    def fit():
        sgd = opt_mod.SGD(SGDParams(max_iter=4, global_batch_size=160))
        sgd.optimize(BinaryLogisticLoss(), np.zeros(6), x, y, w, mesh=mesh8)
        assert sgd.last_execution_path == "xla-while"

    fit()
    assert built == ["sgd_segment"]
    with Capture(tmp_path) as cap:
        fit()
    assert built == ["sgd_segment"]
    optimize, = (r for r in tracer.recent if r["name"] == "sgd.optimize")
    assert optimize["attrs"]["weights"] == weights

    # what the capture saw the runtime do: every jitted call and every
    # XLA program the fit's thread ran (jax traces both by these names)
    events = [ev.name for ev in cap.events()]
    assert {e for e in events if e.startswith("PjitFunction(")} == {
        "PjitFunction(sgd_segment)"}
    assert events.count("PjRtCpuExecutable::Execute") == 1


def test_a_fit_with_a_weight_column_says_so(table, tmp_path):
    x = np.asarray(table.column("features"))
    weighted = Table.from_columns(
        features=x, label=np.asarray(table.column("label")),
        weight=np.linspace(0.5, 1.5, x.shape[0]).astype(np.float32))
    with Capture(tmp_path):
        estimator().set_weight_col("weight").fit(weighted)
    by_name, _ = one_trace("LogisticRegression.fit")
    assert by_name["sgd.optimize"][0]["attrs"]["weights"] == "column"
