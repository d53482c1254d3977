"""Cold spans: a process's cold start accounted from inside.

``Tracer.cold_span`` records whether or not anybody is looking, because it
sits only where code runs once a process: the imports (stamped by the
stdlib-only ``flink_ml_tpu/_cold.py`` until the tracer exists, then adopted),
the first fit of a stage class (``api/stage.py``), a program builder's body
behind its ``lru_cache``. The process-wide facts are held in fresh
subprocesses, one a stage the benchmark's cells run; the tracer's own rules
on private ``Tracer`` instances in this process.
"""

import functools
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from flink_ml_tpu import _cold
from flink_ml_tpu.observability import compilestats, server, tracing
from flink_ml_tpu.observability.tracing import Tracer, tracer

ROOT = Path(__file__).resolve().parents[1]
STAGES = ("LogisticRegression", "KMeans", "NaiveBayes", "RobustScaler")

SCRIPT = r"""
import json, sys
import flink_ml_tpu
import jax, jax.numpy as jnp, numpy as np
from flink_ml_tpu._cold import importing
from flink_ml_tpu.api import Estimator
from flink_ml_tpu.benchmark.runner import resolve_stage
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh

set_default_mesh(create_mesh(devices=jax.devices()[:1]))
rng = np.random.default_rng(0)
n, d = 4096, 6
x = jnp.asarray(rng.random((n, d), dtype=np.float32))
TABLES = {
    "LogisticRegression": lambda: Table.from_columns(
        features=x, label=jnp.asarray(rng.integers(0, 2, n), jnp.float32)),
    "KMeans": lambda: Table.from_columns(features=x),
    "NaiveBayes": lambda: Table.from_columns(
        features=jnp.asarray(rng.integers(0, 4, (n, d)), jnp.float32),
        label=jnp.asarray(rng.integers(0, 3, n), jnp.float32)),
    "RobustScaler": lambda: Table.from_columns(input=x),
}


class LateImporter(Estimator):
    def fit(self, *inputs):
        with importing("colorsys"):
            import colorsys  # noqa: F401
        return None


name = sys.argv[1]
other = "KMeans" if name != "KMeans" else "RobustScaler"
stage, table = resolve_stage(name)(), TABLES[name]()
stage.fit(table)
after_first = list(tracer.cold)
stage.fit(table)
out = {"after_first": after_first, "path": stage.last_execution_path,
       "cold_after_second": len(tracer.cold),
       "recent_after_second": len(tracer.recent),
       "active": tracer.active}
resolve_stage(other)().fit(TABLES[other]())
LateImporter().fit()
out["after_all"] = list(tracer.cold)
out["dropped"] = tracer.cold_dropped
out["modules"] = sorted(m for m in sys.modules if m.startswith(
    "flink_ml_tpu.observability"))
print(json.dumps(out))
"""


def run_fresh(stage: str, trace_dir=None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    for var in (tracing.TRACE_DIR_ENV, tracing.TRACE_PARENT_ENV,
                "FLINK_ML_TPU_METRICS_PORT", "FLINK_ML_TPU_PROFILE_DIR"):
        env.pop(var, None)
    if trace_dir is not None:
        env[tracing.TRACE_DIR_ENV] = str(trace_dir)
    done = subprocess.run([sys.executable, "-c", SCRIPT, stage], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@functools.lru_cache(maxsize=None)
def fresh(stage: str) -> dict:
    return run_fresh(stage)


def end_us(record) -> int:
    return record["ts_us"] + record["dur_us"]


def tree_of(records, root) -> list:
    """``root`` and every record under it."""
    by_parent = {}
    for r in records:
        by_parent.setdefault(r["parent"], []).append(r)
    out, todo = [], [root]
    while todo:
        out.append(todo.pop())
        todo.extend(by_parent.get(out[-1]["id"], []))
    return out


# -- a fresh process, one a stage ---------------------------------------------

@pytest.mark.parametrize("stage", STAGES)
def test_a_cold_start_is_recorded_with_nobody_looking(stage):
    got = fresh(stage)
    assert got["active"] is False
    records = got["after_first"]
    by_id = {r["id"]: r for r in records}
    names = [r["name"] for r in records]
    # the package's import holds jax's: stamped before a tracer existed
    pkg = next(r for r in records if r["name"] == "import:flink_ml_tpu")
    jax_ = next(r for r in records if r["name"] == "import:jax")
    assert jax_["parent"] == pkg["id"] and pkg["parent"] is None
    assert pkg["dur_us"] > jax_["dur_us"] > 0
    models = next(r for r in records
                  if r["name"] == "import:flink_ml_tpu.models")
    for dep in ("import:scipy.stats", "import:scipy.cluster"):
        assert dep in [r["name"] for r in tree_of(records, models)]
    # one first_fit root, the stage's, with its builds under it
    roots = [r for r in records if r["name"] == "first_fit"]
    assert [r["attrs"]["stage"] for r in roots] == [stage]
    root, = roots
    assert root["parent"] is None and root["attrs"]["kind"] == "first_fit"
    under = tree_of(records, root)
    assert any(r["name"].startswith("build:") for r in under[1:]), names
    # what jax did meanwhile, on the tree's spans
    assert sum(r["attrs"].get("trace_s", 0.0) for r in under) > 0
    assert sum(r["attrs"].get("lower_s", 0.0) for r in under) > 0
    assert sum(r["attrs"].get("traces", 0) for r in under) >= 1
    built = sum(r["attrs"].get(a, 0.0) for r in under
                for a in ("trace_s", "lower_s", "compile_s"))
    assert built <= root["dur_us"] / 1e6
    # every child inside its parent in time, to the microsecond
    for r in records:
        parent = by_id.get(r["parent"])
        if parent is not None:
            assert parent["ts_us"] <= r["ts_us"], (r["name"], parent["name"])
            assert end_us(r) <= end_us(parent), (r["name"], parent["name"])
            assert r["trace"] == parent["trace"]
    assert all(r["parent"] is None or r["parent"] in by_id for r in records)


@pytest.mark.parametrize("stage", STAGES)
def test_a_second_fit_adds_nothing_anywhere(stage):
    got = fresh(stage)
    assert got["cold_after_second"] == len(got["after_first"])
    assert got["recent_after_second"] == 0
    assert got["dropped"] == 0


@pytest.mark.parametrize("stage", STAGES)
def test_a_second_stage_class_gets_its_own_first_fit(stage):
    got = fresh(stage)
    other = "KMeans" if stage != "KMeans" else "RobustScaler"
    roots = [r for r in got["after_all"]
             if r["name"] == "first_fit" and r["parent"] is None]
    assert [r["attrs"]["stage"] for r in roots] == [
        stage, other, "LateImporter"]
    assert len({r["trace"] for r in roots}) == 3


@pytest.mark.parametrize("stage", STAGES)
def test_an_import_inside_a_first_fit_is_its_child(stage):
    records = fresh(stage)["after_all"]
    late = next(r for r in records if r["name"] == "import:colorsys")
    parent = next(r for r in records if r["id"] == late["parent"])
    assert parent["name"] == "first_fit"
    assert parent["attrs"]["stage"] == "LateImporter"
    assert late["attrs"] == {"kind": "import"}
    # KMeans' and NaiveBayes' fits import the kernels' module (every run
    # here fits KMeans): Pallas' import is the first such fit's
    pallas = next(r for r in records if r["name"] == "import:pallas")
    above = next(r for r in records if r["id"] == pallas["parent"])
    while above["name"] != "first_fit":
        above = next(r for r in records if r["id"] == above["parent"])
    assert above["attrs"]["stage"] in ("KMeans", "NaiveBayes")


def test_nothing_new_is_imported_at_package_import():
    """The listener and ``compilestats`` come in with the first fit, the
    tracer with the first module that uses it: ``import flink_ml_tpu``
    alone loads none of them, and its stamps wait to be adopted."""
    code = ("import sys, flink_ml_tpu; from flink_ml_tpu import _cold; "
            "print(sorted(m for m in sys.modules "
            "if m.startswith('flink_ml_tpu.observability')), "
            "[r['name'] for r in _cold._pending])")
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT)),
        timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == (
        "[] ['import:numpy', 'import:jax', 'import:flink_ml_tpu']")


def test_with_a_trace_dir_the_cold_records_are_in_the_span_file(tmp_path):
    got = run_fresh("LogisticRegression", trace_dir=tmp_path)
    assert got["active"] is True
    files = list(tmp_path.glob("spans-*.jsonl"))
    assert len(files) == 1
    written = [json.loads(line) for line in
               files[0].read_text().splitlines()]
    names = [r["name"] for r in written]
    # the adopted import stamps among them, once each
    for name in ("import:flink_ml_tpu", "import:jax", "import:numpy",
                 "import:flink_ml_tpu.models", "import:scipy.stats",
                 "import:colorsys", "build:sgd_segment"):
        assert names.count(name) == 1, name
    assert names.count("first_fit") == 3
    # every cold record the process kept is in the file, as it was kept
    by_id = {r["id"]: r for r in written}
    for kept in got["after_all"]:
        assert by_id[kept["id"]]["dur_us"] == kept["dur_us"]
        assert by_id[kept["id"]]["parent"] == kept["parent"]
    # the armed fit's own tree is what it was: its root is a root
    fits = [r for r in written if r["name"] == "LogisticRegression.fit"]
    assert len(fits) == 2 and all(r["parent"] is None for r in fits)
    # and the operator's reader shows them with no change of its own
    shown = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "mltrace.py"),
         str(tmp_path)], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT)))
    assert shown.returncode == 0, shown.stderr[-2000:]
    assert "first_fit" in shown.stdout and "import:jax" in shown.stdout


# -- the tracer's rules, on private tracers ------------------------------------

@pytest.fixture
def quiet(monkeypatch):
    """A tracer nobody is looking at: no dir, no ring, no capture."""
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    monkeypatch.delenv(tracing.TRACE_PARENT_ENV, raising=False)
    t = Tracer()
    assert not t.active
    return t


def test_a_cold_span_is_kept_and_a_plain_one_is_not(quiet):
    with quiet.cold_span("first_fit", kind="first_fit", stage="X") as root:
        assert quiet.span("X.fit") is tracing._NOOP
        with quiet.cold_span("build:p") as child:
            assert quiet.cold_current() is child
        assert quiet.cold_current() is root
    assert quiet.cold_current() is None
    assert [r["name"] for r in quiet.cold] == ["build:p", "first_fit"]
    build, first = quiet.cold
    assert build["parent"] == first["id"] and first["parent"] is None
    assert build["trace"] == first["trace"]
    assert first["attrs"] == {"kind": "first_fit", "stage": "X"}
    assert len(quiet.recent) == 0
    assert set(first) == {"type", "name", "trace", "id", "parent", "ts_us",
                          "dur_us", "pid", "tid", "attrs", "events"}


def test_an_active_tracer_rings_the_cold_spans_too(quiet):
    quiet.keep_recent = True
    with quiet.cold_span("first_fit"):
        with quiet.span("X.fit", kind="fit") as plain:
            with quiet.cold_span("build:p"):
                pass
    assert [r["name"] for r in quiet.recent] == [
        "build:p", "X.fit", "first_fit"]
    assert [r["name"] for r in quiet.cold] == ["build:p", "first_fit"]
    build, fit, first = quiet.recent
    # cold spans nest among themselves; the armed fit's tree is its own
    assert fit["parent"] is None and fit["id"] == plain.span_id
    assert build["parent"] == first["id"]
    assert fit["trace"] != first["trace"]


def test_the_list_stops_at_its_bound_and_counts_what_it_dropped(quiet):
    for k in range(tracing.COLD_SPANS + 44):
        with quiet.cold_span(f"build:{k}"):
            pass
    assert len(quiet.cold) == tracing.COLD_SPANS
    assert quiet.cold_dropped == 44
    # the oldest are kept: set-up's
    assert quiet.cold[0]["name"] == "build:0"
    assert quiet.cold[-1]["name"] == f"build:{tracing.COLD_SPANS - 1}"


def test_a_child_lies_inside_its_parent_to_the_microsecond(quiet):
    for _ in range(200):
        with quiet.cold_span("a"):
            with quiet.cold_span("b"):
                pass
    by_id = {r["id"]: r for r in quiet.cold}
    for r in quiet.cold:
        if r["parent"] is not None:
            p = by_id[r["parent"]]
            assert p["ts_us"] <= r["ts_us"] and end_us(r) <= end_us(p)


def test_an_error_is_said_on_the_cold_span(quiet):
    with pytest.raises(KeyError):
        with quiet.cold_span("first_fit"):
            raise KeyError("x")
    assert quiet.cold[0]["attrs"]["error"] == "KeyError"
    assert quiet.cold_current() is None


def test_cold_spans_of_two_threads_do_not_nest(quiet):
    def other():
        with quiet.cold_span("first_fit", stage="B"):
            pass

    with quiet.cold_span("first_fit", stage="A"):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join()
    b, a = quiet.cold
    assert b["parent"] is None and a["parent"] is None
    assert b["trace"] != a["trace"]


def test_a_launched_child_s_cold_roots_join_the_launcher_s_trace(
        quiet, monkeypatch):
    monkeypatch.setenv(tracing.TRACE_PARENT_ENV, "abc-1:abc-2")
    with quiet.cold_span("first_fit"):
        pass
    assert quiet.cold[0]["trace"] == "abc-1"
    assert quiet.cold[0]["parent"] == "abc-2"


def test_a_forked_child_starts_with_an_empty_list(quiet):
    with quiet.cold_span("first_fit"):
        pass
    quiet.adopt_cold([{"name": "import:x"}])
    quiet.reseed_child()
    assert quiet.cold == [] and quiet.cold_dropped == 0
    assert quiet.cold_current() is None


def test_the_builder_s_body_is_a_cold_span_and_a_cache_hit_is_none(
        monkeypatch):
    monkeypatch.setattr(tracing, "tracer", Tracer())
    calls = []

    @functools.lru_cache(maxsize=4)
    @tracing.cold_build("thing")
    def build(k):
        """doc"""
        calls.append(k)
        return k * 2

    assert build(3) == 6 and build(3) == 6 and build(4) == 8
    assert calls == [3, 4]
    assert [r["name"] for r in tracing.tracer.cold] == [
        "build:thing", "build:thing"]
    assert tracing.tracer.cold[0]["attrs"] == {"kind": "build"}
    assert build.__wrapped__.__doc__ == "doc" and build.cache_info().hits == 1


# -- the stamps: before a tracer exists ---------------------------------------

@pytest.fixture
def no_tracer(monkeypatch):
    """``_cold`` as it is before ``observability/tracing.py`` is imported."""
    monkeypatch.setattr(_cold, "_TRACING", "flink_ml_tpu.no_such_module")
    monkeypatch.setattr(_cold, "_pending", [])
    monkeypatch.setattr(_cold, "_open", [])
    monkeypatch.delenv(tracing.TRACE_PARENT_ENV, raising=False)


def test_stamps_nest_and_wait_in_the_span_record_s_format(no_tracer):
    with _cold.importing("outer") as outer:
        assert _cold.innermost_open() == (outer["trace"], outer["id"])
        with _cold.importing("inner"):
            time.sleep(0.001)
    assert _cold.innermost_open() is None
    inner, outer = _cold.take_pending()
    assert _cold.take_pending() == []
    assert (inner["name"], outer["name"]) == ("import:inner", "import:outer")
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert inner["trace"] == outer["trace"]
    assert outer["ts_us"] <= inner["ts_us"] and end_us(inner) <= end_us(outer)
    assert inner["dur_us"] >= 1000
    # the tracer's own record, key for key
    t = Tracer()
    with t.cold_span("x", kind="import"):
        pass
    assert set(inner) == set(t.cold[0])
    assert inner["attrs"] == {"kind": "import"}
    assert abs(inner["ts_us"] - time.time_ns() // 1000) < 5_000_000


def test_a_stamp_joins_the_launcher_s_trace(no_tracer, monkeypatch):
    monkeypatch.setenv(tracing.TRACE_PARENT_ENV, "abc-1:abc-2")
    with _cold.importing("m"):
        pass
    stamp, = _cold.take_pending()
    assert (stamp["trace"], stamp["parent"]) == ("abc-1", "abc-2")


def test_an_import_that_fails_is_still_stamped(no_tracer):
    with pytest.raises(ImportError):
        with _cold.importing("nothing"):
            raise ImportError("nothing")
    stamp, = _cold.take_pending()
    assert stamp["attrs"] == {"kind": "import", "error": "ImportError"}
    assert _cold._open == []


def test_the_tracer_adopts_the_stamps_and_parents_under_an_open_one(
        monkeypatch, tmp_path):
    """A tracer built while an import that began before it still runs: the
    finished stamps are its records, the open one the parent of the cold
    spans it opens, and adopted when it ends."""
    monkeypatch.setattr(_cold, "_pending", [])
    monkeypatch.setattr(_cold, "_open", [])
    monkeypatch.delenv(tracing.TRACE_PARENT_ENV, raising=False)
    monkeypatch.setattr(_cold, "_TRACING", "flink_ml_tpu.no_such_module")
    late = Tracer()
    holder = type(sys)("holder")
    with _cold.importing("pkg") as pkg:
        with _cold.importing("dep"):
            pass
        # the tracer comes to be
        late.adopt_cold(_cold.take_pending())
        holder.tracer = late
        monkeypatch.setitem(sys.modules, "flink_ml_tpu.holder", holder)
        monkeypatch.setattr(_cold, "_TRACING", "flink_ml_tpu.holder")
        with _cold.importing("models") as models:
            assert isinstance(models, tracing.Span)
            assert models.parent_id == pkg["id"]
            assert models.trace_id == pkg["trace"]
    assert [r["name"] for r in late.cold] == [
        "import:dep", "import:models", "import:pkg"]
    assert _cold._pending == [] and _cold._open == []
    by_id = {r["id"]: r for r in late.cold}
    for r in late.cold[:2]:
        assert by_id[r["parent"]]["name"] == "import:pkg"
        assert end_us(r) <= end_us(by_id[r["parent"]])


def test_adopted_stamps_reach_the_span_file_with_the_next_record(
        monkeypatch, tmp_path):
    monkeypatch.delenv(tracing.TRACE_PARENT_ENV, raising=False)
    t = Tracer()
    t.configure(str(tmp_path))
    try:
        t.adopt_cold([{"type": "span", "name": "import:jax", "trace": "t",
                       "id": "s1", "parent": None, "ts_us": 1, "dur_us": 2,
                       "pid": 1, "tid": 1, "attrs": {}, "events": []}])
        assert not list(tmp_path.glob("spans-*.jsonl"))
        with t.cold_span("first_fit"):
            pass
        lines = [json.loads(line) for line in
                 Path(t.span_file()).read_text().splitlines()]
    finally:
        t.shutdown()
    assert [r["name"] for r in lines] == ["import:jax", "first_fit"]
    # with no dir set, nothing is owed
    t2 = Tracer()
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    t2.adopt_cold([{"name": "import:jax"}])
    assert t2._cold_unwritten == [] and len(t2.cold) == 1


# -- what jax did meanwhile -----------------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def test_the_listener_adds_to_the_innermost_cold_span(monkeypatch):
    t = Tracer()
    monkeypatch.setattr(tracing, "tracer", t)
    stats = compilestats.CompileStats()
    now = [10.0]
    monkeypatch.setattr(compilestats.time, "perf_counter",
                        lambda: now.__setitem__(0, now[0] + 2.0) or now[0])
    with t.cold_span("first_fit") as root:
        stats._on_duration(TRACE, 0.25)
        with t.cold_span("build:p") as build:
            stats._on_duration(LOWER, 0.5)
        stats._on_duration(COMPILE, 1.0)
        stats._on_duration(LOAD, 0.75)
        stats._on_event("/jax/compilation_cache/cache_hits")
        stats._on_duration("/jax/some/other_duration", 9.0)
    stats._on_duration(TRACE, 4.0)     # no cold span open: nobody's
    assert build.attrs == {"lower_s": 0.5}
    assert root.attrs == {"trace_s": 0.25, "traces": 1, "compile_s": 1.0,
                          "compiles": 1, "cache_load_s": 0.75,
                          "cache_hits": 1}
    # the registry stays tied to the trace dir: nothing was enabled
    assert stats._enabled is False


def test_a_trace_inside_a_trace_is_counted_once(monkeypatch):
    """jax reports the inner trace alone and again within the outer one's
    seconds; the sum over the span is time that passed once."""
    t = Tracer()
    monkeypatch.setattr(tracing, "tracer", t)
    stats = compilestats.CompileStats()
    now = [100.0]
    monkeypatch.setattr(compilestats.time, "perf_counter", lambda: now[0])
    with t.cold_span("first_fit") as root:
        now[0] = 100.3
        stats._on_duration(TRACE, 0.1)      # inner: [100.2, 100.3]
        now[0] = 100.5
        stats._on_duration(TRACE, 0.1)      # inner: [100.4, 100.5]
        now[0] = 100.6
        stats._on_duration(TRACE, 0.6)      # outer: [100.0, 100.6]
        now[0] = 101.0
        stats._on_duration(LOWER, 0.25)     # after it: disjoint
        now[0] = 102.0
        stats._on_duration(COMPILE, 0.5)
        stats._on_duration(LOAD, 0.4)       # inside the compile: beside it
    assert root.attrs["traces"] == 3
    assert root.attrs["trace_s"] == pytest.approx(0.6)
    assert root.attrs["lower_s"] == 0.25 and root.attrs["compile_s"] == 0.5
    assert root.attrs["cache_load_s"] == 0.4


def test_watch_cold_subscribes_the_one_listener_once(monkeypatch):
    from jax import monitoring

    registered = []
    monkeypatch.setattr(monitoring, "register_event_duration_secs_listener",
                        registered.append)
    monkeypatch.setattr(monitoring, "register_event_listener",
                        registered.append)
    stats = compilestats.CompileStats()
    assert stats.watch_cold() is True and stats._enabled is False
    assert stats.install() is True and stats._enabled is True
    assert stats.watch_cold() is True
    assert registered == [stats._on_duration, stats._on_event]


# -- one operator's read ---------------------------------------------------------

def test_spans_recent_carries_the_cold_records(monkeypatch):
    monkeypatch.setenv(server.METRICS_PORT_ENV, "0")
    server.stop()
    try:
        srv = server.maybe_start()
        assert srv is not None
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/spans/recent",
                timeout=10) as resp:
            body = json.loads(resp.read().decode("utf-8"))
    finally:
        server.stop()
    assert set(body) == {"spans", "cold"}
    assert [r["id"] for r in body["cold"]] == [r["id"] for r in tracer.cold]
    assert any(r["name"] == "import:flink_ml_tpu" for r in body["cold"])
