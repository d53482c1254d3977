"""A warm dense SGD fit builds and places nothing it built before
(docs/performance.md; PERF.md section 5): no ``jax.jit`` object made, no
compile request issued, a carry that crosses the host placed by ONE
``jax.device_put`` of host arrays with the epoch bounds host scalars, a
plain fit's start made inside its program from the one host operand that
carries information, the coefficients (PR 39) — on every dense execution
path, for every linear estimator's loss, SGD and Adam, one device and
eight. The
answers are the parent tree's to the last bit
(``tests/fixtures/optimizer_warm_fit/golden.json``, written from commit
6708c0f by running this file as a script there; the four cases under its
``later`` key by the same ``fit`` at 1297d45, the while-loop program
chosen as the benchmark chose it). The parent's fits had a weight column,
ones it wrote anew in every fit; since PR 32 a fit with no column builds
none, and XLA's CPU backend orders the loss's sum otherwise where it fuses
it without the multiply by 1.0: the coefficients are the parent's to the
last bit, the loss to 1e-6 (2e-7 at most: four of the eight-device fits
and one health series move at all), and the same fit given a column of
ones answers the parent's loss to the last bit too.
"""

import contextlib
import json
import os
import sys

if __name__ == "__main__":  # the golden writer: the mesh conftest.py gives
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import (
    LogisticRegression,
)
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.observability import health
from flink_ml_tpu.ops import optimizer as opt_mod
from flink_ml_tpu.ops.optimizer import SGD, SGDParams
from flink_ml_tpu.parallel import create_mesh, update_sharding

GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures",
                      "optimizer_warm_fit", "golden.json")
ESTIMATORS = {"lr": LogisticRegression, "svc": LinearSVC,
              "linreg": LinearRegression}
N, D, BATCH, ROUNDS, SEGMENT = 2000, 6, 160, 6, 2

#: (estimator, update rule, devices, execution path)
CASES = [
    ("lr", "sgd", 1, "xla-while"), ("lr", "sgd", 8, "xla-while"),
    ("lr", "adam", 1, "xla-while"), ("lr", "adam", 8, "xla-while"),
    ("svc", "sgd", 8, "xla-while"), ("svc", "adam", 1, "xla-while"),
    ("linreg", "sgd", 1, "xla-while"), ("linreg", "adam", 8, "xla-while"),
    ("svc", "adam", 8, "xla-while"), ("linreg", "sgd", 8, "xla-while"),
    ("svc", "sgd", 1, "xla-while"), ("linreg", "adam", 1, "xla-while"),
    ("lr", "sgd", 8, "xla-while-segments"),
    ("svc", "sgd", 1, "xla-while-segments"),
    ("linreg", "adam", 8, "xla-while-segments"),
    ("lr", "sgd", 8, "host-rounds"), ("lr", "adam", 1, "host-rounds"),
    ("svc", "sgd", 1, "host-rounds"), ("linreg", "adam", 8, "host-rounds"),
]


#: golden answers computed on a later commit than the file's ``commit``
LATER = {"1297d45063649a4babae330e93cc399e420f5eb7": [
    "linreg-adam-1-xla-while", "linreg-sgd-8-xla-while",
    "svc-adam-8-xla-while", "svc-sgd-1-xla-while"]}


def case_id(case) -> str:
    return "-".join(map(str, case))


def device_twin(case):
    """The case whose golden answer ``case`` is held to, bit for bit: its
    own, but for host rounds the same fit on an all-device path — "device
    and host modes are numerically identical by construction"
    (``_build_sgd_round_program``). The parent's host rounds closed over
    the table, so XLA folded it into the round as a constant and one loss
    of the four came out 3 ulps off its own device paths'; its own golden
    answer is held too, the loss to 1e-6."""
    if case[3] != "host-rounds":
        return case
    return next(c for c in CASES
                if c[:3] == case[:3] and c[3] != "host-rounds")


def make_mesh(devices: int):
    return create_mesh(devices=jax.devices()[:devices])


def make_data(est: str):
    rng = np.random.default_rng(7)
    x = rng.random((N, D)).astype(np.float32)
    dots = x @ rng.normal(size=D)
    y = dots if est == "linreg" else (dots > np.median(dots))
    return x, y.astype(np.float32)


def fit(case, ckpt_dir, manager=None, weights=None):
    """One fit of ``case`` -> (coefficients, loss, the path it reported)."""
    est, method, devices, path = case
    config = None
    if path == "xla-while-segments":
        config = IterationConfig(
            checkpoint_interval=SEGMENT,
            checkpoint_manager=manager or CheckpointManager(str(ckpt_dir)))
    elif path == "host-rounds":
        config = IterationConfig(mode="host")
    sgd = SGD(SGDParams(max_iter=ROUNDS, global_batch_size=BATCH,
                        method=method))
    x, y = make_data(est)
    coeffs, loss = sgd.optimize(ESTIMATORS[est].loss, np.zeros(D), x, y,
                                weights, mesh=make_mesh(devices),
                                config=config)
    return coeffs, loss, sgd.last_execution_path


def short_fit(method: str, mesh, config=None, rounds=2):
    """``rounds`` logistic rounds under ``method``: long enough to place a
    carry, where ``config`` asks for one -> (coefficients, loss)."""
    x, y = make_data("lr")
    return SGD(SGDParams(max_iter=rounds, global_batch_size=BATCH,
                         method=method)).optimize(
        LogisticRegression.loss, np.zeros(D), x, y, None, mesh=mesh,
        config=config)


def one_segment(ckpt_dir, rounds=2):
    """The checkpointed fit whose one segment is the whole fit: the plain
    fit's rounds, from a carry that crossed the host."""
    return IterationConfig(checkpoint_interval=rounds,
                           checkpoint_manager=CheckpointManager(
                               str(ckpt_dir)))


def health_series(case, ckpt_dir):
    """The convergence series a health-armed fit of ``case`` records."""
    seen = []
    real = health.check_fit
    health.check_fit = lambda algo, series, **kw: (
        seen.append({k: [float(v) for v in vs] for k, vs in series.items()}),
        real(algo, series, **kw))[1]
    try:
        fit(case, ckpt_dir)
    finally:
        health.check_fit = real
    series, = seen
    return series


HEALTH_CASES = [("lr", "sgd", 8, "xla-while"),
                ("linreg", "adam", 1, "xla-while-segments")]


@pytest.fixture(autouse=True)
def telemetry_and_sharded_update_off(monkeypatch):
    monkeypatch.delenv(health.HEALTH_ENV, raising=False)
    monkeypatch.delenv(update_sharding.ENV, raising=False)


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


class _NoSpan:
    def set_attribute(self, *a):
        pass


class Watch:
    """What a fit builds and places while armed: the ``jax.jit`` objects
    it makes, the compile requests it issues (the ``jax.monitoring``
    channel ``benchmarks/harness/compiles.py`` counts; ``events`` may name
    more channels), and each ``jax.device_put`` call with the span of
    ``module``'s tracer it fell under."""

    REQUEST = "/jax/core/compile/backend_compile_duration"

    def __init__(self, monkeypatch, module=opt_mod, events=(REQUEST,)):
        self.events = events
        self.jits, self.requests, self.puts = [], 0, []
        self.opened = []  # (span name, attributes), as they opened
        self.armed = False
        self._stack = []
        real_jit, real_put = jax.jit, jax.device_put
        watch = self

        def jit(fn, *a, **k):
            if watch.armed:
                watch.jits.append(getattr(fn, "__name__", repr(fn)))
            return real_jit(fn, *a, **k)

        def device_put(x, *a, **k):
            out = real_put(x, *a, **k)
            if watch.armed:
                watch.puts.append((watch._stack[-1] if watch._stack
                                   else None, out))
            return out

        class Spans:
            enabled = active = False

            @contextlib.contextmanager
            def span(self, name, **attrs):
                if watch.armed:
                    watch.opened.append((name, attrs))
                watch._stack.append(name)
                try:
                    yield _NoSpan()
                finally:
                    watch._stack.pop()

        monkeypatch.setattr(jax, "jit", jit)
        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(module, "tracer", Spans())
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration_secs, **kw):
        if self.armed and event in self.events:
            self.requests += 1

    @contextlib.contextmanager
    def __call__(self):
        self.armed = True
        try:
            yield self
        finally:
            self.armed = False

    def carry_puts(self):
        return [out for span, out in self.puts if span == "sgd.init_carry"]


@pytest.fixture
def watch(monkeypatch):
    w = Watch(monkeypatch)
    yield w
    w.armed = False  # jax keeps the listener; it counts nothing from here


def batch_reads() -> int:
    """``ml.sgd batchReads`` so far in this process."""
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics

    return metrics.group(ML_GROUP, "sgd").get_counter("batchReads")


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_a_warm_fit_builds_nothing_and_answers_as_the_parent_did(
        case, tmp_path, watch, golden):
    want = golden["fits"][case_id(device_twin(case))]
    coeffs, loss, path = fit(case, tmp_path)
    assert path == case[3]
    reads = batch_reads()
    with watch():
        again, loss_again, _ = fit(case, tmp_path)
    assert watch.jits == []
    assert watch.requests == 0
    # every round read its batch from HBM once, and the spans say so
    assert batch_reads() - reads == ROUNDS
    assert {attrs["batch"] for name, attrs in watch.opened
            if name in ("sgd.optimize", "sgd.launch")} == {"onchip"}
    assert [name for name, _ in watch.opened].count("sgd.launch") == (
        ROUNDS // SEGMENT if case[3] == "xla-while-segments" else 1)
    # a carry that crosses the host goes up in one call, a plain fit
    # places none, and nothing else is placed between the inputs and the
    # fetch
    assert [span for span, _ in watch.puts
            if span != "sgd.place_inputs"] == (
        [] if case[3] == "xla-while" else ["sgd.init_carry"])
    assert loss_again == loss
    for got_c, got_l in ((coeffs, loss), (again, loss_again)):
        assert got_c.dtype == np.float64
        assert got_c.tolist() == want["coefficients"]
        assert got_l == pytest.approx(want["loss"], rel=1e-6, abs=0)
    # given the column the parent's fits wrote, the parent's last bit
    column, column_loss, _ = fit(case, tmp_path,
                                 weights=np.ones(N, np.float32))
    assert column.tolist() == want["coefficients"]
    assert column_loss == want["loss"]
    parents = golden["fits"][case_id(case)]
    assert coeffs.tolist() == parents["coefficients"]
    assert loss == pytest.approx(parents["loss"], rel=1e-6, abs=0)


def serial_reads(boundary):
    """``read_boundary`` as it was until PR 34: each leaf waited on before
    the next one's copy starts."""
    if isinstance(boundary, (tuple, list)):
        return [np.asarray(v) for v in boundary]
    return list(np.asarray(boundary))


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_one_wait_answers_as_serial_reads_did(case, tmp_path, monkeypatch):
    """The same buffers are copied, only the order of start and wait
    changes: coefficients and loss are those of one read after another,
    bit for bit, on every path."""
    from flink_ml_tpu.iteration import iteration

    coeffs, loss, path = fit(case, tmp_path)
    assert path == case[3]
    monkeypatch.setattr(iteration, "read_boundary", serial_reads)
    serial, serial_loss, _ = fit(case, tmp_path)
    assert coeffs.dtype == serial.dtype == np.float64
    assert coeffs.tolist() == serial.tolist()
    assert loss == serial_loss and type(loss) is float


#: the carry's leaves in order — coefficients, per-task offsets, loss, then
#: the rule's moments and Adam's step — as (dtype, spec, shape); "w" is the
#: coefficient spec, "m" the moments'
CARRY_LEAVES = {
    "sgd": [("float32", "w", "d"), ("int32", "data", "p"),
            ("float32", None, ())],
    "momentum": [("float32", "w", "d"), ("int32", "data", "p"),
                 ("float32", None, ()), ("float32", "m", "d")],
    "adam": [("float32", "w", "d"), ("int32", "data", "p"),
             ("float32", None, ()), ("float32", "m", "d"),
             ("float32", "m", "d"), ("float32", None, ())],
}
#: layout -> (mesh shape, axis names, "w" spec, "m" spec, padded d)
LAYOUTS = {
    "replicated": ((8,), ("data",), P(), P(), D),
    "sharded-update": ((8,), ("data",), P(), P("data"), 8),
    "tensor-parallel": ((2, 4), ("data", "model"), P("model"), P("model"),
                        8),
}


def layout_mesh(layout, monkeypatch):
    """``layout``'s mesh, with the sharded update armed where it is one."""
    shape, names = LAYOUTS[layout][:2]
    if layout == "sharded-update":
        monkeypatch.setenv(update_sharding.ENV, "1")
    return create_mesh(shape, names)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("method", CARRY_LEAVES)
def test_the_carry_is_one_put_of_the_same_leaves(method, layout, watch,
                                                 monkeypatch, tmp_path):
    """A checkpointed fit's carry crosses the host, so it is placed."""
    shape, _, wspec, mspec, d = LAYOUTS[layout]
    mesh = layout_mesh(layout, monkeypatch)
    with watch():
        short_fit(method, mesh, one_segment(tmp_path))
    carry, = watch.carry_puts()
    assert [attrs for name, attrs in watch.opened
            if name == "sgd.launch"] == [{"start": "carry",
                                          "batch": "onchip"}]
    specs = {"w": wspec, "m": mspec, "data": P("data"), None: P()}
    sizes = {"d": (d,), "p": (shape[0],), (): ()}
    coeffs, offsets, loss, opt = carry
    assert isinstance(opt, tuple)
    got = [(str(leaf.dtype), leaf.sharding.spec, leaf.shape, leaf.weak_type)
           for leaf in jax.tree_util.tree_leaves(carry)]
    assert got == [(dtype, specs[spec], sizes[size], False)
                   for dtype, spec, size in CARRY_LEAVES[method]]
    assert all(leaf.sharding.mesh == mesh
               for leaf in jax.tree_util.tree_leaves(carry))


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("method", CARRY_LEAVES)
def test_a_plain_fit_places_no_carry_and_hands_over_one_host_operand(
        method, layout, watch, monkeypatch):
    """The program makes the zero carry and the bounds itself: beside the
    resident table the call takes the coefficients, a host array that
    jit's own argument path places, and nothing else."""
    d = LAYOUTS[layout][4]
    mesh = layout_mesh(layout, monkeypatch)
    calls = []
    build = opt_mod._build_sgd_segment_program

    def recording(*a, **k):
        prog = build(*a, **k)
        assert k["fresh"] is True
        return lambda *operands: (calls.append(operands), prog(*operands))[1]

    monkeypatch.setattr(opt_mod, "_build_sgd_segment_program", recording)
    with watch():
        short_fit(method, mesh)
    assert [span for span, _ in watch.puts
            if span != "sgd.place_inputs"] == []
    (xs, ys, ws, coeffs), = calls
    assert isinstance(xs, jax.Array) and isinstance(ys, jax.Array)
    assert ws is None
    assert type(coeffs) is np.ndarray
    assert (coeffs.dtype, coeffs.shape) == (np.float32, (d,))
    assert [attrs for name, attrs in watch.opened
            if name == "sgd.launch"] == [{"start": "fresh",
                                          "batch": "onchip"}]
    assert [name for name, _ in watch.opened].count("sgd.init_carry") == 1


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("method", CARRY_LEAVES)
def test_a_plain_fit_answers_as_the_one_segment_checkpointed_fit(
        method, layout, tmp_path, golden, monkeypatch):
    """Both forms of the program wrap one loop: a start made on the device
    and the same start placed from the host answer bit for bit, and on the
    layout the golden answers were written on, as the parent did."""
    mesh = layout_mesh(layout, monkeypatch)
    plain, plain_loss = short_fit(method, mesh, rounds=ROUNDS)
    carried, carried_loss = short_fit(
        method, mesh, one_segment(tmp_path, ROUNDS), rounds=ROUNDS)
    assert plain.dtype == carried.dtype == np.float64
    assert plain.tolist() == carried.tolist()
    assert plain_loss == carried_loss
    want = golden["fits"].get(f"lr-{method}-8-xla-while")
    if layout == "replicated" and want:
        assert plain.tolist() == want["coefficients"]
        assert plain_loss == pytest.approx(want["loss"], rel=1e-6, abs=0)


@pytest.mark.parametrize("case", HEALTH_CASES, ids=case_id)
def test_health_armed_the_history_comes_from_one_cached_program(
        case, tmp_path, watch, golden, monkeypatch):
    monkeypatch.setenv(health.HEALTH_ENV, "1")
    opt_mod._health_hist_program.cache_clear()
    first = health_series(case, tmp_path)
    assert opt_mod._health_hist_program.cache_info().misses == 1
    with watch():
        second = health_series(case, tmp_path)
    assert watch.jits == [] and watch.requests == 0
    info = opt_mod._health_hist_program.cache_info()
    assert (info.misses, info.currsize) == (1, 1) and info.hits >= 1
    prog = opt_mod._health_hist_program(
        ROUNDS, jax.sharding.NamedSharding(make_mesh(case[2]), P()))
    assert prog.__name__ == "sgd_health_hist"
    assert np.isnan(np.asarray(prog())).all()
    want = golden["health"][case_id(case)]
    assert first == second and set(first) == set(want)
    for key, series in want.items():
        # the norms are the parent's; the loss as the header says
        assert first[key] == pytest.approx(
            series, rel=1e-6 if key == "loss" else 0, abs=0)


def test_health_off_no_history_is_built(tmp_path, monkeypatch):
    made = []
    monkeypatch.setattr(opt_mod, "_health_hist_program",
                        lambda *a: made.append(a))
    fit(("lr", "sgd", 8, "xla-while"), tmp_path)
    fit(("lr", "sgd", 8, "xla-while-segments"), tmp_path)
    assert made == []


class _DiesAfter(CheckpointManager):
    """Snapshots as usual, then the process "dies" once ``epoch`` is safe."""

    def __init__(self, base_dir, epoch):
        super().__init__(base_dir)
        self.die_at = epoch

    def save(self, carry, epoch, extras=None):
        out = super().save(carry, epoch, extras)
        if epoch == self.die_at:
            raise KeyboardInterrupt(f"killed after epoch {epoch}")
        return out


@pytest.mark.parametrize("case", [c for c in CASES
                                  if c[3] == "xla-while-segments"],
                         ids=case_id)
def test_a_checkpointed_fit_restored_midway_resumes_to_the_same_answer(
        case, tmp_path, golden):
    want = golden["fits"][case_id(case)]
    with pytest.raises(KeyboardInterrupt):
        fit(case, tmp_path, manager=_DiesAfter(str(tmp_path), 2 * SEGMENT))
    manager = CheckpointManager(str(tmp_path))
    restored = []
    real = manager.restore
    manager.restore = lambda template: (
        restored.append(real(template)), restored[-1])[1]
    coeffs, loss, _ = fit(case, tmp_path, manager=manager)
    assert restored[0] is not None and restored[0][1] == 2 * SEGMENT
    assert coeffs.tolist() == want["coefficients"]
    assert loss == pytest.approx(want["loss"], rel=1e-6, abs=0)


@pytest.mark.parametrize("method,want", [
    # replicated coefficients (8 padded floats) + each replica's 1/8 slice
    # of every moment vector (+ Adam's step scalar)
    ("sgd", {"SGD[logistic]": 32}),
    ("momentum", {"SGD[logistic]": 36, "SGD[logistic].moments": 4}),
    ("adam", {"SGD[logistic]": 44, "SGD[logistic].moments": 12}),
])
def test_the_sharded_update_records_the_same_state_bytes(method, want,
                                                         monkeypatch):
    monkeypatch.setenv(update_sharding.ENV, "1")
    update_sharding.reset_last()
    short_fit(method, make_mesh(8))
    got = {algo: update_sharding.last_state_bytes(algo) for algo in want}
    assert got == want


def write_golden(path):
    import tempfile

    out = {"commit": "6708c0f08c6290ddb4d92b3a37c88bb0f2519f0e",
           "later": LATER, "fits": {}, "health": {}}
    for case in CASES:
        with tempfile.TemporaryDirectory() as tmp:
            coeffs, loss, reported = fit(case, tmp)
        assert reported == case[3], (case, reported)
        out["fits"][case_id(case)] = {"coefficients": coeffs.tolist(),
                                      "loss": loss}
    os.environ[health.HEALTH_ENV] = "1"
    for case in HEALTH_CASES:
        with tempfile.TemporaryDirectory() as tmp:
            out["health"][case_id(case)] = health_series(case, tmp)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    write_golden(sys.argv[1])
