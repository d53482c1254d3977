"""The plain dense fit is one program, the while-loop segment program
(``xla-while``), whatever its round count, batch or mesh. It is held here
to a float64 NumPy reference of the reference implementation's round
(SGD.java:206-213, 231-243, 262-284) that shares no code with the program:
per task of the data axis a contiguous batch share with clip at the shard's
end and wrap to zero, the all-reduced [grad | weight | loss], the step and
the elastic-net shrink, and the stop at ``loss < tol``.

A fit with no weight column builds none: its programs take no weight
operand and a row weighs 1 where its round's batch holds it (0 where
``ensure_on_mesh`` padded it on). It is held to the same fit given a device
column of ones on every dense path, and the program of a fit that has a
column to the parent's text (``fixtures/sgd_programs/weighted_lowered.json``,
written from commit fe1a259 by running this file as a script there).
"""

import dataclasses
import hashlib
import itertools
import json
import os
import sys

if __name__ == "__main__":  # the fixture writer: the mesh conftest.py gives
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.iteration import CheckpointManager, IterationConfig
from flink_ml_tpu.observability import health
from flink_ml_tpu.ops import optimizer as opt_mod
from flink_ml_tpu.ops.losses import (
    BinaryLogisticLoss,
    HingeLoss,
    LeastSquareLoss,
)
from flink_ml_tpu.ops.optimizer import SGD, SGDParams
from flink_ml_tpu.parallel import create_mesh, update_sharding

WEIGHTED_LOWERED = os.path.join(os.path.dirname(__file__), "fixtures",
                                "sgd_programs", "weighted_lowered.json")


def _reference_terms(name, dots, y, w):
    """(loss sum, gradient multipliers) of one minibatch, float64."""
    if name == "least_square":
        return np.sum(w * 0.5 * (dots - y) ** 2), w * (dots - y)
    s = 2.0 * y - 1.0
    if name == "hinge":
        active = 1.0 - s * dots > 0.0
        return np.sum(w * np.maximum(1.0 - s * dots, 0.0)), -s * w * active
    return (np.sum(w * np.logaddexp(0.0, -s * dots)),
            w * -s / (np.exp(s * dots) + 1.0))


def _reference_fit(prm, loss_name, x, y, w, tasks):
    """``(coeffs, last mean loss, rounds run)`` of SGD.java's schedule over
    ``tasks`` shards of ``ceil(n / tasks)`` rows (the last one short), in
    float64 on the float32 values the device holds."""
    x, y = (np.asarray(a, np.float32).astype(np.float64) for a in (x, y))
    n, d = x.shape
    w = (np.ones(n) if w is None
         else np.asarray(w, np.float32).astype(np.float64))
    shard = -(-n // tasks)
    share = [min(prm.global_batch_size // tasks
                 + (t < prm.global_batch_size % tasks), shard)
             for t in range(tasks)]
    offsets = [0] * tasks
    coeffs, mean_loss, rounds = np.zeros(d), np.inf, 0
    while rounds < prm.max_iter and not mean_loss < prm.tol:
        rows = []
        for t in range(tasks):
            local = np.arange(offsets[t], min(offsets[t] + share[t], shard))
            rows.append(t * shard + local)
            offsets[t] = (0 if offsets[t] + share[t] >= shard
                          else offsets[t] + share[t])
        rows = np.concatenate(rows)
        rows = rows[rows < n]  # the last shard's padding weighs nothing
        xb, yb, wb = x[rows], y[rows], w[rows]
        loss_sum, multipliers = _reference_terms(loss_name, xb @ coeffs,
                                                 yb, wb)
        total_w = wb.sum()
        if total_w > 0:
            coeffs = coeffs - prm.learning_rate / total_w * (
                xb.T @ multipliers)
            coeffs = coeffs - prm.learning_rate * prm.reg * (
                prm.elastic_net * np.sign(coeffs)
                + (1.0 - prm.elastic_net) * coeffs)
        mean_loss = loss_sum / max(total_w, 1e-30)
        rounds += 1
    return coeffs, mean_loss, rounds


@dataclasses.dataclass(frozen=True)
class Case:
    loss: type
    prm: SGDParams
    rows: int
    dim: int
    learnable: bool = False
    weighted: bool = False
    mesh: tuple = None
    rounds: int = None  # rounds the reference must run (default: all)


CASES = {
    **{f"loss-{cls.NAME}": Case(
        cls, SGDParams(learning_rate=0.05, global_batch_size=160,
                       max_iter=7, tol=0.0), 1000, 8)
       for cls in (BinaryLogisticLoss, HingeLoss, LeastSquareLoss)},
    # shard length 125 on the 8-device mesh, share 20: round 7 clips at
    # the shard's end (5 rows count), round 8 starts again at zero
    "clip-and-wrap": Case(
        BinaryLogisticLoss, SGDParams(learning_rate=0.1,
                                      global_batch_size=160, max_iter=9,
                                      tol=0.0), 1000, 5, learnable=True),
    # a tol the first round already meets: one round of six
    "tol-early-exit": Case(
        BinaryLogisticLoss, SGDParams(learning_rate=0.05,
                                      global_batch_size=80, max_iter=6,
                                      tol=1e9), 400, 4, rounds=1),
    "weighted-regularised": Case(
        BinaryLogisticLoss, SGDParams(learning_rate=0.1,
                                      global_batch_size=240, max_iter=5,
                                      tol=0.0, reg=0.02, elastic_net=0.4),
        600, 6, weighted=True),
    "tensor-parallel-mesh": Case(
        BinaryLogisticLoss, SGDParams(learning_rate=0.1,
                                      global_batch_size=200, max_iter=5,
                                      tol=0.0), 800, 10,
        mesh=((4, 2), ("data", "model"))),
    # past the round count at which a plain fit used to change programs;
    # shard length 38, share 4: six wraps, each after a 2-row clip
    "rounds-65": Case(
        BinaryLogisticLoss, SGDParams(learning_rate=0.1,
                                      global_batch_size=32, max_iter=65,
                                      tol=0.0), 300, 3),
    # 31 rows over eight tasks: seven take 4 and the last 3, and the last
    # shard holds 34 rows and 4 of padding
    "batch-31-of-8": Case(
        BinaryLogisticLoss, SGDParams(learning_rate=0.1,
                                      global_batch_size=31, max_iter=12,
                                      tol=0.0), 300, 3),
}


@pytest.mark.parametrize("name", CASES)
def test_plain_fit_matches_the_float64_reference(rng, name):
    case = CASES[name]
    mesh, tasks = None, len(jax.devices())
    if case.mesh:
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        mesh, tasks = create_mesh(*case.mesh), case.mesh[0][0]
    x = rng.normal(size=(case.rows, case.dim))
    y = ((x @ rng.normal(size=case.dim) > 0) if case.learnable
         else (rng.random(case.rows) > 0.5)).astype(np.float64)
    w = rng.random(case.rows) + 0.5 if case.weighted else None

    sgd = SGD(case.prm)
    coeffs, mean_loss = sgd.optimize(case.loss(), np.zeros(case.dim), x, y,
                                     w, mesh=mesh)
    assert sgd.last_execution_path == "xla-while"
    want, want_loss, rounds = _reference_fit(case.prm, case.loss.NAME, x, y,
                                             w, tasks)
    assert rounds == (case.rounds or case.prm.max_iter)
    # float32 on the device against float64: the nine cases read 2e-6 of a
    # coefficient and 9e-8 of the loss at most
    np.testing.assert_allclose(coeffs, want, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(mean_loss, want_loss, rtol=2e-6)


@pytest.mark.parametrize("rounds,unroll_max", [
    (1, None), (20, None), (64, None), (65, None), (20, "0"), (20, "64")])
def test_every_plain_fit_runs_the_while_program(rng, monkeypatch, rounds,
                                                unroll_max):
    """One program at every round count. ``FLINK_ML_TPU_SGD_UNROLL_MAX``,
    which the benchmark's LR configurations still set, selects nothing:
    the answer is the unset environment's bit for bit."""
    x = rng.normal(size=(1000, 8))
    y = (rng.random(1000) > 0.5).astype(np.float64)

    def fit():
        sgd = SGD(SGDParams(learning_rate=0.05, global_batch_size=160,
                            max_iter=rounds, tol=0.0))
        coeffs, loss = sgd.optimize(BinaryLogisticLoss(), np.zeros(8), x, y)
        assert sgd.last_execution_path == "xla-while"
        return coeffs.tolist(), loss

    monkeypatch.delenv("FLINK_ML_TPU_SGD_UNROLL_MAX", raising=False)
    unset = fit()
    if unroll_max is not None:
        monkeypatch.setenv("FLINK_ML_TPU_SGD_UNROLL_MAX", unroll_max)
        assert fit() == unset


# -- a fit with no weight column builds none ----------------------------------

#: mesh name -> (shape, axis names)
UNIT_MESHES = {"one-device": ((1,), ("data",)),
               "data-4": ((4,), ("data",)),
               "tensor-parallel": ((4, 2), ("data", "model"))}
UNIT_PATHS = ("xla-while", "xla-while-segments", "host-rounds")
#: 400 rows divide over the data axis of every mesh; 397 leave the last of
#: four shards 97 rows and 3 of padding, which the second round's batch
#: (rows 50-99 of each shard) reaches
UNIT_ROWS = {"rows-divide": 400, "rows-padded": 397}
#: the sharded update is a data-parallel mesh's: a tensor-parallel mesh
#: never takes it, so that pairing would be the "replicated" case again
UNIT_CASES = [
    c for c in itertools.product(
        UNIT_PATHS, UNIT_MESHES, ("sgd", "momentum", "adam"),
        ("replicated", "sharded-update"), UNIT_ROWS,
        ("health-off", "health-on"))
    if not (c[1] == "tensor-parallel" and c[3] == "sharded-update")]


UNIT_PRM = SGDParams(learning_rate=0.1, global_batch_size=200, max_iter=5,
                     tol=0.0)


def _unit_mesh(name):
    shape, names = UNIT_MESHES[name]
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    return create_mesh(shape, names,
                       devices=jax.devices()[:int(np.prod(shape))])


def _unit_data(rng, rows):
    x = rng.normal(size=(UNIT_ROWS[rows], 5)).astype(np.float32)
    return x, (x @ rng.normal(size=5) > 0).astype(np.float32)


def _unit_fit(path, mesh, method, x, y, w, ckpt_dir):
    """``(coefficients, loss)`` of one 5-round fit on ``path``."""
    config = None
    if path == "xla-while-segments":
        config = IterationConfig(
            checkpoint_interval=2,
            checkpoint_manager=CheckpointManager(str(ckpt_dir)))
    elif path == "host-rounds":
        config = IterationConfig(mode="host")
    sgd = SGD(dataclasses.replace(UNIT_PRM, method=method))
    out = sgd.optimize(BinaryLogisticLoss(), np.zeros(5), x, y, w,
                       mesh=mesh, config=config)
    assert sgd.last_execution_path == path
    return out


@pytest.mark.parametrize("path,mesh_name,method,update,rows,telemetry",
                         UNIT_CASES, ids=map("-".join, UNIT_CASES))
def test_no_weight_column_answers_as_a_column_of_ones(
        rng, tmp_path, monkeypatch, path, mesh_name, method, update, rows,
        telemetry):
    monkeypatch.setenv(update_sharding.ENV,
                       "1" if update == "sharded-update" else "0")
    monkeypatch.setenv(health.HEALTH_ENV,
                       "1" if telemetry == "health-on" else "0")
    series, real = [], health.check_fit
    monkeypatch.setattr(
        health, "check_fit", lambda algo, rows, **kw: (
            series.append({k: np.asarray(v, np.float64)
                           for k, v in rows.items()}),
            real(algo, rows, **kw))[1])
    mesh = _unit_mesh(mesh_name)
    x, y = _unit_data(rng, rows)
    ones = jnp.ones(y.shape, jnp.float32)  # a device column, as a table's

    unit = _unit_fit(path, mesh, method, x, y, None, tmp_path / "unit")
    unit_series = series[:]
    del series[:]
    column = _unit_fit(path, mesh, method, x, y, ones, tmp_path / "column")
    # multiplying by 1.0 is exact; what may differ is how the compiler
    # orders a sum it fuses without the multiply: the cases read 0 on the
    # coefficients and 2e-7 of the loss at most
    assert unit[0].tolist() == column[0].tolist()
    assert unit[1] == pytest.approx(column[1], rel=1e-6, abs=0)
    assert len(unit_series) == len(series)
    for got, want in zip(unit_series, series):
        for key in want:
            np.testing.assert_allclose(got[key], want[key], rtol=1e-6)


def test_the_padded_rows_weigh_nothing_by_the_row_count_alone(rng):
    """The case above is one the padding decides: the same program built
    without the row count counts the 3 padded rows and answers otherwise."""
    mesh = _unit_mesh("data-4")
    x, y = _unit_data(rng, "rows-padded")
    n = y.shape[0]
    rows = NamedSharding(mesh, P("data"))
    pad = [(0, 400 - n)]

    def run(n_valid):
        prog = opt_mod._build_sgd_segment_program(
            BinaryLogisticLoss, mesh, UNIT_PRM, weighted=False,
            n_valid=n_valid)
        out = prog(jax.device_put(np.pad(x, pad + [(0, 0)]), rows),
                   jax.device_put(np.pad(y, pad), rows), None,
                   jnp.zeros((5,), jnp.float32),
                   jax.device_put(np.zeros((4,), np.int32), rows), (),
                   np.int32(0), np.int32(5))
        return np.asarray(out[0], np.float64)

    want, _ = _unit_fit("xla-while", mesh, "sgd", x, y,
                        jnp.ones((n,), jnp.float32), None)
    assert run(n).tolist() == want.tolist()
    assert np.abs(run(None) - want).max() > 1e-5


# -- a fit with a weight column runs the parent's program ----------------------

#: program name -> (builder, its keywords, trailing operands)
LOWERED = {
    "segment": ("_build_sgd_segment_program", {}, ("epoch0", "limit")),
    "segment-fused": ("_build_sgd_segment_program", {"fused": True},
                      ("epoch0", "limit")),
    "segment-fused-health": ("_build_sgd_segment_program",
                             {"fused": True, "health": True},
                             ("epoch0", "limit", "hist", "fin")),
    "segment-fused-sharded": ("_build_sgd_segment_program",
                              {"fused": True, "sharded": True},
                              ("epoch0", "limit")),
    "round": ("_build_sgd_round_program", {}, ()),
}


def _shape(mesh, dims, spec, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype,
                                sharding=NamedSharding(mesh, spec))


def _carry_shapes(mesh, method, sharded, n, d, weighted):
    """A segment or round program's operands up to the carry, as shapes:
    the table (its weight column only for a ``weighted`` fit), the
    coefficients, the per-task offsets and the rule's moments."""
    tp = "model" in mesh.axis_names
    wspec = P("model") if tp else P()
    mspec = P("data") if sharded else wspec
    vec = _shape(mesh, (d,), mspec)
    opt = {"sgd": (), "momentum": (vec,),
           "adam": (vec, vec, _shape(mesh, (), P()))}[method]
    rows = _shape(mesh, (n,), P("data"))
    return [_shape(mesh, (n, d), P("data", "model") if tp else P("data")),
            rows, rows if weighted else None, _shape(mesh, (d,), wspec),
            _shape(mesh, (mesh.shape["data"],), P("data"), jnp.int32), opt]


def weighted_lowered_text(program, mesh_name, method="sgd", n=400, d=8):
    """The lowered text of ``program`` for a fit that has a weight column,
    at ``n`` rows of ``d`` features on ``mesh_name``."""
    builder, keywords, trailing = LOWERED[program]
    mesh = _unit_mesh(mesh_name)
    prm = dataclasses.replace(UNIT_PRM, method=method)
    operands = {"epoch0": np.int32(0), "limit": np.int32(5),
                "hist": _shape(mesh, (5, 3), P()), "fin": np.bool_(True)}
    prog = getattr(opt_mod, builder)(BinaryLogisticLoss, mesh, prm,
                                     **keywords)
    if builder == "_build_sgd_round_program":
        prog = jax.jit(prog)
    # the sharded build is an ``instrumented_jit``, which keeps its jit
    return getattr(prog, "_jitted", prog).lower(
        *_carry_shapes(mesh, method, keywords.get("sharded"), n, d, True),
        *(operands[name] for name in trailing)).as_text()


LOWERED_CASES = [
    ("segment", "one-device", "sgd"), ("segment-fused", "one-device", "sgd"),
    ("segment-fused", "data-4", "sgd"), ("segment-fused", "data-4", "adam"),
    ("segment-fused", "tensor-parallel", "momentum"),
    ("segment-fused-health", "data-4", "sgd"),
    ("segment-fused-sharded", "data-4", "adam"),
    ("round", "one-device", "sgd"), ("round", "data-4", "momentum"),
]


@pytest.fixture
def two_reads(monkeypatch):
    """Every batch past the on-chip gate: the programs built under it read
    their batch from HBM once a product, as the parent's did. The builders'
    caches are emptied on both sides, so no program of one form is found
    under the other."""
    def clear():
        opt_mod._build_sgd_segment_program.cache_clear()
        opt_mod._build_sgd_round_program.cache_clear()

    clear()
    monkeypatch.setattr(opt_mod, "ONCHIP_BATCH_BYTES", 0)
    yield
    clear()


@pytest.mark.parametrize("program,mesh_name,method", LOWERED_CASES,
                         ids=map("-".join, LOWERED_CASES))
def test_a_weighted_fit_lowers_to_the_parents_text(program, mesh_name,
                                                   method, two_reads):
    """Past the on-chip gate a round reads its batch where it lies, as the
    parent did: the program is the parent's text."""
    with open(WEIGHTED_LOWERED) as f:
        want = json.load(f)["programs"]["-".join((program, mesh_name,
                                                  method))]
    text = weighted_lowered_text(program, mesh_name, method)
    assert len(text) == want["characters"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]


# -- a round reads its batch from HBM once ------------------------------------

@dataclasses.dataclass(frozen=True)
class Parity:
    loss: type = BinaryLogisticLoss
    rows: int = 1000
    dim: int = 7
    batch: int = 160
    rounds: int = 9
    method: str = "sgd"
    weighted: bool = False
    path: str = "xla-while"


#: on four devices a shard is a quarter of the rows; on one, all of them
PARITY = {
    **{f"loss-{cls.NAME}": Parity(cls) for cls in (
        BinaryLogisticLoss, HingeLoss, LeastSquareLoss)},
    "column-weights": Parity(weighted=True),
    "hinge-column-weights": Parity(HingeLoss, weighted=True),
    # 397 rows: the last of four shards holds 97 and 3 of padding
    "padded-rows": Parity(rows=397, batch=200, rounds=5),
    # shards of 250 (or 1000) rows, windows of 90 (or 360): the third
    # round's window is clamped to the shard's end, the fourth wraps to 0
    "clip-at-end-and-wrap": Parity(batch=360, rounds=6),
    # the window is the whole shard, every round
    "whole-table": Parity(rows=300, batch=1200, rounds=4),
    "momentum": Parity(method="momentum"),
    "adam": Parity(LeastSquareLoss, method="adam", weighted=True),
    "segments": Parity(method="adam", path="xla-while-segments"),
    "host-rounds": Parity(weighted=True, path="host-rounds"),
}


def parity_fit(case, mesh, ckpt_dir):
    """``(coefficients, loss, HBM reads of a batch)`` of one fit."""
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics

    rng = np.random.default_rng(41)
    x = rng.normal(size=(case.rows, case.dim)).astype(np.float32)
    y = (x @ rng.normal(size=case.dim)).astype(np.float32)
    if case.loss is not LeastSquareLoss:
        y = (y > 0).astype(np.float32)
    w = (jnp.asarray(rng.random(case.rows) + 0.5, jnp.float32)
         if case.weighted else None)
    config = {"xla-while": None,
              "host-rounds": IterationConfig(mode="host"),
              "xla-while-segments": IterationConfig(
                  checkpoint_interval=2,
                  checkpoint_manager=CheckpointManager(str(ckpt_dir)))
              }[case.path]
    prm = SGDParams(learning_rate=0.1, global_batch_size=case.batch,
                    max_iter=case.rounds, tol=0.0, method=case.method)
    counter = metrics.group(ML_GROUP, "sgd")
    before = counter.get_counter("batchReads")
    sgd = SGD(prm)
    coeffs, loss = sgd.optimize(case.loss(), np.zeros(case.dim), x, y, w,
                                mesh=mesh, config=config)
    assert sgd.last_execution_path == case.path
    return coeffs, loss, counter.get_counter("batchReads") - before


def assert_reassociated(one, two):
    """The same float32 products, summed in another order: on the CPU the
    cases read 7.5e-9 of a coefficient and 9.2e-8 of the loss at most, most
    of them 0."""
    np.testing.assert_allclose(one[0], two[0], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(one[1], two[1], rtol=1e-6)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", PARITY)
def test_the_one_read_form_answers_as_the_two_read_form(
        name, devices, tmp_path, request):
    case = PARITY[name]
    mesh = create_mesh(devices=jax.devices()[:devices])
    one = parity_fit(case, mesh, tmp_path / "one")
    assert one[2] == case.rounds  # a read a round
    request.getfixturevalue("two_reads")
    two = parity_fit(case, mesh, tmp_path / "two")
    assert two[2] == 2 * case.rounds  # a read a product
    assert_reassociated(one, two)


@pytest.mark.parametrize("method", ["sgd", "adam"])
def test_a_tensor_parallel_round_reads_its_feature_shard_once(
        method, tmp_path, request):
    """Under ``model=2`` a task's window is its ``(d / 2, rows)`` block: the
    margins are partial products added over the model axis, the gradient
    stays the shard's."""
    mesh = _unit_mesh("tensor-parallel")
    case = Parity(dim=10, method=method)
    one = parity_fit(case, mesh, tmp_path / "one")
    request.getfixturevalue("two_reads")
    two = parity_fit(case, mesh, tmp_path / "two")
    assert (one[2], two[2]) == (case.rounds, 2 * case.rounds)
    assert_reassociated(one, two)


def test_the_gate_reads_the_padded_window_a_task_holds():
    """A window is its rows by its local features padded to 8, in float32;
    at the budget it is read once, a row past it twice."""
    budget = opt_mod.ONCHIP_BATCH_BYTES
    rows = budget // (8 * 4)
    assert opt_mod._batch_onchip(rows, 8)
    assert opt_mod._batch_onchip(rows, 1)  # one feature is a tile of 8
    assert not opt_mod._batch_onchip(rows + 1, 8)
    assert not opt_mod._batch_onchip(rows, 9)
    prm = SGDParams(global_batch_size=4 * rows + 3)
    one, four = (create_mesh(devices=jax.devices()[:k]) for k in (1, 4))
    # the first task of four takes the remainder's extra row: over the gate
    assert opt_mod._batch_form(prm, four, 8 * rows, 8) == "hbm"
    assert opt_mod._batch_form(
        SGDParams(global_batch_size=4 * rows), four, 8 * rows, 8) == "onchip"
    # a shard shorter than the share bounds the window
    assert opt_mod._batch_form(prm, one, rows, 8) == "onchip"
    if len(jax.devices()) >= 8:
        tp = create_mesh((4, 2), ("data", "model"))
        assert opt_mod._batch_form(
            SGDParams(global_batch_size=4 * rows), tp, 8 * rows, 16) == (
                "onchip")


def test_a_batch_over_the_gate_reads_the_table_twice(monkeypatch):
    """A window past the budget takes the two-read form and says so: 2.1M
    rows of 8 features a round on one device, 64 MiB and a row."""
    from flink_ml_tpu.observability import tracing

    rows = opt_mod.ONCHIP_BATCH_BYTES // 32 + 1
    x = np.ones((rows, 8), np.float32)
    x[:, 0] = np.linspace(-1.0, 1.0, rows, dtype=np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    opened = []
    real = tracing.tracer.span

    def span(name, **attrs):
        opened.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(opt_mod.tracer, "span", span)
    sgd = SGD(SGDParams(learning_rate=0.5, global_batch_size=rows,
                        max_iter=2, tol=0.0))
    coeffs, _ = sgd.optimize(BinaryLogisticLoss(), np.zeros(8), x, y,
                             mesh=create_mesh(devices=jax.devices()[:1]))
    assert {attrs.get("batch") for name, attrs in opened
            if name in ("sgd.optimize", "sgd.launch")} == {"hbm"}
    # the data pulls the first coefficient up, the rest stay level
    assert coeffs[0] > 0.1 and np.ptp(coeffs[1:]) < 1e-6


# -- a plain fit's start is made inside the same program -----------------------

def _loops(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _loops(sub)


FRESH_CASES = [("one-device", "sgd", False), ("data-4", "sgd", False),
               ("data-4", "adam", False), ("data-4", "momentum", True),
               ("data-4", "adam", True), ("tensor-parallel", "momentum", False),
               ("tensor-parallel", "adam", False)]


@pytest.mark.parametrize("mesh_name,method,sharded", FRESH_CASES)
def test_the_fresh_form_wraps_the_loop_the_carried_form_runs(
        mesh_name, method, sharded):
    """``fresh`` changes what crosses the program's boundary and nothing
    inside the loop: one ``while`` in either form, its body and its test
    the same text, so a round's arithmetic cannot differ; the fresh form
    takes the table and the coefficients and no other operand, and returns
    what the carried form returns."""
    mesh = _unit_mesh(mesh_name)
    prm = dataclasses.replace(UNIT_PRM, method=method)
    traced = {}
    for fresh in (False, True):
        prog = opt_mod._build_sgd_segment_program(
            BinaryLogisticLoss, mesh, prm, fused=True, sharded=sharded,
            weighted=False, fresh=fresh)
        # the fresh form takes the table and the coefficients alone
        operands = _carry_shapes(mesh, method, sharded, 400, 8, False)
        operands = operands[:4] if fresh else operands + [np.int32(0),
                                                          np.int32(5)]
        traced[fresh] = jax.make_jaxpr(getattr(prog, "_jitted", prog))(
            *operands)
    carried, = _loops(traced[False].jaxpr)
    made, = _loops(traced[True].jaxpr)
    for part in ("body_jaxpr", "cond_jaxpr"):
        assert str(made.params[part]) == str(carried.params[part])
    assert len(traced[True].jaxpr.invars) == 3
    assert ([v.aval for v in traced[True].jaxpr.outvars]
            == [v.aval for v in traced[False].jaxpr.outvars])


def test_a_health_armed_program_has_no_fresh_form():
    opt_mod._build_sgd_segment_program.cache_clear()
    with pytest.raises(ValueError, match="carry"):
        opt_mod._build_sgd_segment_program(
            BinaryLogisticLoss, _unit_mesh("one-device"), UNIT_PRM,
            health=True, fresh=True)


def write_weighted_lowered(path, commit):
    out = {"commit": commit, "programs": {}}
    for case in LOWERED_CASES:
        text = weighted_lowered_text(*case)
        out["programs"]["-".join(case)] = {
            "characters": len(text),
            "sha256": hashlib.sha256(text.encode()).hexdigest()}
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    write_weighted_lowered(sys.argv[1], sys.argv[2])
