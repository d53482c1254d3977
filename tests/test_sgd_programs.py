"""The plain dense fit is one program, the while-loop segment program
(``xla-while``), whatever its round count, batch or mesh. It is held here
to a float64 NumPy reference of the reference implementation's round
(SGD.java:206-213, 231-243, 262-284) that shares no code with the program:
per task of the data axis a contiguous batch share with clip at the shard's
end and wrap to zero, the all-reduced [grad | weight | loss], the step and
the elastic-net shrink, and the stop at ``loss < tol``.
"""

import dataclasses

import jax
import numpy as np
import pytest

from flink_ml_tpu.ops.losses import (
    BinaryLogisticLoss,
    HingeLoss,
    LeastSquareLoss,
)
from flink_ml_tpu.ops.optimizer import SGD, SGDParams
from flink_ml_tpu.parallel import create_mesh


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
