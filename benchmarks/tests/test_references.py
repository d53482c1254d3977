"""The plain references against cases worked by hand, and the control (one
precision down) and the planted faults against the reference: each has to
read far from it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import references
from benchmarks.harness.references import sgd_logistic

LR_PARAMS = {"maxIter": 3, "globalBatchSize": 8, "learningRate": 0.1,
             "tol": 1e-6, "reg": 0.0}


def _lr_table(n=40, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, d)).astype(np.float32)
    y = np.floor(rng.random(n) * 2).astype(np.float32)
    return x, y


def _numpy_sgd(x, y, tasks, params):
    """The schedule spelt out row by row, in float64."""
    n, d = x.shape
    local_n, lb = n // tasks, params["globalBatchSize"] // tasks
    w = np.zeros(d)
    for r in range(params["maxIter"]):
        rows = np.concatenate([s * local_n + np.arange(r * lb, (r + 1) * lb)
                               for s in range(tasks)])
        xb, sign = x[rows].astype(np.float64), 2.0 * y[rows] - 1.0
        grad = xb.T @ (-sign / (np.exp((xb @ w) * sign) + 1.0))
        w -= params["learningRate"] / len(rows) * grad
    return w


@pytest.mark.parametrize("tasks", [1, 4])
def test_sgd_follows_the_upstream_schedule(tasks):
    x, y = _lr_table()
    out = sgd_logistic.run({"features": jnp.asarray(x),
                            "label": jnp.asarray(y)}, LR_PARAMS, tasks)
    assert out["_rounds"] == 3
    np.testing.assert_allclose(out["coefficient"][0],
                               _numpy_sgd(x, y, tasks, LR_PARAMS),
                               rtol=1e-5, atol=1e-9)


def test_sgd_one_round_by_hand():
    # w = 0: every multiplier is -sign/2, so w1 = lr/n * sum(sign * x) / 2
    x = np.array([[1.0, 0.0], [0.0, 1.0]], np.float32)
    y = np.array([1.0, 0.0], np.float32)
    params = dict(LR_PARAMS, maxIter=1, globalBatchSize=2)
    out = sgd_logistic.run({"features": jnp.asarray(x),
                            "label": jnp.asarray(y)}, params, 1)
    np.testing.assert_allclose(out["coefficient"][0], [0.025, -0.025],
                               rtol=1e-6)


def test_sgd_wraps_and_clips_at_the_end_of_a_task_rows():
    x, y = _lr_table(n=20)
    params = dict(LR_PARAMS, maxIter=4, globalBatchSize=8)
    out = sgd_logistic.run({"features": jnp.asarray(x),
                            "label": jnp.asarray(y)}, params, 1)
    # rounds take rows 0-7, 8-15, 16-19 (short), then 0-7 again
    w = np.zeros(4)
    for rows in (range(0, 8), range(8, 16), range(16, 20), range(0, 8)):
        xb = x[list(rows)].astype(np.float64)
        sign = 2.0 * y[list(rows)] - 1.0
        w -= 0.1 / len(rows) * xb.T @ (-sign / (np.exp((xb @ w) * sign) + 1))
    np.testing.assert_allclose(out["coefficient"][0], w, rtol=1e-5)


def test_task_views_reads_each_task_on_its_own_device():
    devices = jax.devices()[:4]
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    x = jax.device_put(jnp.arange(80.0).reshape(40, 2),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec("data", None)))
    views = references.task_views(x, 4)
    assert [int(data[off, 0]) for data, off in views] == [0, 20, 40, 60]
    assert len({next(iter(data.devices())) for data, _ in views}) == 4
    with pytest.raises(ValueError):
        references.task_views(x, 3)


def _gaps(module, columns, params, tasks, **kw):
    reference = module.run(columns, params, tasks)
    return module.compare(module.run(columns, params, tasks, **kw), reference)


@pytest.fixture(scope="module")
def lr_case():
    x, y = _lr_table(n=8000, d=100, seed=5)
    params = dict(LR_PARAMS, maxIter=20, globalBatchSize=400)
    return {"features": jnp.asarray(x), "label": jnp.asarray(y)}, params


@pytest.mark.parametrize("tasks", [1, 4])
def test_control_one_precision_down_is_not_correct(lr_case, tasks):
    """The control, put in the program's place and taken through the
    comparison that decides ``correct`` with the cells' own limit."""
    from benchmarks.harness import check, spec

    limits = spec.load_cell("lr_fit_ref20").config["correct"]["limits"]
    columns, params = lr_case
    reference = sgd_logistic.run(columns, params, tasks)
    control = sgd_logistic.run(columns, params, tasks, precision="bfloat16")
    correct, compared = check.decide(
        [{"coefficient": control["coefficient"]}], sgd_logistic, reference,
        limits)
    assert correct is False
    assert compared["coef_gap"]["value"] > 100 * compared["coef_gap"]["limit"]
    same, _ = check.decide([{"coefficient": reference["coefficient"]}],
                           sgd_logistic, reference, limits)
    assert same is True


@pytest.mark.parametrize("fault", sgd_logistic.FAULTS)
def test_planted_faults_read_far_from_the_reference(fault, lr_case):
    assert _gaps(sgd_logistic, *lr_case, 4, fault=fault)["coef_gap"] > 0.1


def test_compare_of_a_wrong_shape_or_a_nan_is_infinite():
    ref = {"coefficient": np.ones((1, 3))}
    assert sgd_logistic.compare({"coefficient": np.ones((1, 2))},
                                ref)["coef_gap"] == float("inf")
    assert sgd_logistic.compare({"coefficient": np.full((1, 3), np.nan)},
                                ref)["coef_gap"] == float("inf")
    assert sgd_logistic.compare({}, ref)["coef_gap"] == float("inf")
