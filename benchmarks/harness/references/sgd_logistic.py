"""Minibatch SGD for binary logistic loss, as upstream's ``SGD.java`` runs
it under ``tasks`` parallel tasks: task ``s`` holds rows
``[s*n/tasks, (s+1)*n/tasks)`` and in each round takes its next
``globalBatchSize/tasks`` rows (the first ``globalBatchSize % tasks`` tasks
one more), clipped at the end of its rows, starting again at 0 after the
end; gradient, weight and loss sums are added over the tasks, then
``w -= learningRate / weightSum * gradSum``; a round whose mean loss is
under ``tol`` is the last. ``reg`` must be 0."""

from __future__ import annotations

import functools

import numpy as np

from . import device_precision, np_dtype, task_views, worst_gap

FAULTS = ("state_unchanged", "half_batch", "no_exchange")


@functools.lru_cache(maxsize=None)
def _partials_program(lb: int, precision: str, weighted: bool):
    import jax
    import jax.numpy as jnp

    dtype_name, hi = device_precision(precision)
    dtype = jnp.dtype(dtype_name)

    def partials(x, y, sw, w, start, first_valid, last_valid):
        xb = jax.lax.dynamic_slice_in_dim(x, start, lb).astype(dtype)
        yb = jax.lax.dynamic_slice_in_dim(y, start, lb).astype(dtype)
        rows = jnp.arange(lb)
        wb = ((rows >= first_valid) & (rows < last_valid)).astype(dtype)
        if weighted:
            wb = wb * jax.lax.dynamic_slice_in_dim(sw, start, lb).astype(dtype)
        sign = 2.0 * yb - 1.0
        margins = jnp.dot(xb, w.astype(dtype), precision=hi) * sign
        loss = jnp.sum(wb * jnp.logaddexp(0.0, -margins))
        mult = wb * (-sign / (jnp.exp(margins) + 1.0))
        grad = jnp.dot(mult, xb, precision=hi)
        return grad, jnp.sum(wb), loss

    return jax.jit(partials)


def run(columns: dict, params: dict, tasks: int,
        precision: str = "float32", fault: str = None) -> dict:
    if float(params.get("reg", 0.0)) != 0.0:
        raise NotImplementedError("this reference covers reg = 0 only")
    x = columns[params.get("featuresCol", "features")]
    y = columns[params.get("labelCol", "label")]
    weight_col = params.get("weightCol")
    sw = columns[weight_col] if weight_col else y
    n, d = x.shape
    local_n = n // tasks
    gb = int(params["globalBatchSize"])
    lr, tol = float(params["learningRate"]), float(params["tol"])
    state = np_dtype(precision)
    device_dtype = precision
    views = list(zip(task_views(x, tasks), task_views(y, tasks),
                     task_views(sw, tasks)))
    w = np.zeros(d, state)
    offsets = [0] * tasks
    rounds = 0
    if fault == "state_unchanged":
        return {"coefficient": np.asarray(w, np.float64)[None], "_rounds": 0}
    for _ in range(int(params["maxIter"])):
        pending = []
        for s, ((xs, base), (ys, _), (ws, _)) in enumerate(views):
            lb = min(gb // tasks + (1 if s < gb % tasks else 0), local_n)
            start = min(offsets[s], local_n - lb)
            first = offsets[s] - start
            last = lb // 2 if fault == "half_batch" else lb
            offsets[s] = (0 if offsets[s] + lb >= local_n
                          else offsets[s] + lb)
            if fault == "no_exchange" and s > 0:
                continue
            prog = _partials_program(lb, device_dtype, bool(weight_col))
            pending.append(prog(xs, ys, ws, np.asarray(w), base + start,
                                first, last))
        grad, total_w, loss = (
            sum(np.asarray(p[i]).astype(state) for p in pending)
            for i in range(3))
        rounds += 1
        if float(total_w) > 0:
            w = (w - np.asarray(lr, state) / total_w * grad).astype(state)
        if float(loss) / max(float(total_w), 1e-30) < tol:
            break
    return {"coefficient": np.asarray(w, np.float64)[None],
            "_rounds": rounds}


def compare(answer: dict, reference: dict) -> dict:
    return {"coef_gap": worst_gap(answer.get("coefficient"),
                                  reference["coefficient"])}
