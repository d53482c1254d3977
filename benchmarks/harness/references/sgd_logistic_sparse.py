"""Minibatch SGD for binary logistic loss over a sparse vector column
(``{"ids", "values", "size"}``, ``k`` entries a row), on upstream's ``SGD.java``
schedule exactly as ``sgd_logistic.py`` runs it: task ``s`` of ``tasks``
holds rows ``[s*n/tasks, (s+1)*n/tasks)`` and in each round takes its next
``globalBatchSize/tasks`` rows (the first ``globalBatchSize % tasks`` tasks
one more), clipped at the end of its rows, starting again at 0 after the
end; gradient, weight and loss sums are added over the tasks, then
``w -= learningRate / weightSum * gradSum``; a round whose mean loss is
under ``tol`` is the last. ``reg`` must be 0.

Another algorithm than any device program: only the rows a round touches
come to the host (a task's window, sliced on the device that holds it), and
there the margins are a fancy index of the coefficients and the gradient an
``np.bincount`` over the ids, in float64. Two entries of a row in one
bucket both count, as a CSR sum adds them.

The control (``precision="bfloat16"``) rounds the values, the margins, the
multipliers, the gradient and the state to bfloat16; the ids stay exact.
"""

from __future__ import annotations

import functools

import numpy as np

from . import np_dtype, task_views, worst_gap

FAULTS = ("state_unchanged", "half_batch", "duplicates_dropped")


@functools.lru_cache(maxsize=None)
def _window_program(lb: int):
    """``window(a, start)``: rows ``[start, start + lb)`` of one shard, a
    rank-2 one as ``(k, lb)``: the transpose of the column-major table is a
    relabelling, and the slice goes to the host lane-dense."""
    import jax

    def window(a, start):
        if a.ndim == 2:
            return jax.lax.dynamic_slice_in_dim(a.T, start, lb, axis=1)
        return jax.lax.dynamic_slice_in_dim(a, start, lb)

    return jax.jit(window)


def _rows(view, start: int, lb: int, first: int, last: int) -> np.ndarray:
    """Rows ``[start + first, start + last)`` of a task's view, on the host,
    a rank-2 column as ``(rows, k)``."""
    data, base = view
    out = np.asarray(_window_program(lb)(data, np.int32(base + start)))
    return (out.T if out.ndim == 2 else out)[first:last]


def run(columns: dict, params: dict, tasks: int,
        precision: str = "float32", fault: str = None) -> dict:
    if float(params.get("reg", 0.0)) != 0.0:
        raise NotImplementedError("this reference covers reg = 0 only")
    sparse = columns[params.get("featuresCol", "features")]
    num_features = int(sparse["size"])
    y = columns[params.get("labelCol", "label")]
    weight_col = params.get("weightCol")
    n = y.shape[0]
    local_n = n // tasks
    gb = int(params["globalBatchSize"])
    lr, tol = float(params["learningRate"]), float(params["tol"])
    state = np_dtype(precision)
    if precision == "float32":
        def rounded(a):
            return np.asarray(a, np.float64)
    else:
        def rounded(a):
            return np.asarray(a, np.float64).astype(state).astype(
                np.float64)
    views = list(zip(task_views(sparse["ids"], tasks),
                     task_views(sparse["values"], tasks),
                     task_views(y, tasks),
                     task_views(columns[weight_col] if weight_col else y,
                                tasks)))
    w = np.zeros(num_features, state)
    offsets = [0] * tasks
    rounds = 0
    if fault == "state_unchanged":
        return {"coefficient": np.asarray(w, np.float64)[None], "_rounds": 0}
    for _ in range(int(params["maxIter"])):
        coeffs = np.asarray(w, np.float64)
        grad = np.zeros(num_features, np.float64)
        total_w = loss = 0.0
        for s, (ids_v, vals_v, y_v, sw_v) in enumerate(views):
            lb = min(gb // tasks + (1 if s < gb % tasks else 0), local_n)
            start = min(offsets[s], local_n - lb)
            first = offsets[s] - start
            last = lb // 2 if fault == "half_batch" else lb
            offsets[s] = (0 if offsets[s] + lb >= local_n
                          else offsets[s] + lb)
            ids = _rows(ids_v, start, lb, first, last)
            vals = rounded(_rows(vals_v, start, lb, first, last))
            sign = 2.0 * _rows(y_v, start, lb, first, last) - 1.0
            wb = (_rows(sw_v, start, lb, first, last).astype(np.float64)
                  if weight_col else np.ones(len(ids)))
            margins = rounded(np.sum(coeffs[ids] * vals, axis=1) * sign)
            loss += float(np.sum(wb * np.logaddexp(0.0, -margins)))
            mult = rounded(wb * (-sign / (np.exp(margins) + 1.0)))
            terms = (mult[:, None] * vals).ravel()
            if fault == "duplicates_dropped":
                # one update a bucket: the last write of a scatter wins
                g = np.zeros(num_features, np.float64)
                g[ids.ravel()] = terms
            else:
                g = np.bincount(ids.ravel(), terms, minlength=num_features)
            grad += g
            total_w += float(np.sum(wb))
        rounds += 1
        if total_w > 0:
            grad = rounded(grad).astype(state)
            w = (w - np.asarray(lr, state) / np.asarray(total_w, state)
                 * grad).astype(state)
        if loss / max(total_w, 1e-30) < tol:
            break
    return {"coefficient": np.asarray(w, np.float64)[None],
            "_rounds": rounds}


def compare(answer: dict, reference: dict) -> dict:
    return {"coef_gap": worst_gap(answer.get("coefficient"),
                                  reference["coefficient"])}
