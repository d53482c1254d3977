"""An SGD round's two products over a sparse column's batch window, on the
device: the part of a round that differs from the dense round
(``optimizer._sgd_round_math``), which hands these to the shared update.

The window is a task's batch rows of a ``DeviceSparseColumn`` as the
column lies, ``(k, rows)`` ids and values (a bitcast slice of the
column-major ``(n, k)`` arrays). Two products, each a multiply and an add
an entry:

- margins: a row's entries' ``coeffs[id] * value``, summed (a gather of the
  coefficients and a sum over the ``k`` entries);
- gradient: each entry's ``value * multiplier[row]`` added into its bucket
  of ``size`` (a scatter-add). Every entry counts once: two entries of a
  row in one bucket both add, as do entries of different rows.

The entries the column's index names ``hot`` (``DeviceSparseColumn.hot``:
positions whose id is one bucket on every row) are summed as columns
instead: the margins take ``coeffs[bucket] * values`` and the gradient adds
one row sum a bucket, so the gather and the scatter see the other entries
only. ``form(hot)`` names the gradient's form for ``sgd.optimize``. The
forms timed alone on the chip: ``scripts/sparse_forms.py``.

Ids are in ``[0, size)`` by construction (``device_sparse_column`` refuses
others), so the gather and the scatter take them as promised in bounds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Layout:
    """What a sparse round program is built for besides the shapes: the
    column's ``size`` and its ``hot`` index, ``((entry, bucket), ...)``."""
    size: int
    hot: tuple = ()


def form(hot) -> str:
    """The gradient's form, by whether the column has hot entries:
    ``split-scatter`` (those summed as columns, the rest scattered), or
    ``scatter`` over every entry."""
    return "split-scatter" if hot else "scatter"


def _rows(a, index):
    """Rows ``index`` (static) of a window: a slice where they run on."""
    if not index:
        return None
    lo, hi = index[0], index[-1] + 1
    if list(index) == list(range(lo, hi)):
        return a[lo:hi]
    return a[np.asarray(index)]


def gather(coeffs, ids):
    """``coeffs[ids]``, ids promised in bounds."""
    return coeffs.at[ids].get(mode="promise_in_bounds",
                              wrap_negative_indices=False)


def scatter_add(ids, terms, size: int):
    """A ``(size,)`` float32 vector of the ``terms`` added at their ``ids``
    (same shape), every one of them: duplicates add."""
    return jnp.zeros((size,), terms.dtype).at[ids].add(
        terms, mode="promise_in_bounds", wrap_negative_indices=False)


def products(ids, values, size: int, hot=()):
    """``(margins, gradient)`` over a ``(k, rows)`` window:
    ``margins(coeffs) -> (rows,)`` and ``gradient(multipliers) -> (size,)``,
    the two callables ``optimizer._sgd_update_math`` takes. ``coeffs`` may
    be padded past ``size`` (the sharded update pads it): only its first
    ``size`` are read."""
    k = ids.shape[0]
    hot_at = [j for j, _ in hot]
    buckets = np.asarray([b for _, b in hot], np.int32)
    cold = [j for j in range(k) if j not in hot_at]
    ids_cold, vals_cold = _rows(ids, cold), _rows(values, cold)
    vals_hot = _rows(values, hot_at)

    def margins(coeffs):
        with jax.named_scope("sgd.sparse_margins"):
            w = coeffs[:size]
            parts = []
            if cold:
                parts.append(jnp.sum(gather(w, ids_cold) * vals_cold,
                                     axis=0))
            if hot:
                parts.append(jnp.sum(w[buckets][:, None] * vals_hot,
                                     axis=0))
            return sum(parts[1:], parts[0])

    def gradient(multipliers):
        with jax.named_scope("sgd.sparse_gradient"):
            if cold:
                grad = scatter_add(ids_cold,
                                   vals_cold * multipliers[None, :], size)
            else:
                grad = jnp.zeros((size,), values.dtype)
            if hot:
                grad = grad.at[buckets].add(
                    jnp.sum(vals_hot * multipliers[None, :], axis=1))
            return grad

    return margins, gradient
