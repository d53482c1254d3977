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

XLA's gather and scatter take one entry at a time on a TPU (some 6.7 ns an
entry on a v5e), so the column's index takes two kinds of entry position
off them, by what every row of the table holds there:

- ``hot`` (``DeviceSparseColumn.hot``: one bucket on every row, a numeric
  field) are summed as columns: the margins take ``coeffs[bucket] *
  values`` and the gradient adds one row sum a bucket;
- ``narrow`` (``DeviceSparseColumn.narrow``: at most :data:`NARROW_MAX`
  buckets over the whole table) take the dictionary form: with ``D`` the
  position's buckets (a row of the column's ``dicts``, padded with -1, a
  bucket no id holds), the margins take ``sum_s where(ids == D[s],
  coeffs[D[s]], 0) * values`` (one slot matches, so it is the gather's
  value exactly) and the gradient ``sum_rows where(ids == D[s], value *
  multiplier, 0)`` for each slot, a float32 reduction over the rows, added
  into the buckets in one small add with the hot buckets' sums.

The gather and the scatter see the other, wide, entries only. ``form``
names the gradient's form for ``sgd.optimize``, and ``gradient_ops`` the
operations a compiled program runs for the gradient, by the names a device
trace gives them. The forms timed alone on the chip:
``scripts/sparse_forms.py``.

Ids are in ``[0, size)`` by construction (``device_sparse_column`` refuses
others), so the gather and the scatter take them as promised in bounds.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

import jax
import jax.numpy as jnp

#: the most buckets a position may hold over the whole table and still take
#: the dictionary form. On one TPU v5e (``scripts/sparse_forms.py``, the
#: click-through cell's window of 100,000 rows) the serial gather and
#: scatter take some 6.7 ns an entry each way, 0.67 ms a position; one
#: position's dictionary costs some 0.2 ms each way up to 2,048 slots and
#: 0.9 ps a slot a row past that, so a round alone gains up to some 7,000
#: slots. The cap sits lower for the index: it compares every row of the
#: table with each proposed dictionary, once a column, in set-up, so each
#: 128 slots more is one more pass of compares over the table's rows (at
#: 1024 the click-through cell's 11 narrow fields hold 1,776 slots and its
#: index takes 0.39 s at 23M rows).
NARROW_MAX = 1024

#: the scope every operation of a round's gradient is made under
GRADIENT_SCOPE = "sgd.sparse_gradient"


@dataclasses.dataclass(frozen=True)
class Layout:
    """What a sparse round program is built for besides the shapes: the
    column's ``size``, its ``hot`` index, ``((entry, bucket), ...)``, and
    its ``narrow`` index, ``((entry, slots), ...)``: the dictionary of
    ``entry`` is the first ``slots`` of its row of the column's ``dicts``
    (an operand of the program: no bucket of a dictionary is in its
    text)."""
    size: int
    hot: tuple = ()
    narrow: tuple = ()


def form(hot, narrow=()) -> str:
    """The gradient's form, by the column's index: ``split-`` where hot
    entries are summed as columns, ``dict-`` where narrow ones take the
    dictionary form, and the rest ``scatter``."""
    return ("split-" if hot else "") + ("dict-" if narrow else "") + \
        "scatter"


def gradient_ops(hlo_text: str) -> tuple:
    """The names of a compiled program's operations made under
    :data:`GRADIENT_SCOPE` (the scatter-add of the wide entries, the terms
    it adds, the hot and dictionary sums and their add), as a device trace
    names them: the instructions of its optimized HLO (``Compiled.as_text()``)
    that run as operations of their own, not those inside a fusion's or a
    reduction's computation. Keyed on the scope, so they follow the
    compiler's numbering wherever it goes."""
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", hlo_text))
    names, computation = [], None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "}") and line.endswith("{"):
            computation = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line)[1]
            continue
        if computation in inner:
            continue
        found = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        scope = re.search(r'op_name="([^"]*)"', line)
        if (found and scope and f"/{GRADIENT_SCOPE}/" in scope[1]
                and found[1] not in names):
            names.append(found[1])
    return tuple(names)


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


def products(ids, values, size: int, hot=(), narrow=(), dicts=None):
    """``(margins, gradient)`` over a ``(k, rows)`` window:
    ``margins(coeffs) -> (rows,)`` and ``gradient(multipliers) -> (size,)``,
    the two callables ``optimizer._sgd_update_math`` takes. ``coeffs`` may
    be padded past ``size`` (the sharded update pads it): only its first
    ``size`` are read. With ``narrow`` (``Layout.narrow``), ``dicts`` is the
    column's ``(k, slots)`` int32 dictionaries."""
    k = ids.shape[0]
    hot_at = [j for j, _ in hot]
    buckets = np.asarray([b for _, b in hot], np.int32)
    narrow_at = [j for j, _ in narrow]
    cold = [j for j in range(k) if j not in hot_at and j not in narrow_at]
    ids_cold, vals_cold = _rows(ids, cold), _rows(values, cold)
    vals_hot = _rows(values, hot_at)
    # each narrow position's dictionary, its window ids and values, and
    # the compare of the two, ``(slots, rows)``: fused into each reduction
    dict_of = [(dicts[j, :slots], ids[j], values[j]) for j, slots in narrow]

    def matches(d, row_ids):
        return row_ids[None, :] == d[:, None]

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
            for d, row_ids, row_vals in dict_of:
                wd = w.at[d].get(mode="fill", fill_value=0.0,
                                 wrap_negative_indices=False)
                parts.append(jnp.sum(jnp.where(
                    matches(d, row_ids), wd[:, None], 0.0), axis=0)
                    * row_vals)
            return sum(parts[1:], parts[0])

    def gradient(multipliers):
        with jax.named_scope("sgd.sparse_gradient"):
            if cold:
                grad = scatter_add(ids_cold,
                                   vals_cold * multipliers[None, :], size)
            else:
                grad = jnp.zeros((size,), values.dtype)
            if not narrow:
                # the hot buckets' add alone, as it was before the
                # dictionary form: a column with no narrow position keeps
                # that program's text (the combined add below would do too)
                if hot:
                    grad = grad.at[buckets].add(
                        jnp.sum(vals_hot * multipliers[None, :], axis=1))
                return grad
            at, sums = [], []
            if hot:
                at.append(jnp.asarray(buckets))
                sums.append(jnp.sum(vals_hot * multipliers[None, :],
                                    axis=1))
            for d, row_ids, row_vals in dict_of:
                at.append(d)
                sums.append(jnp.sum(jnp.where(
                    matches(d, row_ids), (row_vals * multipliers)[None, :],
                    0.0), axis=1))
            # the hot buckets' and the dictionaries' sums in one add; a
            # dictionary's padding (-1) is dropped
            return grad.at[jnp.concatenate(at)].add(
                jnp.concatenate(sums), mode="drop",
                wrap_negative_indices=False)

    return margins, gradient
