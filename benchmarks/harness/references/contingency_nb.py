"""Multinomial naive Bayes over categorical values as the configuration
states it (upstream ``NaiveBayes.java``, ``GenerateModelFunction``). For
rows ``i < n``, features ``j < d``, labels ``l`` and values ``v``:

    count[j, l, v] = sum_i [y_i = l] [x_ij = v]
    doc[l]         = sum_i [y_i = l]
    theta[l, j, v] = log(count[j, l, v] + s) - log(doc[l] + s * V_j)
    pi[l]          = log(doc[l] * d + s) - log(n * d + L * s)

with ``V_j`` the number of distinct values present in feature ``j``, ``L``
the number of labels present and ``s`` the ``smoothing``. A feature's
values are listed in ascending order; one with fewer than the widest is
padded with NaN, where ``theta`` holds the floor ``log(s) - log(doc[l] +
s * V_j)``, which ``floors`` holds for every feature.

The counts are taken in integer arithmetic, block by block on the device
that holds the block: every entry's pair ``(label, value)`` as one code,
compared with every code and summed over the block's rows in int32; the
blocks' counts are added in int64 on the host. No product, no one-hot, no
scatter. The logarithms are float64 NumPy. This reference covers tables of
whole numbers that are not negative, which is what the configuration's
generator makes; any other table is refused, not approximated.

``precision="bfloat16"`` is the control: the same sums accumulated in
bfloat16, on the device and over the blocks, which has eight bits: a count
stops growing at 256.

``compare`` gives three numbers. ``theta_gap`` (which covers ``floors``)
and ``pi_gap`` are the largest absolute difference: these are logarithms,
so an absolute gap is a relative error of the probability. ``support_gap``
is 0 where the answer's ``labels`` and ``values`` are the reference's
exactly, infinite otherwise."""

from __future__ import annotations

import functools

import numpy as np

#: faults this reference can plant (``tools/limits_faults.py`` reads them)
FAULTS = ("state_unchanged", "half_blocks", "one_row_short")
#: rows a block: 100 MB at d = 100
BLOCK_ROWS = 250_000


def _state_dtype(precision: str):
    import ml_dtypes

    return np.dtype({"float32": np.int64,
                     "bfloat16": ml_dtypes.bfloat16}[precision])


@functools.lru_cache(maxsize=None)
def _range_program(rows: int):
    import jax
    import jax.numpy as jnp

    def block_range(x, y, start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        whole = jnp.all(xb == jnp.floor(xb)) & jnp.all(yb == jnp.floor(yb))
        return (jnp.minimum(jnp.min(xb), jnp.min(yb)), jnp.max(xb),
                jnp.max(yb), whole)

    return jax.jit(block_range)


@functools.lru_cache(maxsize=None)
def _count_program(rows: int, labels: int, values: int, precision: str):
    import jax
    import jax.numpy as jnp

    acc = {"float32": jnp.int32, "bfloat16": jnp.bfloat16}[precision]

    def block_counts(x, y, start, limit):
        """``(d, labels * values)``: the block's rows ``[0, limit)``."""
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows).astype(jnp.int32)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows).astype(jnp.int32)
        code = jnp.where(jnp.arange(rows)[:, None] < limit,
                         yb[:, None] * values + xb, -1)
        return jnp.sum(code[:, :, None] == jnp.arange(labels * values),
                       axis=0, dtype=acc)

    return jax.jit(block_counts)


def _blocks(x, y):
    """``[(x shard, y shard, start, rows, first global row)]`` over every
    row, shard by shard on the device that holds the shard."""
    def by_start(array):
        return sorted(array.addressable_shards,
                      key=lambda s: s.index[0].start or 0)

    out = []
    for xs, ys in zip(by_start(x), by_start(y)):
        first = xs.index[0].start or 0
        local = xs.data.shape[0]
        for start in range(0, local, BLOCK_ROWS):
            out.append((xs.data, ys.data, start,
                        min(BLOCK_ROWS, local - start), first + start))
    return out


def model_of(counts, n: int, smoothing: float, support=None) -> dict:
    """The model data from ``counts[j, l, v]`` over every candidate label
    and value ``0 .. max``: the equations above in float64. The labels and
    values present are read from ``support`` (counts of the same shape)
    where it is given."""
    counts = np.asarray(counts, np.float64)
    support = counts if support is None else np.asarray(support)
    d = counts.shape[0]
    labels = np.nonzero(support[0].sum(axis=1) > 0)[0]
    doc = counts[0].sum(axis=1)[labels]
    theta, values, floors = [], [], []
    with np.errstate(divide="ignore"):
        for j in range(d):
            present = np.nonzero(support[j].sum(axis=0) > 0)[0]
            denom = np.log(doc + smoothing * len(present))
            values.append(present.astype(np.float64))
            theta.append(np.log(counts[j][labels][:, present] + smoothing)
                         - denom[:, None])
            floors.append(np.log(smoothing) - denom)
        pi = (np.log(doc * d + smoothing)
              - np.log(n * d + len(labels) * smoothing))
    width = max(1, max(len(v) for v in values))
    floors = np.stack(floors, axis=1)                       # (L, d)
    theta_pad = np.repeat(floors[:, :, None], width, axis=2)
    values_pad = np.full((d, width), np.nan)
    for j in range(d):
        theta_pad[:, j, :len(values[j])] = theta[j]
        values_pad[j, :len(values[j])] = values[j]
    return {"theta": theta_pad, "values": values_pad, "piArray": pi,
            "labels": labels.astype(np.float64), "floors": floors}


def run(columns: dict, params: dict, tasks: int,
        precision: str = "float32", fault: str = None) -> dict:
    if params.get("modelType", "multinomial") != "multinomial":
        raise NotImplementedError("this reference covers multinomial only")
    if params.get("weightCol"):
        raise NotImplementedError("this reference covers unit weights only")
    x = columns[params.get("featuresCol", "features")]
    y = columns[params.get("labelCol", "label")]
    smoothing = float(params.get("smoothing", 1.0))
    n, d = x.shape
    blocks = _blocks(x, y)
    ranges = [_range_program(rows)(xs, ys, start)
              for xs, ys, start, rows, _ in blocks]
    lo = min(float(r[0]) for r in ranges)
    if lo < 0 or not all(bool(r[3]) for r in ranges):
        raise NotImplementedError(
            "this reference covers whole numbers from 0 up")
    values = int(max(float(r[1]) for r in ranges)) + 1
    labels = int(max(float(r[2]) for r in ranges)) + 1
    n_counted = n - 1 if fault == "one_row_short" else n
    pending = [
        _count_program(rows, labels, values, precision)(
            xs, ys, start, max(0, min(rows, n_counted - first)))
        for at, (xs, ys, start, rows, first) in enumerate(blocks)
        if not (fault == "half_blocks" and at % 2)
        and fault != "state_unchanged"]
    state = _state_dtype(precision)
    total = np.zeros((d, labels * values), state)
    for block in pending:
        total = (total + np.asarray(block).astype(state)).astype(state)
    counts = np.asarray(total, np.float64).reshape(d, labels, values)
    if fault == "state_unchanged":
        # no row was counted: the smoothed prior alone, over the labels
        # and values the table does hold
        full = run(columns, params, tasks)["_counts"]
        return dict(model_of(np.zeros_like(full), n, smoothing, full),
                    _counts=full, _n=n)
    return dict(model_of(counts, n, smoothing), _counts=counts, _n=n)


def _gap(answer, reference) -> float:
    """max |answer - reference| with entries that are equal (infinite ones
    too) at 0; infinite where the sizes differ or a NaN is on one side."""
    a = np.asarray(answer, np.float64)
    r = np.asarray(reference, np.float64)
    if a.size != r.size:
        return float("inf")
    a = a.reshape(r.shape)
    with np.errstate(invalid="ignore"):
        gap = np.where(a == r, 0.0, np.abs(a - r))
    return float("inf") if np.isnan(gap).any() else float(np.max(gap,
                                                                 initial=0.0))


def _same(answer, reference) -> bool:
    a = np.asarray(answer, np.float64)
    r = np.asarray(reference, np.float64)
    return a.size == r.size and np.array_equal(a.reshape(r.shape), r,
                                               equal_nan=True)


def compare(answer: dict, reference: dict) -> dict:
    missing = [k for k in ("theta", "values", "piArray", "labels", "floors")
               if k not in answer]
    if missing:
        inf = float("inf")
        return {"theta_gap": inf, "pi_gap": inf, "support_gap": inf}
    same = (_same(answer["labels"], reference["labels"])
            and _same(answer["values"], reference["values"]))
    return {
        "theta_gap": max(_gap(answer["theta"], reference["theta"]),
                         _gap(answer["floors"], reference["floors"])),
        "pi_gap": _gap(answer["piArray"], reference["piArray"]),
        "support_gap": 0.0 if same else float("inf")}
