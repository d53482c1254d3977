"""Contingency counts over categorical columns, on the mesh.

``count[v, l, j] = sum_i [y_i = l] [x_ij = v]`` for whole-number labels
``l < L`` and values ``v < V``: the one-pass grouped count that upstream
runs as ``keyBy`` + reduce (NaiveBayes.java's ``GenerateModelFunction``
input). A TPU serialises the natural form, a scatter-add of ``n * d``
updates (9.2 s at 12M x 100 on a v5e: PERF.md section 6, PR 33); here a
tile of rows becomes two one-hot matrices, ``A = onehot(y)`` and ``B =
onehot(x)``, whose entries are whole in bfloat16, and ``A^T B`` on the MXU
with float32 accumulation is the tile's exact count (a tile's sums stay
under 2**24); tiles add up in int32. No integer key, no flatten, no
scatter.

An entry that is not a whole number in range matches no row of a one-hot
and is not counted, so the counts of ``n`` rows add up to ``n * d``
exactly when every value was a whole number in ``[0, V)`` and every label
one in ``[0, L)``: the check of the table rides in the counting pass.

Two programs a mesh, both cached:

- :func:`look_program` — what a fit learns of a table it has not seen
  before it counts: smallest entry, largest value, largest label, and
  whether every entry is a whole number, over the first ``rows`` rows of
  every shard (a guess at ``L`` and ``V``) or over all of them (one fused
  reduction, four numbers to the host);
- :func:`counts_program` — the counts of each shard in one pass over the
  table where it lies (the Pallas kernel ``pallas_kernels.category_counts``
  where the backend and the shape gate admit it, the XLA form
  :func:`category_counts_xla` elsewhere), added over the shards once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.observability.tracing import cold_build
from flink_ml_tpu.parallel import mapreduce as mr
from flink_ml_tpu.parallel.mesh import data_axes, data_pspec

#: one-hot entries a block of the XLA form may hold (bfloat16: 16 MiB)
XLA_BLOCK_ENTRIES = 1 << 23


def category_counts_xla(x, y, n_valid, labels: int, values: int,
                        one_hot_dtype=jnp.bfloat16):
    """The XLA form of ``pallas_kernels.category_counts``: the same one-hot
    product block by block in a loop, → ``(values, labels, d)`` int32. The
    last block is the table's last ``rows`` rows, masked down to the rows
    no earlier block has counted. ``one_hot_dtype`` is float32 where the
    backend multiplies no bfloat16 (XLA's CPU): as exact, not as fast."""
    n, d = x.shape
    if n == 0:
        return jnp.zeros((values, labels, d), jnp.int32)
    rows = max(1, min(n, XLA_BLOCK_ENTRIES // (d * values)))
    value = jnp.arange(values, dtype=x.dtype)
    label = jnp.arange(labels, dtype=y.dtype)

    def block(i, acc):
        start = jnp.minimum(i * rows, n - rows)
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        yb = jax.lax.dynamic_slice_in_dim(y, start, rows)
        at = start + jnp.arange(rows)
        mine = (at >= i * rows) & (at < n_valid)
        a = ((yb[:, None] == label) & mine[:, None]).astype(one_hot_dtype)
        b = (xb[:, None, :] == value[None, :, None]).astype(one_hot_dtype)
        tile = jax.lax.dot_general(                    # (L, V, d), exact
            a, b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc + tile.astype(jnp.int32)

    acc = jax.lax.fori_loop(0, -(-n // rows), block,
                            jnp.zeros((labels, values, d), jnp.int32))
    return jnp.swapaxes(acc, 0, 1)


@functools.lru_cache(maxsize=32)
@cold_build("nb_look")
def look_program(mesh, rows: int = None):
    """``look(xs, ys) -> (4,)`` replicated float32: ``[smallest entry of
    either, largest value, largest label, 1 where every entry is a whole
    number else 0]`` over the first ``rows`` rows of every shard, or over
    every row. A shard's zero padding is whole and not negative, so it
    moves none of the four on a table that passes."""
    axes = data_axes(mesh)

    def nb_look(xl, yl):
        if rows is not None:
            xl, yl = xl[:rows], yl[:rows]
        whole = jnp.logical_and(jnp.all(xl == jnp.floor(xl)),
                                jnp.all(yl == jnp.floor(yl)))
        # one reduce_max over the shards: a smallest and an all are the
        # largest of the negated
        return mr.reduce_max(jnp.stack([
            -jnp.minimum(jnp.min(xl), jnp.min(yl)), jnp.max(xl),
            jnp.max(yl), -whole.astype(xl.dtype)]), axes) * jnp.asarray(
                [-1.0, 1.0, 1.0, -1.0], xl.dtype)

    spec0 = data_pspec(mesh)
    return mr.map_shards(nb_look, mesh,
                         in_specs=(P(spec0, None), P(spec0)), out_specs=P())


@functools.lru_cache(maxsize=32)
@cold_build("nb_counts")
def counts_program(mesh, labels: int, values: int, use_kernel: bool):
    """``counts(xs, ys, n_valid) -> (values, labels, d)`` replicated int32
    over the rows ``[0, n_valid)`` of the row-sharded table: each shard
    counts its own rows and the shards' counts are added once."""
    axes = data_axes(mesh)
    on_cpu = mesh.devices.flat[0].platform == "cpu"
    one_hot_dtype = jnp.float32 if on_cpu else jnp.bfloat16

    def nb_counts(xl, yl, n_valid):
        local_n = xl.shape[0]
        nl = jnp.clip(n_valid - mr.shard_index(axes) * local_n, 0, local_n)
        if use_kernel:
            from flink_ml_tpu.ops.pallas_kernels import category_counts
            local = category_counts(xl, yl, nl, labels, values)
        else:
            local = category_counts_xla(xl, yl, nl, labels, values,
                                        one_hot_dtype)
        return mr.reduce_sum(local, axes)

    spec0 = data_pspec(mesh)
    return mr.map_shards(
        nb_counts, mesh, in_specs=(P(spec0, None), P(spec0), P()),
        out_specs=P())
