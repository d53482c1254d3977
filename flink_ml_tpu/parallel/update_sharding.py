"""Cross-replica sharding of the weight update + optimizer state.

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336): in plain data parallelism every replica
all-reduces the full gradient and then applies the identical full update
— per-replica update FLOPs and optimizer-state memory do NOT scale down
with the mesh. The sharded formulation splits the update across the
replicas instead::

        per-shard partial gradient  g_i            (full length, padded)
                   │ reduce_scatter                 1/N slice per replica
                   ▼
        g_slice ──▶ apply_fn(g_slice, param_slice, opt_state_slice)
                   │                │ opt-state slices STAY sharded
                   │ all_gather     ▼ (1/N memory per replica)
                   ▼
        fresh replicated params    new opt-state slices

Per-replica optimizer memory (FTRL's z/n accumulators, momentum) and
update FLOPs scale as ``1/N``; the wire cost is the same as the
all-reduce it replaces (reduce-scatter + all-gather IS the all-reduce,
split around the update). Built entirely from the named primitives in
``parallel/mapreduce.py`` so every leg records ``ml.collective``
accounting.

Enabling: the fit families (SGD programs, KMeans lloyd, FTRL) read
:func:`enabled` — set ``FLINK_ML_TPU_UPDATE_SHARDING=1``. Default off:
replicated and sharded fits agree only up to float reassociation (the
reduce-scatter sums in a different order than the fused psum), and the
replicated path is the long-standing numerics oracle. Parity is pinned
by tests/test_mapreduce.py at mesh sizes {1, 2, 8} and benchmarked by
scripts/mapreduce_bench.py (BENCH_mapreduce.json: per-replica
optimizer-state bytes must shrink ~1/N).
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from flink_ml_tpu.parallel import mapreduce as mr

#: env var: arm the cross-replica sharded update in every fit family
ENV = "FLINK_ML_TPU_UPDATE_SHARDING"

__all__ = [
    "ENV", "enabled", "padded_len", "pad_leading", "owned_slice",
    "sharded_apply", "place_opt_state", "record_state_bytes",
    "last_state_bytes", "provenance",
]


def enabled() -> bool:
    """True when ``FLINK_ML_TPU_UPDATE_SHARDING`` arms the sharded
    update (accepted truthy spellings: 1/true/on/yes)."""
    return os.environ.get(ENV, "").strip().lower() in (
        "1", "true", "on", "yes")


def padded_len(n: int, n_shards: int) -> int:
    """``n`` rounded up to a multiple of the shard count — the dim-0
    length reduce-scatter needs. Zero-padding is inert through every
    update rule here (zero gradient → zero update; FTRL's
    soft-threshold keeps a zero coordinate exactly zero)."""
    n_shards = max(int(n_shards), 1)
    return int(n) + (-int(n)) % n_shards


def pad_leading(x, target: int):
    """``x`` zero-padded along dim 0 up to ``target`` (trace-safe: the
    pad width is a static Python int)."""
    import jax.numpy as jnp

    pad = int(target) - x.shape[0]
    if pad <= 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def owned_slice(x, axes=None):
    """Inside a map body: this replica's ``1/N`` slice of a replicated
    array (dim 0 must be a multiple of the shard count). The slice
    order matches :func:`mapreduce.reduce_scatter`, so the slice pairs
    with the scattered gradient it will be updated by."""
    import jax

    axes = axes if axes is not None else mr.DATA_AXIS
    n = mr.shard_count(axes)
    chunk = x.shape[0] // n
    start = mr.shard_index(axes) * chunk
    return jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=0)


def sharded_apply(axes, grads, params, opt_state, apply_fn):
    """ONE cross-replica sharded update step, inside a map body.

    - ``grads``: pytree of per-shard partial gradients, full length with
      dim 0 padded to the shard multiple (:func:`padded_len`).
    - ``params``: pytree of REPLICATED parameter arrays (same padded
      dim 0) — each replica updates only its own slice.
    - ``opt_state``: pytree of already-SHARDED optimizer-state slices
      (each replica's ``1/N`` rows — FTRL z/n, momentum), or ``None``.
      They stay sharded: this is where the ``1/N`` memory comes from.
    - ``apply_fn(grad_slices, param_slices, opt_state) ->
      (new_param_slices, new_opt_state)`` — the update rule, applied to
      slices; must be elementwise/rowwise along dim 0 (every rule in
      this framework is).

    Returns ``(new_params, new_opt_state)`` with the parameters
    all-gathered back to replicated (the forward pass needs them whole)
    and the optimizer state still sharded.
    """
    import jax

    g = jax.tree_util.tree_map(lambda a: mr.reduce_scatter(a, axes), grads)
    p = jax.tree_util.tree_map(lambda a: owned_slice(a, axes), params)
    new_p, new_opt = apply_fn(g, p, opt_state)
    gathered = jax.tree_util.tree_map(
        lambda a: mr.all_gather(a, axes), new_p)
    return gathered, new_opt


def place_opt_state(mesh, tree, axes=None):
    """Host boundary: place full-length (padded) optimizer-state arrays
    onto the mesh sharded on dim 0 — each device holds only its ``1/N``
    slice. The map-body view under ``in_specs=P(data_pspec(mesh))`` is
    exactly the slice :func:`sharded_apply` carries."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.parallel.mesh import data_pspec

    spec0 = data_pspec(mesh)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            a, NamedSharding(mesh, P(spec0, *([None] * (a.ndim - 1))))),
        tree)


# -- accounting ---------------------------------------------------------------
#: last per-algo record: {"algo": {"bytesPerReplica", "sharded", "shards"}}
_last: dict = {}


def _leaf_bytes_per_replica(leaf) -> int:
    """MEASURED bytes one replica holds for ``leaf``: the first
    addressable shard's buffer size for a device array (full size when
    replicated, the 1/N slice when dim-0-sharded — so a regression that
    silently replicates 'sharded' state shows up as real bytes, not as
    wishful arithmetic), the whole array for a host leaf."""
    shards = getattr(leaf, "addressable_shards", None)
    if shards:
        return int(shards[0].data.nbytes)
    return int(np.prod(getattr(leaf, "shape", np.shape(leaf)),
                       dtype=np.int64)
               * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize)


def record_state_bytes(algo: str, leaves, n_shards: int,
                       sharded: bool) -> int:
    """Record the per-replica bytes of a fit's update state (parameters
    + optimizer accumulators), MEASURED from the leaves' actual device
    buffers (:func:`_leaf_bytes_per_replica`) — replicated carries
    report their full size even when the sharded *update* ran (SGD
    coefficients and KMeans centroids all-gather back to replicated
    every step; only genuinely sharded state like FTRL's z/n slices
    shrinks). ``sharded`` labels whether the sharded update was armed.
    Lands as ``ml.update stateBytesPerReplica{algo=,sharded=}`` gauges
    and feeds benchmark provenance (``optStateBytesPerReplica`` on
    runner rows and the bench.py one-liner). Returns the byte count."""
    per_replica = int(sum(_leaf_bytes_per_replica(leaf)
                          for leaf in leaves))
    _last[algo] = {"bytesPerReplica": per_replica, "sharded": bool(sharded),
                   "shards": int(n_shards)}
    _last["__latest__"] = _last[algo]
    try:  # telemetry must never sink a fit
        from flink_ml_tpu.common.metrics import ML_GROUP, metrics

        grp = metrics.group(ML_GROUP, "update")
        labels = {"algo": algo, "sharded": str(int(sharded))}
        grp.gauge("stateBytesPerReplica", per_replica, labels=labels)
    except Exception:
        pass
    return per_replica


def last_state_bytes(algo: Optional[str] = None) -> Optional[int]:
    """The most recently recorded per-replica state bytes (for ``algo``,
    or of whichever fit recorded last) — benchmark provenance."""
    rec = _last.get(algo or "__latest__")
    return None if rec is None else rec["bytesPerReplica"]


def reset_last() -> None:
    """Forget the recorded state bytes. The benchmark runner calls this
    before each benchmark so a row only carries provenance from ITS own
    run — a transform-only row must not inherit the previous fit's
    ``optStateBytesPerReplica``."""
    _last.clear()


def provenance() -> dict:
    """Benchmark-row provenance: whether the sharded update is armed,
    the last recorded per-replica state bytes (absent if nothing has
    recorded yet), and the elastic-run fields (``elasticEvents`` /
    ``participationMin`` — parallel/elastic.py) that sit beside
    ``processCount`` on every row."""
    out = {"updateSharding": enabled()}
    b = last_state_bytes()
    if b is not None:
        out["optStateBytesPerReplica"] = b
    from flink_ml_tpu.parallel import elastic

    out.update(elastic.provenance())
    return out
