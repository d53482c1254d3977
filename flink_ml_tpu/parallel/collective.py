"""Collectives + sharding helpers.

Ref parity (flink-ml-core):
- ``all_reduce_sum`` ≙ AllReduceImpl.allReduceSum (AllReduceImpl.java:71-102):
  the reference hand-rolls reduce-scatter + all-gather out of 4 KB chunks and
  TCP shuffles; here it is a single XLA ``psum`` lowered to an ICI all-reduce.
- ``broadcast_from`` / ``replicate`` ≙ BroadcastUtils.withBroadcastStream
  (BroadcastUtils.java:65): broadcast variables become replicated shardings —
  XLA inserts the all-gather; no caching/blocking operator is needed.
- ``termination_vote`` ≙ SharedProgressAligner.EpochStatus.isTerminated
  (SharedProgressAligner.java:277-292): the coordinator's "all subtasks
  reported, zero records this round" vote becomes a psum of per-shard counts.

The in-axis functions are for use inside ``shard_map``/``pjit`` bodies; the
host-level helpers (``shard_batch``) place host arrays onto the mesh.

Telemetry (docs/observability.md "Distributed telemetry"): the in-axis
collectives are the named seams of every SPMD program, so each records
its payload into ``ml.collective`` at TRACE time — op count and payload
bytes labeled ``{op=,axis=,devices=}``. That is per *compiled program
structure*, not per executed step (the compiled body contains no Python;
JL107's whole point), which is exactly the right meaning here: it
answers "what collectives does this program issue, over which axes, at
what sizes". Runtime timing comes from the host-level helpers below,
which ARE host boundaries: each records an ``ml.collective
opMs{op=,devices=}`` histogram and, whenever the tracer is active, a
``collective.host`` span.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_ml_tpu.parallel.mesh import DATA_AXIS
from flink_ml_tpu.parallel.shardmap import axis_size  # noqa: F401 — re-export

#: byte-shaped histogram bounds for collective payloads (the default
#: buckets are latency-shaped)
PAYLOAD_BUCKETS = (256.0, 4096.0, 65536.0, 1048576.0, 16777216.0,
                   268435456.0, 4294967296.0)

#: env var: force the hierarchical two-level reduce on ("1") or off
#: ("0"); unset/other = auto (on when the runtime spans processes).
#: Read at program TRACE time: already-compiled (lru-cached) fit
#: programs keep the structure they were traced with, so set it before
#: the first fit — the multihost bench runs each mode in its own
#: process for exactly this reason.
HIER_ENV = "FLINK_ML_TPU_HIER_REDUCE"


def _collective_group():
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics

    return metrics.group(ML_GROUP, "collective")


def _payload_bytes(x) -> int:
    """Static per-shard payload of a traced operand (shape/dtype are
    trace-time constants even when the values are tracers)."""
    shape = jnp.shape(x)
    return int(np.prod(shape, dtype=np.int64)) * jnp.result_type(x).itemsize


def _note_traced(op: str, x, axis_name) -> None:
    """Trace-time accounting of one in-axis collective site: op count +
    payload bytes into ``ml.collective``, and an instant event on the
    open span (the fit/transform span is open while its program traces).
    Never raises — telemetry must not sink a trace."""
    try:
        axes = ((axis_name,) if isinstance(axis_name, str)
                else tuple(axis_name))
        devices = axis_size(axes[0]) if len(axes) == 1 else int(
            np.prod([axis_size(a) for a in axes]))
        nbytes = _payload_bytes(x)
        labels = {"op": op, "axis": ",".join(str(a) for a in axes),
                  "devices": str(devices)}
        group = _collective_group()
        group.counter("tracedOps", labels=labels)
        group.histogram("payloadBytes", buckets=PAYLOAD_BUCKETS,
                        labels=labels).observe(nbytes)
        from flink_ml_tpu.observability import tracing

        if tracing.tracer.current() is not None:
            tracing.tracer.event("ml.collective.traced", op=op,
                                 axis=labels["axis"], devices=devices,
                                 payload_bytes=nbytes)
    except Exception:
        pass


def _note_level(op: str, level: str, x, axes) -> None:
    """Trace-time per-LEVEL payload accounting of the two-level reduce
    topology (``ml.collective levelPayloadBytes{op=,level=,axis=}``):
    ``level="inter"`` bytes cross the slow outer fabric (DCN / the
    inter-process network), ``level="intra"`` bytes stay on the fast
    local axis. The multihost bench gates on the inter sum — the
    hierarchical decomposition must record strictly fewer inter bytes
    than the flat psum it replaces. Never raises."""
    try:
        labels = {"op": op, "level": level,
                  "axis": ",".join(str(a) for a in axes)}
        group = _collective_group()
        group.counter("levelOps", labels=labels)
        group.histogram("levelPayloadBytes", buckets=PAYLOAD_BUCKETS,
                        labels=labels).observe(_payload_bytes(x))
    except Exception:
        pass


def hier_reduce_forced() -> Optional[bool]:
    """The ``FLINK_ML_TPU_HIER_REDUCE`` override: True/False when the
    env forces the hierarchical or flat path, None for auto."""
    raw = os.environ.get(HIER_ENV, "").strip().lower()
    if raw in ("1", "true", "on", "yes"):
        return True
    if raw in ("0", "false", "off", "no"):
        return False
    return None


def _hier_active(axes) -> bool:
    """Whether :func:`all_reduce_sum` over these axes decomposes into
    the two-level reduce: needs a (slow, fast) axis split to exploit,
    then the env override decides, else auto — hierarchical exactly when
    the runtime spans processes (a single-process hybrid mesh's "dcn"
    axis rides the same ICI as its data axis, so the flat psum is
    already optimal there; tests force the path via the env)."""
    if len(axes) < 2:
        return False
    forced = hier_reduce_forced()
    if forced is not None:
        return forced
    try:
        return jax.process_count() > 1
    except Exception:
        return False


def _hier_psum(x, axes):
    """The two-level tree reduce (arXiv:1903.06701 — reduce near the
    data, cross the slow fabric at 1/N width): reduce_scatter over the
    fast inner axes (each local shard owns a ``1/local_N`` slice of the
    local sum), all-reduce the slices over the slow outer axis — the
    ONLY inter-level traffic, ``1/local_N`` of the flat psum's payload —
    then all_gather the fresh slices back over the fast axes. Equals the
    flat psum up to float reassociation (pinned in
    tests/test_multiprocess.py)."""
    outer, inner = axes[0], axes[1:]
    inner_ax = inner[0] if len(inner) == 1 else inner
    local_n = int(np.prod([axis_size(a) for a in inner]))
    if local_n <= 1 or jnp.ndim(x) == 0:
        # no fast axis to scatter over / a scalar: the split degenerates
        _note_traced("psum", x, axes)
        _note_level("psum", "inter", x, axes)
        return jax.lax.psum(x, axes)
    n0 = x.shape[0]
    pad = (-n0) % local_n
    xp = (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          if pad else x)
    _note_traced("psum_scatter", xp, inner_ax)
    _note_level("reduce_scatter", "intra", xp, axes)
    part = jax.lax.psum_scatter(xp, inner_ax, scatter_dimension=0,
                                tiled=True)
    _note_traced("psum", part, outer)
    _note_level("psum", "inter", part, axes)
    part = jax.lax.psum(part, outer)
    _note_traced("all_gather", part, inner_ax)
    _note_level("all_gather", "intra", part, axes)
    full = jax.lax.all_gather(part, inner_ax, axis=0, tiled=True)
    return full[:n0] if pad else full


# -- in-axis collectives (inside shard_map / with named axes) ---------------

def all_reduce_sum(x, axis_name=DATA_AXIS):
    """Sum across the mesh axis (ref: AllReduceImpl.java:54 allReduceSum).

    ``axis_name`` may be a tuple of axes — e.g. ``("dcn", "data")`` on a
    hybrid multi-slice or multi-process mesh. When the runtime spans
    processes (or ``FLINK_ML_TPU_HIER_REDUCE=1`` forces it), the tuple
    form lowers through the explicit two-level tree reduce
    (:func:`_hier_psum`) so the inter-process fabric carries
    ``1/local_N`` of the payload; otherwise one fused ``psum`` (XLA
    decomposes it over ICI/DCN on real hardware).
    """
    axes = ((axis_name,) if isinstance(axis_name, str)
            else tuple(axis_name))
    if _hier_active(axes):
        return _hier_psum(x, axes)
    _note_traced("psum", x, axis_name)
    if len(axes) > 1:
        # flat reduce over a mesh with a slow outer axis: the FULL
        # payload crosses the inter level — the comparison baseline the
        # hierarchical path's accounting is gated against
        _note_level("psum", "inter", x, axes)
    return jax.lax.psum(x, axis_name)


def renormalized_sum(x, include, axis_name=DATA_AXIS):
    """Partial-participation all-reduce (JiT aggregation,
    arXiv:2208.09740): every shard still executes the collective (SPMD
    lockstep — a shard cannot skip a psum), but a shard whose ``include``
    is 0 contributes zero, and the sum is rescaled by
    ``n_shards / participants`` so the expected update stays unbiased —
    dropping shard k for one round scales the survivors up instead of
    silently shrinking the step. ``include`` is this shard's 0/1 scalar,
    decided on HOST from the *previous* round's readiness timings
    (parallel/elastic.py:round_participation — the actuator guarantees
    at least one participant; the ``maximum(…, 1)`` below only keeps a
    pathological all-dropped round finite). With every shard included
    the result is bit-identical to :func:`all_reduce_sum` (``include``
    multiplies by exactly 1 and the scale is exactly 1)."""
    axes = ((axis_name,) if isinstance(axis_name, str)
            else tuple(axis_name))
    n_shards = int(np.prod([axis_size(a) for a in axes]))
    dtype = jnp.result_type(x)
    if not jnp.issubdtype(dtype, jnp.inexact):
        dtype = jnp.float32
    inc = jnp.asarray(include).astype(dtype)
    total = all_reduce_sum(x * inc, axis_name)
    participants = all_reduce_sum(inc, axis_name)
    scale = n_shards / jnp.maximum(participants, jnp.asarray(1, dtype))
    return total * scale


def all_reduce_mean(x, axis_name: str = DATA_AXIS):
    _note_traced("pmean", x, axis_name)
    return jax.lax.pmean(x, axis_name)


def all_reduce_max(x, axis_name: str = DATA_AXIS):
    _note_traced("pmax", x, axis_name)
    return jax.lax.pmax(x, axis_name)


def all_gather(x, axis_name: str = DATA_AXIS, axis: int = 0, tiled: bool = True):
    _note_traced("all_gather", x, axis_name)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name=DATA_AXIS):
    """Sum across the mesh axis, each shard keeping only its own
    ``1/N`` slice of dim 0 — the first half of the cross-replica sharded
    weight update (arXiv:2004.13336): per-replica update FLOPs and
    optimizer-state traffic scale down with the mesh instead of every
    replica reducing (and then updating) the full vector. Dim 0 must be
    a multiple of the total shard count (pad with zeros — a zero
    gradient is inert through every update rule in this framework); the
    slice order matches :func:`shard_index`, so ``all_gather`` of the
    per-shard slices reconstructs the full reduction.

    ``axis_name`` may be a tuple of axes (hybrid dcn×data meshes); XLA
    then scatters over the flattened axis order, keeping the heavy leg
    on ICI like the hierarchical all-reduce.
    """
    _note_traced("psum_scatter", x, axis_name)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                tiled=True)


def shard_index(axis_name=DATA_AXIS):
    """This shard's position along the (possibly tuple of) data axes —
    the named seam over ``jax.lax.axis_index`` (jaxlint JL108 keeps raw
    index queries out of fit programs). Matches the slice order of
    :func:`reduce_scatter`/:func:`all_gather`."""
    return jax.lax.axis_index(axis_name)


def broadcast_from(x, src: int = 0, axis_name: str = DATA_AXIS):
    """Broadcast shard ``src``'s value to all shards (ref: .broadcast() edges).

    Implemented as a masked psum so it stays a single ICI collective.
    """
    _note_traced("broadcast", x, axis_name)
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def termination_vote(local_count, axis_name: str = DATA_AXIS):
    """True iff the global count is zero — the reference coordinator's
    termination rule (SharedProgressAligner.java:277-292) as one psum."""
    _note_traced("termination_vote", local_count, axis_name)
    total = jax.lax.psum(local_count, axis_name)
    return total == 0


def local_valid_mask(axes, local_n: int, n_valid, dtype=jnp.float32):
    """Inside shard_map: 1 for rows whose GLOBAL index is < ``n_valid`` —
    the padding mask for ``shard_batch``'s zero-padded batches, derived
    on-device from one scalar instead of shipping an (n,) mask array."""
    shard = jax.lax.axis_index(axes)
    global_idx = shard * local_n + jnp.arange(local_n)
    return (global_idx < n_valid).astype(dtype)


# -- host-level placement ----------------------------------------------------

class _HostOp:
    """Time one host-boundary collective/placement op into
    ``ml.collective opMs{op=,devices=}`` (+ payload bytes), with a
    ``collective.host`` span whenever the tracer is active. Also the
    seam that records the mesh topology: a host placement op is proof
    the mesh is in use."""

    __slots__ = ("op", "mesh", "nbytes", "_t0", "_span_cm")

    def __init__(self, op: str, mesh: Mesh, nbytes: int = 0):
        self.op = op
        self.mesh = mesh
        self.nbytes = int(nbytes)
        self._span_cm = None

    def __enter__(self):
        from flink_ml_tpu.observability import meshstats, tracing

        try:  # an unwritable trace dir must not sink the data path
            meshstats.ensure_mesh_recorded(self.mesh)
        except Exception:
            pass
        # the shared no-op unless the tracer is active
        self._span_cm = tracing.tracer.span(
            "collective.host", op=self.op, devices=self.mesh.devices.size,
            payload_bytes=self.nbytes)
        self._span_cm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter() - self._t0) * 1000.0
        labels = {"op": self.op, "devices": str(self.mesh.devices.size)}
        group = _collective_group()
        group.histogram("opMs", labels=labels).observe(ms)
        if self.nbytes:
            group.histogram("payloadBytes", buckets=PAYLOAD_BUCKETS,
                            labels=labels).observe(self.nbytes)
        self._span_cm.__exit__(*exc)
        return False


def _dim0_layout(mesh: Mesh, axis_name, ndim: int):
    """The shared dim-0-sharded placement recipe: (shard count, sharding)
    for an ndim-rank array row-sharded over the given data axes."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    dim0 = axes[0] if len(axes) == 1 else axes
    sharding = NamedSharding(mesh, P(dim0, *([None] * (ndim - 1))))
    return n_shards, sharding


def shard_batch(mesh: Mesh, array, axis_name: str = DATA_AXIS):
    """Place a host array on the mesh, sharded on dim 0 (the batch dim).

    Equivalent of the reference scattering a global batch over subtasks
    (DataStreamUtils.generateBatchData / partitionCustom). Pads dim 0 up to a
    multiple of the axis size with zeros; callers track true counts (padding
    contributes zero weight to every reduction in this framework).
    Returns (device_array, original_length).
    """
    array = np.asarray(array)
    n_shards, sharding = _dim0_layout(mesh, axis_name, array.ndim)
    n = array.shape[0]
    rem = (-n) % n_shards
    if rem:
        pad = np.zeros((rem,) + array.shape[1:], dtype=array.dtype)
        array = np.concatenate([array, pad], axis=0)
    with _HostOp("shard_batch", mesh, array.nbytes):
        return jax.device_put(array, sharding), n


def replicate(mesh: Mesh, tree):
    """Replicate a pytree across the whole mesh (broadcast-variable parity)."""
    sharding = NamedSharding(mesh, P())
    nbytes = sum(getattr(leaf, "nbytes", 0)
                 for leaf in jax.tree_util.tree_leaves(tree))
    with _HostOp("replicate", mesh, nbytes):
        return jax.device_put(tree, sharding)


@functools.lru_cache(maxsize=128)
def _prepare_program(rem: int, dtype_name: str, sharding, ndim: int):
    """Compiled cast+pad+reshard for device-resident inputs — keyed so
    repeated fits at the same shapes reuse one program."""
    dtype = jnp.dtype(dtype_name)

    def prepare_rows(a):
        a = a.astype(dtype)
        if rem:
            a = jnp.pad(a, ((0, rem),) + ((0, 0),) * (a.ndim - 1))
        return a

    return jax.jit(prepare_rows, out_shardings=sharding)


def ensure_on_mesh(mesh: Mesh, array, axis_name=DATA_AXIS, dtype=None):
    """Device-aware :func:`shard_batch`: a host array is cast and placed via
    ``shard_batch``; an already-device ``jax.Array`` is cast/padded/resharded
    ON device (no host round-trip). This is the residency contract that makes
    datagen→fit chains and repeated fits transfer-free — the data-cache role
    of the reference (ListStateWithCache.java:54) where the cached shard
    simply stays in HBM. Returns (device_array, original_row_count)."""
    if not isinstance(array, jax.Array):
        arr = np.asarray(array)
        if dtype is not None and arr.dtype != np.dtype(dtype):
            arr = arr.astype(dtype)
        return shard_batch(mesh, arr, axis_name)
    n = array.shape[0]
    n_shards, sharding = _dim0_layout(mesh, axis_name, array.ndim)
    rem = (-n) % n_shards
    want = jnp.dtype(dtype) if dtype is not None else array.dtype
    with _HostOp("ensure_on_mesh", mesh, array.nbytes):
        if rem == 0 and array.dtype == want:
            # device_put with a matching placement is a no-op; a mismatched
            # one is a device-to-device reshard — still no PCIe leg
            return jax.device_put(array, sharding), n
        return _prepare_program(rem, want.name, sharding,
                                array.ndim)(array), n
