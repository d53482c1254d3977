"""Mesh construction.

The Flink-subtask ≙ TPU-core mapping lives here (SURVEY.md §7 layer 3): the
reference's "parallelism" knob becomes the size of the ``data`` mesh axis.
Single-slice meshes ride ICI; multi-slice/multi-host meshes extend over DCN
via jax.distributed — same code path, the mesh just gets bigger.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

DATA_AXIS = "data"    # data parallelism (the reference's only training parallelism)
MODEL_AXIS = "model"  # tensor/model parallelism (TPU-native bonus axis)
DCN_AXIS = "dcn"      # cross-slice axis (slow network between TPU slices)

_default_mesh: Optional[Mesh] = None


def _all_devices():
    """All default-backend devices. A backend that fails to initialize
    raises here: a mesh silently rebuilt on host devices would let a run
    without its accelerator look like success."""
    return jax.devices()


def local_device_count() -> int:
    return len(_all_devices())


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     **kwargs) -> bool:
    """Join the multi-host JAX runtime so ``jax.devices()`` sees every chip
    in the cluster (the coordinator role of the reference's JobManager —
    SharedProgressAligner RPC — maps onto jax's distributed service; SPMD
    lockstep then replaces the per-epoch alignment protocol entirely).

    Safe to call unconditionally: a single-process run (no coordinator
    configured and no cluster env detected) or an already-initialized
    runtime is a no-op. Returns True when a multi-process runtime is live.
    """
    if num_processes == 1 and coordinator_address is None:
        return False
    try:  # no public API for "is the distributed client live?"
        from jax._src import distributed as _distributed
        already = _distributed.global_state.client is not None
    except Exception:
        already = False
    if already:
        return jax.process_count() > 1
    if coordinator_address is None and num_processes is None:
        # rely on cluster auto-detection (TPU metadata, SLURM, ...); if no
        # cluster environment exists this raises, which we treat as
        # "single process" — but log it, since on a real pod a transient
        # join failure here would otherwise silently degrade this process
        # to single-host while its peers form the cluster
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as e:
            import logging

            logging.getLogger(__name__).warning(
                "jax.distributed.initialize auto-detection failed (%s); "
                "continuing single-process. Pass coordinator_address/"
                "num_processes/process_id explicitly to force a cluster "
                "join.", e)
            return False
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id, **kwargs)
    return jax.process_count() > 1


def create_mesh(shape: Sequence[int] = None,
                axis_names: Sequence[str] = (DATA_AXIS,),
                devices=None) -> Mesh:
    """Create a mesh over the given devices (default: all of them).

    ``create_mesh()`` → 1-D data mesh over every device.
    ``create_mesh((4, 2), ("data", "model"))`` → 2-D mesh.
    """
    devices = list(devices if devices is not None else _all_devices())
    if shape is None:
        shape = (len(devices),)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def create_hybrid_mesh(ici_shape: Sequence[int] = None,
                       dcn_shape: Sequence[int] = None,
                       axis_names: Sequence[str] = None,
                       devices=None) -> Mesh:
    """Mesh spanning multiple TPU slices: DCN-connected axes outermost so
    XLA keeps the heavy collectives on ICI and only crosses the slow
    network on the explicitly-DCN axes (the scaling-book layout recipe).

    ``create_hybrid_mesh(ici_shape=(4,), dcn_shape=(2,))`` on 2 slices of 4
    chips → a ("dcn", "data") mesh of shape (2, 4): psum over "data" rides
    ICI inside each slice; psum over ("dcn", "data") is a hierarchical
    all-reduce (in-slice reduce, one cross-slice exchange, in-slice
    broadcast) — XLA decomposes it that way automatically because the DCN
    axis is outermost in device order.

    On a single-slice/CPU runtime (no slice topology) the same axes are
    laid out over the flat device list so multi-slice programs stay
    runnable in tests — sharding semantics identical, only the physical
    transport differs.
    """
    devices = list(devices if devices is not None else _all_devices())
    dcn_shape = tuple(dcn_shape or (1,))
    if ici_shape is None:
        ici_shape = (len(devices) // max(int(np.prod(dcn_shape)), 1),)
    ici_shape = tuple(ici_shape)
    if axis_names is None:
        axis_names = (DCN_AXIS,) * len(dcn_shape) + (DATA_AXIS,) * len(ici_shape)
        if len(dcn_shape) != 1 or len(ici_shape) != 1:
            raise ValueError(
                "default axis_names only cover 1 dcn + 1 ici axis; pass "
                "axis_names explicitly for higher-rank hybrid meshes")
    n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    if n_slices > 1:
        from jax.experimental import mesh_utils
        # create_hybrid_device_mesh wants same-rank shapes and returns an
        # array of elementwise-product shape, so pad each side with 1s to
        # get a (*dcn_shape, *ici_shape) result
        arr = mesh_utils.create_hybrid_device_mesh(
            (1,) * len(dcn_shape) + ici_shape,
            dcn_shape + (1,) * len(ici_shape),
            devices=devices)
    else:
        arr = np.asarray(devices).reshape(dcn_shape + ici_shape)
    return Mesh(arr, tuple(axis_names))


def data_axes(mesh: Mesh) -> tuple:
    """The mesh axes that together form the data-parallel domain, DCN axis
    first. Algorithms shard batches and psum over ALL of these, so a flat
    ("data",) mesh and a ("dcn", "data") hybrid mesh with the same total
    device count run the identical SPMD program — the hybrid one simply
    routes the outer reduction leg over DCN."""
    axes = tuple(a for a in (DCN_AXIS, DATA_AXIS) if a in mesh.axis_names)
    if not axes:
        raise ValueError(
            f"mesh has no data-parallel axis: expected {DATA_AXIS!r} "
            f"(optionally with {DCN_AXIS!r}) among {mesh.axis_names}")
    return axes


def data_shard_count(mesh: Mesh) -> int:
    """Total data-parallel shard count (the reference's 'parallelism')."""
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


def data_pspec(mesh: Mesh):
    """The PartitionSpec dim-0 entry for batch sharding on this mesh: the
    single data axis name on a flat mesh, the (dcn, data) tuple on a hybrid
    one. Use as ``P(data_pspec(mesh), ...)``."""
    axes = data_axes(mesh)
    return axes[0] if len(axes) == 1 else axes


def model_axis_of(mesh: Mesh) -> Optional[str]:
    """The tensor-parallel axis name, or None on a DP-only mesh."""
    return MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None


def default_mesh() -> Mesh:
    """Process-wide default mesh (lazily: all devices on one data axis)."""
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = create_mesh()
    return _default_mesh


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh, _local_mesh
    _default_mesh = mesh
    _local_mesh = None


_local_mesh: Optional[Mesh] = None


def local_mesh() -> Mesh:
    """The mesh the *transform/predict* tier places batches on: the
    default mesh single-process, a data mesh over THIS process's
    addressable devices when the runtime spans processes
    (jax.distributed — docs/distributed.md "Multi-process meshes").
    Training is SPMD across every process, but prediction is a
    per-process operation — each process scores its own traffic, and a
    prediction column sharded over a multi-process mesh could never be
    fetched by its local caller (jax refuses to materialize
    non-addressable shards)."""
    global _local_mesh
    if jax.process_count() <= 1:
        return default_mesh()
    if _local_mesh is None:
        _local_mesh = create_mesh(devices=jax.local_devices())
    return _local_mesh
