"""``shard_map`` — THE seam every SPMD program builds on.

Every fit program and test in this repo goes through :func:`shard_map`
below rather than ``jax.shard_map`` directly: jaxlint JL108 keeps raw
collectives and direct ``shard_map`` calls inside ``parallel/``, and
this is the mesh-telemetry seam (docs/observability.md "Distributed
telemetry"): wrapping a program over a mesh is the moment the runtime
provably commits to a topology, so when tracing is armed the mesh
snapshot (device count, axis layout, platform) is recorded here — once
per mesh — as root-span attributes, ``ml.mesh`` gauges and a
``mesh.json`` trace artifact (observability/meshstats.py).
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "axis_size"]


def shard_map(f, mesh=None, in_specs=None, out_specs=None,
              check_vma: bool = True):
    """``jax.shard_map`` plus the mesh-telemetry record. All arguments
    after ``f`` are keyword-style."""
    _record_mesh(mesh)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name) -> int:
    """Static size of a named mesh axis, from inside a traced body."""
    return jax.lax.axis_size(axis_name)


def _record_mesh(mesh) -> None:
    """Mesh-topology telemetry at the program-build seam; free when the
    tracer is disarmed, once per mesh when armed."""
    if mesh is None:
        return
    try:
        from flink_ml_tpu.observability import meshstats

        meshstats.ensure_mesh_recorded(mesh)
    except Exception:  # telemetry must never sink a program build
        pass
