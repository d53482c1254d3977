"""Named map-reduce training primitives — THE programming layer for fits.

DrJAX (arXiv:2403.07128) showed that large-scale map-reduce learning
programs want *named first-class primitives* — ``broadcast`` / ``map`` /
``reduce`` — rather than ad-hoc SPMD bodies: the names are where sharding
decisions, telemetry and static analysis attach. This module is that
layer for every fit program in the framework:

- **in-axis primitives** (used inside map bodies): :func:`broadcast`,
  :func:`reduce_sum` / :func:`reduce_mean` / :func:`reduce_max`,
  :func:`reduce_scatter`, :func:`all_gather`, :func:`shard_index` /
  :func:`shard_count`, plus the padding-mask helper
  :func:`local_valid_mask`. All delegate to ``parallel/collective.py``,
  so each records its trace-time ``ml.collective`` accounting
  (op count + payload bytes labeled ``{op=,axis=,devices=}`` —
  docs/observability.md "Distributed telemetry") for free.
- :func:`map_shards` — the ONE way a fit program becomes SPMD: wraps a
  per-shard body in the version-portable ``parallel/shardmap.py`` seam
  (inheriting mesh-topology telemetry) and jits it, optionally through
  ``instrumented_jit`` with buffer donation for the sharded-update
  carries. jaxlint rule JL108 ``raw-collective`` enforces that nothing
  outside ``flink_ml_tpu/parallel/`` calls ``jax.lax.psum``-family
  collectives or ``shard_map`` directly — programs go through here.
- :class:`MapReduceProgram` — composes *partition → map → reduce →
  update* into ONE jittable per-step program. The same program runs
  identically on a 1-device mesh and an N-device mesh: the primitives
  degrade to identities/local ops at N=1, so the single-device hot path
  pays nothing for the abstraction (gated by ``mltrace diff --budget``
  in scripts/mapreduce_bench.py).

The cross-replica *sharded* update (reduce-scatter the gradients, update
a ``1/N`` parameter/optimizer-state slice per replica, all-gather fresh
parameters — arXiv:2004.13336) composes from these primitives in
``parallel/update_sharding.py``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax

from flink_ml_tpu.parallel import collective as _c
from flink_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    data_axes,
    data_pspec,
    data_shard_count,
    default_mesh,
)
from flink_ml_tpu.parallel.shardmap import axis_size
from flink_ml_tpu.parallel.shardmap import shard_map as _shard_map

__all__ = [
    "broadcast", "map_shards", "map_rows", "reduce_sum", "reduce_mean",
    "reduce_max", "reduce_scatter", "renormalized_sum", "all_gather",
    "shard_index", "shard_count", "local_valid_mask", "MapReduceProgram",
]


# -- in-axis primitives (inside map bodies) -----------------------------------

def broadcast(x, axis_name=DATA_AXIS, src: int = 0):
    """Shard ``src``'s value on every shard (DrJAX ``broadcast``: one
    replicated value entering the mapped computation). One masked psum
    on the wire; records ``ml.collective`` at trace time."""
    return _c.broadcast_from(x, src=src, axis_name=axis_name)


def reduce_sum(x, axis_name=DATA_AXIS):
    """Sum of the per-shard partials on every shard (map → reduce)."""
    return _c.all_reduce_sum(x, axis_name)


def reduce_mean(x, axis_name=DATA_AXIS):
    return _c.all_reduce_mean(x, axis_name)


def reduce_max(x, axis_name=DATA_AXIS):
    return _c.all_reduce_max(x, axis_name)


def reduce_scatter(x, axis_name=DATA_AXIS):
    """Sum of the per-shard partials, scattered: each shard keeps its
    own ``1/N`` slice of dim 0 (see collective.reduce_scatter)."""
    return _c.reduce_scatter(x, axis_name)


def renormalized_sum(x, include, axis_name=DATA_AXIS):
    """Partial-participation reduce: shards with ``include=0`` contribute
    zero and the sum is rescaled by ``n_shards / participants`` so the
    update stays unbiased — the straggler-aware round primitive
    (parallel/elastic.py decides ``include`` per round on host; see
    collective.renormalized_sum)."""
    return _c.renormalized_sum(x, include, axis_name)


def all_gather(x, axis_name=DATA_AXIS, axis: int = 0, tiled: bool = True):
    return _c.all_gather(x, axis_name, axis=axis, tiled=tiled)


def shard_index(axis_name=DATA_AXIS):
    """This shard's position along the data axes (tuple-capable)."""
    return _c.shard_index(axis_name)


def shard_count(axis_name=DATA_AXIS) -> int:
    """Static total shard count over the (possibly tuple of) axes, from
    inside a traced body — a Python int at trace time."""
    axes = ((axis_name,) if isinstance(axis_name, str)
            else tuple(axis_name))
    return int(np.prod([axis_size(a) for a in axes]))


def local_valid_mask(axes, local_n: int, n_valid, dtype=None):
    """Per-shard validity mask for zero-padded batches (re-exported from
    the collective layer so map bodies import one module)."""
    import jax.numpy as jnp

    return _c.local_valid_mask(axes, local_n, n_valid,
                               dtype if dtype is not None else jnp.float32)


# -- the SPMD program seam ----------------------------------------------------

def map_shards(fn, mesh, in_specs, out_specs, *, check_vma: bool = False,
               jit: bool = True, donate_argnums=None,
               name: Optional[str] = None):
    """Build the named SPMD map: ``fn`` runs once per shard of the
    mesh's data domain with its inputs partitioned per ``in_specs``.

    THE seam every fit program builds through (JL108): wraps ``fn`` in
    the version-portable ``shard_map`` (recording mesh topology when
    tracing is armed) and jits the result. ``donate_argnums`` (the
    iteration state carries) makes the donated buffers update in place —
    the first rung of the raw-speed ladder (docs/performance.md); with
    ``name`` the jit additionally goes through ``instrumented_jit`` for
    per-function compile accounting. Donation WITHOUT a name keeps
    plain ``jax.jit``'s C++ dispatch cache — the per-batch hot loops
    (replicated FTRL, unsharded SGD) donate without paying a Python
    signature lookup per call. ``jit=False`` returns the bare mapped
    callable for host loops that jit the round themselves
    (iteration.iterate_bounded).

    The program's name on the device trace (``XLA Modules``:
    ``jit_<name>``) is ``fn.__name__`` — ``shard_map`` and ``jit`` carry
    it through — so fit bodies are given stable names (``sgd_segment``,
    ``sgd_round``), not ``per_shard``. ``name=`` is the
    compile-accounting label, not that name, and changes the dispatch."""
    mapped = _shard_map(fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=check_vma)
    if not jit:
        return mapped
    donate_kw = ({"donate_argnums": tuple(donate_argnums)}
                 if donate_argnums else {})
    if name is not None:
        from flink_ml_tpu.observability.compilestats import instrumented_jit

        return instrumented_jit(mapped, name=name, **donate_kw)
    return jax.jit(mapped, **donate_kw)


def map_rows(fn, mesh, *, n_extra: int = 0, name: Optional[str] = None,
             donate_argnums=None):
    """Row-parallel apply — the *serving* dispatch shape: argument 0 is
    sharded on dim 0 over the mesh's data axes, the ``n_extra``
    remaining arguments are replicated (model parameters), and the
    output is row-sharded, gathered to the host only when the caller
    fetches it.

    This is how a padded serving micro-batch (serving/batcher.py)
    spreads over the mesh: each device predicts its contiguous
    ``rows / N`` slice of the batch, no collective on the hot path at
    all — the gather happens on the fetch side of the dispatch. The
    caller guarantees dim 0 divides the data-shard count (the bucket
    table makes that a static property; non-divisible buckets stay on
    the single-device path). Embarrassingly row-parallel ``fn`` bodies
    need no primitives; a body that does reduce across rows would need
    the in-axis primitives above and should use :func:`map_shards`
    with explicit specs instead."""
    from jax.sharding import PartitionSpec as P

    spec0 = data_pspec(mesh)
    in_specs = (P(spec0),) + (P(),) * int(n_extra)
    return map_shards(fn, mesh, in_specs, P(spec0), name=name,
                      donate_argnums=donate_argnums)


class MapReduceProgram:
    """*partition → map → reduce → update* as ONE jittable SPMD step.

    The builder names the four phases of every distributed fit round
    (the reference's scatter / CalculateLocalGradient / all-reduce /
    UpdateModel pipeline, SURVEY.md §7) so a program is its composition,
    not an ad-hoc ``shard_map`` body::

        prog = MapReduceProgram(mesh, name="ftrl.dense")
        step = prog.build(map_fn, update_fn,
                          in_specs=(...), out_specs=(...))
        new_state = step(*data, *state)

    - ``map_fn(*args) -> partials`` runs per shard on the partitioned
      inputs and returns a pytree of local partials.
    - ``reduce`` (default :func:`reduce_sum`) is applied leaf-wise over
      the mesh's data axes; pass a pytree of reducers matching the
      partials to mix modes — e.g. ``reduce_scatter`` for the gradient
      leaf and ``reduce_sum`` for the loss scalar, the cross-replica
      sharded-update composition (update_sharding.py).
    - ``update_fn(reduced, *args) -> outputs`` consumes the reduced
      partials (on every shard, or each shard's slice) and produces the
      new state.

    The same built program runs identically on a 1-device and an
    N-device mesh — partition/reduce degrade to local ops at N=1.
    """

    def __init__(self, mesh=None, name: Optional[str] = None):
        self.mesh = mesh if mesh is not None else default_mesh()
        self.axes = data_axes(self.mesh)
        self.spec0 = data_pspec(self.mesh)
        self.n_shards = data_shard_count(self.mesh)
        self.name = name

    # -- partition (host boundary; records ml.collective opMs) ---------------
    def partition(self, array, dtype=None):
        """Place a batch on the mesh sharded on dim 0 (device-resident
        inputs reshard on device). Returns (device_array, true_rows)."""
        return _c.ensure_on_mesh(self.mesh, array, self.axes, dtype)

    def replicate(self, tree):
        """Broadcast-variable placement: the tree on every device."""
        return _c.replicate(self.mesh, tree)

    def data_spec(self, ndim: int = 1):
        """PartitionSpec for a dim-0-sharded operand of rank ``ndim``."""
        from jax.sharding import PartitionSpec as P

        return P(self.spec0, *([None] * (ndim - 1)))

    # -- the composed step ---------------------------------------------------
    def build(self, map_fn, update_fn, *, in_specs, out_specs,
              reduce=None, donate_argnums=None, check_vma: bool = False,
              jit: bool = True, name: Optional[str] = None):
        reducers = reduce if reduce is not None else reduce_sum
        axes = self.axes

        def per_shard(*args):
            partials = map_fn(*args)
            if callable(reducers):
                reduced = jax.tree_util.tree_map(
                    lambda p: reducers(p, axes), partials)
            else:  # pytree of per-leaf reducers matching the partials
                reduced = jax.tree_util.tree_map(
                    lambda r, p: r(p, axes), reducers, partials)
            return update_fn(reduced, *args)

        return map_shards(per_shard, self.mesh, in_specs, out_specs,
                          check_vma=check_vma, jit=jit,
                          donate_argnums=donate_argnums,
                          name=name or self.name)
