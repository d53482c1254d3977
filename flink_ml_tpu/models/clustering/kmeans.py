"""K-means clustering (Lloyd's algorithm).

Ref parity: flink-ml-lib/.../clustering/kmeans/{KMeans.java:79,
KMeansModel.java, KMeansModelData.java, KMeansParams.java}:

- init: k random points sampled from the input (selectRandomCentroids,
  KMeans.java:96,310);
- per round: assign every point to the nearest centroid, new centroid =
  mean of assigned points, model weights = assignment counts
  (CentroidsUpdateAccumulator + ModelDataGenerator, KMeans.java:200-280);
- termination: maxIter rounds (TerminateOnMaxIter, KMeans.java:150);
- predict: nearest-centroid index (KMeansModel.java:105).

TPU design: the whole fit is one compiled SPMD program — points stay sharded
on device across rounds (the ListStateWithCache equivalent), assignment is a
batched pairwise-distance matmul on the MXU, the per-round cross-task sync
(the reference's countWindowAll(parallelism).reduce) is a single psum of
(k,d) sums + (k,) counts. Deviation from the reference: an empty cluster
keeps its previous centroid instead of producing NaN.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.linalg.distance import DistanceMeasure
from flink_ml_tpu.parallel import mapreduce as mr
from flink_ml_tpu.parallel import update_sharding as _upd
from flink_ml_tpu.parallel.collective import ensure_on_mesh
from flink_ml_tpu.parallel.mesh import (
    data_axes,
    data_pspec,
    data_shard_count,
    default_mesh,
    local_mesh,
)
from flink_ml_tpu.params.param import IntParam, ParamValidators, StringParam
from flink_ml_tpu.params.shared import (
    HasDistanceMeasure,
    HasFeaturesCol,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
)
from flink_ml_tpu.models.common import IterationRuntimeMixin
from flink_ml_tpu.observability.tracing import cold_build, tracer
from flink_ml_tpu.utils import io as rw


class KMeansModelParams(HasDistanceMeasure, HasFeaturesCol, HasPredictionCol):
    K = IntParam("k", "The max number of clusters to create.", 2,
                 ParamValidators.gt(1))


class KMeansParams(KMeansModelParams, HasSeed, HasMaxIter):
    INIT_MODE = StringParam(
        "initMode", "The initialization algorithm.", "random",
        ParamValidators.in_array("random"))



@functools.lru_cache(maxsize=32)
def _build_assign_program(mesh, measure_name: str, use_kernel: bool = False):
    """Nearest-centroid index per row, row-parallel over the mesh: each
    device assigns its own shard of the batch (a pallas call under plain
    jit would have every device gather and assign the whole batch). With
    ``use_kernel`` (TPU + euclidean) the shard runs the fused
    distance+argmin kernel — no (n, k) block in HBM."""
    measure = DistanceMeasure.get_instance(measure_name)

    def assign(x, c):
        if use_kernel:
            from flink_ml_tpu.ops.pallas_kernels import assign_nearest
            return assign_nearest(x, c)
        return jnp.argmin(measure.pairwise(x, c), axis=1).astype(jnp.int32)

    return mr.map_rows(assign, mesh, n_extra=1)


def _local_valid_count(axes, local_n: int, n_valid):
    """How many of this shard's ``local_n`` rows are real: the shard holds
    the global rows ``[shard * local_n, (shard + 1) * local_n)`` and the
    rows from ``n_valid`` on are ``shard_batch``'s zero padding."""
    return jnp.clip(n_valid - mr.shard_index(axes) * local_n, 0, local_n)


def _lloyd_round_math(measure, axes, partials_fn=None,
                      sharded: bool = False):
    """The per-shard math of ONE Lloyd round — shared verbatim by the
    all-device programs and the host-driven round program so every mode
    stays numerically identical by construction. Must be called inside
    a ``mapreduce.map_shards`` body over the mesh's data axes (flat or
    dcn-hybrid).

    ``partials_fn(xl, nl, centroids) -> (k, d+1)`` overrides how the
    local [sums | counts] partials over the shard's first ``nl`` rows are
    computed (the fused pallas kernel); the cross-shard reduction and the
    empty-cluster-preserving renormalization stay shared either way. Both
    forms multiply in float32 (here at ``HIGHEST``; the kernel adds the
    same part-products itself, ``pallas_kernels._split3``). Caveat scoping
    the identity claim: the kernel's csq − 2·c·xᵀ assignment and its
    tile-by-tile sums differ from ``measure.pairwise`` and the one
    ``one_hot.T @ x`` in float32 rounding, so a kernel-partialed fit
    matches the XLA programs up to near-tie rows (the same asymmetry the
    predict path accepts for ``assign_nearest``) — modes sharing
    ``partials_fn=None`` remain bit-identical.

    With ``sharded`` (update_sharding.py) the centroid update is
    cross-replica sharded: the (k, d+1) partials reduce-scatter over
    centroid rows (padded to the shard multiple — padded rows count 0
    and are trimmed), each replica renormalizes only its own rows, and
    the fresh centroids all-gather. Per-replica update FLOPs scale
    1/N; the carry stays (k, d), so every caller is unchanged."""

    def local_partials(xl, nl, centroids):
        k = centroids.shape[0]
        # the validity mask from the scalar and an iota: XLA fuses it
        # into the one-hot, no (n,) operand
        vl = (jnp.arange(xl.shape[0]) < nl).astype(xl.dtype)
        dists = measure.pairwise(xl, centroids)
        one_hot = jax.nn.one_hot(jnp.argmin(dists, axis=1), k,
                                 dtype=xl.dtype) * vl[:, None]
        return jnp.concatenate(
            [jnp.dot(one_hot.T, xl, precision=jax.lax.Precision.HIGHEST),
             jnp.sum(one_hot, axis=0)[:, None]], axis=1)

    def renormalize(sums, counts, centroids):
        # ref CentroidsUpdateAccumulator; empty clusters keep position
        return jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1),
            centroids)

    def round_step(xl, nl, centroids):
        packed = (partials_fn or local_partials)(xl, nl, centroids)
        if sharded:
            k = centroids.shape[0]
            kp = _upd.padded_len(k, mr.shard_count(axes))

            def apply_fn(p_slice, c_slice, _state):
                sums, counts = p_slice[:, :-1], p_slice[:, -1]
                new_c = renormalize(sums, counts, c_slice)
                return (new_c, counts[:, None]), None

            (new_c, counts_col), _ = _upd.sharded_apply(
                axes, _upd.pad_leading(packed, kp),
                _upd.pad_leading(centroids, kp), None, apply_fn)
            return new_c[:k], counts_col[:k, 0]
        packed = mr.reduce_sum(packed, axes)
        sums, counts = packed[:, :-1], packed[:, -1]
        return renormalize(sums, counts, centroids), counts

    return round_step


@functools.lru_cache(maxsize=32)
@cold_build("lloyd")
def _build_lloyd_program(mesh, measure_name: str, max_iter: int,
                         unroll: bool = False, use_kernel: bool = False,
                         health: bool = False, sharded: bool = False):
    """One compiled Lloyd's program per (mesh, measure, maxIter); k and
    shapes are trace-time static, handled by jit's shape cache. With
    ``unroll`` the static round count compiles as a straight-line Python
    loop instead of a while_loop — identical results by construction (one
    round_step, one builder), but XLA may pipeline across rounds. With
    ``use_kernel`` (TPU + euclidean) the per-shard partials come from the
    fused pallas assign+accumulate kernel: each round reads the shard
    once instead of once per sub-op, where it lies: the kernel masks the
    ragged last tile itself, so the program keeps no copy of the shard
    and its temporaries do not grow with ``n``.

    Signature: ``fit(xs, n_valid, c0, counts0) -> (centroids, counts)``
    (``(..., shifts)`` with health). The ``(c0, counts0)`` carry is
    DONATED: the carry leaves match the outputs shape-for-shape, so the
    while/unrolled loop updates the centroid state in place — the same
    in-place contract as the SGD/FTRL carries. (The pre-donation layout
    packed ``[centroids | counts]`` into one ``(k, d+1)`` output to save
    a fetch, which matched no input buffer and blocked donation; the
    split costs one extra ``(k,)`` fetch ONCE per fit and unblocks the
    per-round in-place update.)

    With ``health`` (observability/health.py) ``shifts`` is the
    per-round Frobenius center-shift series ``(max_iter,)`` — ONE scalar
    per round folding every centroid element, so a NaN centroid surfaces
    as a NaN shift with no per-leaf host sync."""
    axes = data_axes(mesh)
    spec0 = data_pspec(mesh)
    partials_fn = None
    if use_kernel:
        from flink_ml_tpu.ops.pallas_kernels import lloyd_partial_sums
        partials_fn = lloyd_partial_sums
    round_step = _lloyd_round_math(
        DistanceMeasure.get_instance(measure_name), axes, partials_fn,
        sharded=sharded)

    def lloyd_fit(xl, n_valid, c0, counts0):
        nl = _local_valid_count(axes, xl.shape[0], n_valid)
        centroids, counts = c0, counts0
        shifts = jnp.zeros((max_iter if health else 0,), jnp.float32)
        if unroll:
            for epoch in range(max_iter):
                new_centroids, counts = round_step(xl, nl, centroids)
                if health:
                    shift = jnp.sqrt(jnp.sum(jnp.square(
                        new_centroids - centroids))).astype(jnp.float32)
                    shifts = shifts.at[epoch].set(shift)
                centroids = new_centroids
        else:
            def cond(state):
                _, _, epoch, _ = state
                return epoch < max_iter

            def step(state):
                centroids, counts, epoch, shifts = state
                new_centroids, counts = round_step(xl, nl, centroids)
                if health:
                    shift = jnp.sqrt(jnp.sum(jnp.square(
                        new_centroids - centroids))).astype(jnp.float32)
                    shifts = jax.lax.dynamic_update_index_in_dim(
                        shifts, shift, epoch, 0)
                return new_centroids, counts, epoch + 1, shifts

            centroids, counts, _, shifts = jax.lax.while_loop(
                cond, step, (centroids, counts, jnp.int32(0), shifts))
        return ((centroids, counts, shifts) if health
                else (centroids, counts))

    return mr.map_shards(
        lloyd_fit, mesh,
        in_specs=(P(spec0, None), P(), P(), P()),
        out_specs=((P(), P(), P()) if health else (P(), P())),
        donate_argnums=(2, 3),
        name="kmeans.lloyd" if sharded else None)


@functools.lru_cache(maxsize=32)
@cold_build("init_rows")
def _build_init_rows_program(mesh, k: int):
    """``rows(xs, index) -> (k, d)`` replicated: the chosen rows of the
    row-sharded column, each taken by the shard that holds it and summed
    over the shards (the others add zeros: exact). One cached program a
    mesh and ``k`` in place of a fancy index traced in every fit; its
    output is a fresh buffer, so the fit program may take it as its
    donated carry. The rows are ``k`` dynamic slices, not a gather: a
    resident ``(n, 100)`` float32 column lies column-major on the TPU, a
    gather wants it row-major, and XLA then copies the whole column first
    (6.1 GB and 17 ms a fit at 12M rows; a ``lax.map`` over the indices
    does the same)."""
    axes = data_axes(mesh)

    def lloyd_init_rows(xl, index):
        local_n = xl.shape[0]
        local = index - mr.shard_index(axes) * local_n
        mine = (local >= 0) & (local < local_n)
        rows = jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(xl, local[j], 1, axis=0)
             for j in range(k)], axis=0)  # (an index off the shard clamps)
        return mr.reduce_sum(jnp.where(mine[:, None], rows, 0.0), axes)

    return mr.map_shards(
        lloyd_init_rows, mesh,
        in_specs=(P(data_pspec(mesh), None), P()), out_specs=P())


#: fits with at most this many rounds compile fully unrolled — Lloyd's has
#: no data-dependent exit (TerminateOnMaxIter only, ref KMeans.java:150),
#: so the unrolled body is just max_iter repetitions XLA can pipeline
#: (compile time scales with the unroll; 0 disables unrolling)
_UNROLL_MAX_ROUNDS = int(os.environ.get(
    "FLINK_ML_TPU_LLOYD_UNROLL_MAX", "64"))


@functools.lru_cache(maxsize=32)
def _build_lloyd_round_program(mesh, measure_name: str,
                               sharded: bool = False):
    """ONE Lloyd round, compiled once and found again in every fit — the
    building block of the host-driven rounds (``iterate_bounded`` calls
    it as it is, ``jit_round=False``); wraps the same _lloyd_round_math
    as the all-device program. Never the kernel: per-round dispatch is
    host-bound already, and listeners may hold the carry between rounds,
    so nothing is donated either."""
    axes = data_axes(mesh)
    round_step = _lloyd_round_math(
        DistanceMeasure.get_instance(measure_name), axes, sharded=sharded)

    def lloyd_round(xl, n_valid, centroids):
        return round_step(
            xl, _local_valid_count(axes, xl.shape[0], n_valid), centroids)

    return mr.map_shards(
        lloyd_round, mesh,
        in_specs=(P(data_pspec(mesh), None), P(), P()),
        out_specs=(P(), P()))


@functools.lru_cache(maxsize=32)
def _build_lloyd_segment_program(mesh, measure_name: str,
                                 sharded: bool = False,
                                 use_kernel: bool = False,
                                 fused: bool = True):
    """The rounds ``[epoch0, limit)`` as one compiled while_loop — the
    checkpointed fit's segment, the same program for every segment of
    every fit (the bounds are arguments). ``seg(xs, n_valid, centroids,
    counts, epoch0, limit) -> (centroids, counts, boundary)``; the
    ``(centroids, counts)`` carry is DONATED. ``boundary`` is what
    ``iteration.read_boundary`` fetches: ``[epoch, stop]`` stacked into
    one int32 vector when ``fused`` (one transfer a boundary), the two
    scalars otherwise; Lloyd has no early stop, so ``stop`` is 0. With
    ``use_kernel`` (TPU + euclidean) the partials come from the fused
    pallas assign+accumulate kernel, which reads the shard where it lies
    (no pad, no copy)."""
    axes = data_axes(mesh)
    partials_fn = None
    if use_kernel:
        from flink_ml_tpu.ops.pallas_kernels import lloyd_partial_sums
        partials_fn = lloyd_partial_sums
    round_step = _lloyd_round_math(
        DistanceMeasure.get_instance(measure_name), axes, partials_fn,
        sharded=sharded)

    def lloyd_segment(xl, n_valid, centroids, counts, epoch0, limit):
        nl = _local_valid_count(axes, xl.shape[0], n_valid)

        def step(state):
            centroids, _, epoch = state
            return round_step(xl, nl, centroids) + (epoch + 1,)

        centroids, counts, epoch = jax.lax.while_loop(
            lambda state: state[2] < limit, step,
            (centroids, counts, epoch0))
        stop = jnp.zeros((), jnp.int32)
        return centroids, counts, (jnp.stack([epoch, stop]) if fused
                                   else (epoch, stop))

    return mr.map_shards(
        lloyd_segment, mesh,
        in_specs=(P(data_pspec(mesh), None),) + (P(),) * 5,
        out_specs=(P(), P(), P() if fused else (P(), P())),
        donate_argnums=(2, 3))


class KMeansModel(Model, KMeansModelParams):
    def __init__(self, centroids: Optional[np.ndarray] = None,
                 weights: Optional[np.ndarray] = None, **kwargs):
        super().__init__(**kwargs)
        self.centroids = None if centroids is None else np.asarray(centroids)
        self.weights = None if weights is None else np.asarray(weights)

    def transform(self, table: Table) -> Tuple[Table]:
        if self.centroids is None:
            raise ValueError("KMeansModel has no model data")
        x = table.vectors(self.features_col)
        from flink_ml_tpu.ops.pallas_kernels import (
            lloyd_kernel_fits,
            pallas_supported,
        )
        k, dim = self.centroids.shape
        # the assign kernel's working set is a subset of the fused Lloyd
        # round's, so one shape gate serves both
        use_kernel = (self.distance_measure == "euclidean"
                      and pallas_supported() and lloyd_kernel_fits(k, dim))
        mesh = local_mesh()
        xs, n = ensure_on_mesh(mesh, x, data_axes(mesh), jnp.float32)
        assign = _build_assign_program(mesh, self.distance_measure,
                                       use_kernel)
        labels = np.asarray(assign(
            xs, jnp.asarray(self.centroids, jnp.float32)))[:n]
        # benchmark provenance (runner.py executionPath)
        self.last_execution_path = ("pallas-assign" if use_kernel
                                    else "xla-assign")
        return (table.with_column(self.prediction_col,
                                  labels.astype(np.int64)),)

    # -- model data (ref: KMeansModelData = centroids[] + weights) ----------
    def set_model_data(self, model_data: Table):
        cents = model_data.vectors("centroid", dtype=np.float64)
        self.centroids = cents
        self.weights = (model_data.scalars("weight", np.float64)
                        if "weight" in model_data
                        else np.ones(len(cents)))
        return self

    def get_model_data(self) -> Tuple[Table]:
        return (Table.from_columns(
            centroid=as_dense_vector_column(self.centroids),
            weight=np.asarray(self.weights, np.float64)),)

    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {
            "centroids": self.centroids, "weights": self.weights})

    def _load_extra(self, path: str, meta: dict) -> None:
        arrays = rw.load_model_arrays(path, "model")
        self.centroids, self.weights = arrays["centroids"], arrays["weights"]


class KMeans(Estimator, KMeansParams, IterationRuntimeMixin):
    def fit(self, table: Table) -> KMeansModel:
        return self._supervised_fit(lambda: self._fit_once(table))

    def _fit_once(self, table: Table) -> KMeansModel:
        x = table.vectors(self.features_col)
        n, dim = x.shape
        k = self.k
        mesh = default_mesh()
        axes = data_axes(mesh)
        repl = NamedSharding(mesh, P())
        with tracer.span("lloyd.place_inputs"):
            # device-resident input (device datagen / upstream device
            # stage) never leaves HBM; host input is cast+placed once
            xs, _ = ensure_on_mesh(mesh, x, axes, jnp.float32)

        if tracer.enabled:
            # mesh telemetry at the fit boundary: per-shard row counts
            # (imbalance/skew) and per-shard non-finite input counts, so
            # a bad replica is identifiable before the fit consumes it
            from flink_ml_tpu.observability import meshstats
            meshstats.record_shard_rows(mesh, n, axes)
            meshstats.record_input_health("KMeans", mesh, xs)

        from flink_ml_tpu.iteration.iteration import (
            device_checkpoint_segment, iterate_bounded, needs_host_loop,
            read_boundary, run_segmented, segment_fusion_enabled)
        from flink_ml_tpu.observability import health as _health
        from flink_ml_tpu.ops.pallas_kernels import (
            lloyd_kernel_fits, pallas_supported)
        health_on = _health.armed()
        # cross-replica sharded centroid update (update_sharding.py):
        # per-replica update FLOPs scale 1/N; carry shape unchanged
        sharded = _upd.enabled()
        listeners = self._iteration_listeners
        host_loop = needs_host_loop(self._iteration_config, listeners)
        seg = device_checkpoint_segment(self._iteration_config, listeners)
        # the kernel is chosen by the backend and the shape gate, nothing
        # else; a Mosaic failure propagates. Segment-mode fits (compiled
        # K-round while_loop slices) use it like the all-device path;
        # true host rounds keep the XLA partials (per-round dispatch is
        # already host-bound there, and listeners may inspect the carry
        # between rounds)
        use_kernel = ((seg > 0 or not host_loop)
                      and self.distance_measure == "euclidean"
                      and pallas_supported() and lloyd_kernel_fits(k, dim))
        path = ("host-rounds" if host_loop and not seg else
                ("pallas-lloyd" if use_kernel else "xla-lloyd")
                + ("-segments" if seg else ""))

        with tracer.span("lloyd.init", rounds=self.max_iter, k=k,
                         path=path):
            # init: k distinct random input points (ref
            # selectRandomCentroids); fewer points than clusters repeat
            # cyclically. The indices, the zero counts and the row count
            # (padded rows must not join any cluster: the validity mask
            # is derived on-device from the scalar n) go up as host arrays
            # in ONE call; the rows leave the column by one cached program,
            # into a fresh buffer that the fit may take as donated
            rng = np.random.default_rng(self.get_seed_or_default())
            index = np.resize(
                rng.choice(n, size=min(k, n), replace=False), k)
            index, counts0, n_valid = jax.device_put(
                (index.astype(np.int32), np.zeros((k,), np.float32),
                 np.int32(n)), repl)
            c0 = _build_init_rows_program(mesh, k)(xs, index)
            # per-replica update-state accounting (benchmark provenance)
            # from the carry's real buffers, honestly full-size: the
            # centroid carry all-gathers back to replicated every round
            # even when the sharded update ran (only persistent sharded
            # state like FTRL's z/n shrinks 1/N)
            _upd.record_state_bytes("KMeans", (c0, counts0),
                                    data_shard_count(mesh), sharded)

        shifts = []  # the health-armed device fit's center-shift series
        if not host_loop:
            with tracer.span("lloyd.build_program"):
                fit = _build_lloyd_program(
                    mesh, self.distance_measure, self.max_iter,
                    unroll=self.max_iter <= _UNROLL_MAX_ROUNDS,
                    use_kernel=use_kernel, health=health_on,
                    sharded=sharded)
            with tracer.span("lloyd.launch"):
                # the (c0, counts0) carry is DONATED: the loop updates
                # the centroid state in place
                centroids, counts, *shifts = fit(xs, n_valid, c0, counts0)
        elif seg:
            with tracer.span("lloyd.build_program"):
                segment = _build_lloyd_segment_program(
                    mesh, self.distance_measure, sharded=sharded,
                    use_kernel=use_kernel, fused=segment_fusion_enabled())

            def run_segment(carry, epoch0, limit):
                centroids, counts, boundary = segment(
                    xs, n_valid, *carry, np.int32(epoch0), np.int32(limit))
                epoch, stop = read_boundary(boundary)
                return (centroids, counts), int(epoch), bool(stop)

            # the segments are enqueued, and their boundaries awaited, by
            # the iteration runtime: one ``segment`` span each under this
            # one. A segmented fit gains no health listener (that would
            # demote it to per-round host dispatch); it keeps the cheap
            # final-state guard
            with tracer.span("lloyd.launch"):
                centroids, counts = run_segmented(
                    run_segment, (c0, counts0), self.max_iter, seg,
                    self._iteration_config.checkpoint_manager)
        else:
            if health_on:
                # true host-driven rounds: the center-shift series rides
                # a listener at the epoch boundary
                listeners = tuple(listeners) + (
                    _health.ConvergenceListener.for_centroids(
                        "KMeans", np.asarray(c0)),)
            with tracer.span("lloyd.build_program"):
                one_round = _build_lloyd_round_program(
                    mesh, self.distance_measure, sharded=sharded)
            # one ``epoch`` span a round under this one
            with tracer.span("lloyd.launch"):
                centroids, counts = iterate_bounded(
                    (c0, counts0),
                    lambda carry, epoch: one_round(xs, n_valid, carry[0]),
                    max_iter=self.max_iter, config=self._iteration_config,
                    listeners=listeners, jit_round=False)
        with tracer.span("lloyd.fetch"):
            # the blocking reads, where the wait for the rounds falls:
            # counted, and under the collective deadline where one is armed
            centroids, counts, *shifts = read_boundary(
                (centroids, counts, *shifts))
        # benchmark provenance (runner.py executionPath)
        self.last_execution_path = path
        with tracer.span("lloyd.health"):
            if shifts:
                series = np.asarray(shifts[0], np.float64)
                _health.check_fit("KMeans", {"centerShift": series},
                                  finite=bool(np.isfinite(series).all()))
            elif seg or not health_on:
                # (health-armed host rounds: the listener has checked)
                _health.guard_final_state("KMeans", centroids)
        with tracer.span("fit.model"):
            model = KMeansModel(
                centroids=np.asarray(centroids, np.float64),
                weights=np.asarray(counts, np.float64))
            return self.copy_params_to(model)
