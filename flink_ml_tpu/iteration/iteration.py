"""Bounded iteration driver.

Ref parity map:
- ``Iterations.iterate_bounded_streams_until_termination``
  (Iterations.java:149) → :func:`iterate_bounded`.
- ``IterationBody.process`` (IterationBody.java:54) → the ``body`` callable:
  ``body(carry, epoch) -> carry`` traced once and compiled.
- ``IterationListener.onEpochWatermarkIncremented / onIterationTerminated``
  → :class:`IterationListener` callbacks (host mode).
- Termination (SharedProgressAligner.java:277-292 + TerminateOnMaxIterOrTol)
  → ``max_iter`` bound plus an optional ``terminate`` predicate on the carry
  (tol comparison, empty-round vote, ...), evaluated on device.
- ALL_ROUND vs PER_ROUND operator lifecycles (IterationConfig) → carry state
  persists across rounds (all-round) vs ``per_round_init`` resetting part of
  the carry each epoch (per-round).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from flink_ml_tpu.resilience import faults

Carry = Any
Body = Callable[[Carry, jnp.ndarray], Carry]
Terminate = Callable[[Carry, jnp.ndarray], jnp.ndarray]  # -> bool scalar


def segment_fusion_enabled() -> bool:
    """Segment-boundary fusion (default ON): the compiled segment
    programs stack their per-boundary scalars — epoch, stop flag, and
    (with health telemetry) the non-finite sentinel — into ONE int32
    vector, so each boundary costs one device→host transfer instead of
    one per scalar. ``FLINK_ML_TPU_SEGMENT_FUSION=0`` restores the
    scalar-by-scalar pre-fusion path (results are bit-identical either
    way — the fusion only changes how the already-computed scalars reach
    the host, never what the program computes)."""
    return os.environ.get("FLINK_ML_TPU_SEGMENT_FUSION", "1") != "0"


def read_boundary(boundary) -> list:
    """Bring a boundary's values to the host under ONE wait, counting
    what it costs into ``ml.iteration``: ``boundaryFetches`` is the
    device→host transfers (the quantity the perf ratchet gates on: 1 per
    boundary when fused), ``boundaryWaits`` the calls, so leaves over
    waits says how many transfers shared a wait. ``boundary`` is either
    one stacked device vector (the fused form — ONE transfer, returned
    as its numpy scalars in order) or a tuple/list of leaves, returned
    as numpy values in order: with two leaves or more ``jax.device_get``
    starts every device leaf's copy before it waits on the first, so a
    fit's results cross together instead of one blocking read after
    another; host scalars and numpy leaves pass through."""
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics
    from flink_ml_tpu.parallel import elastic

    # the boundary fetch is where a wedged inter-process reduce leg
    # surfaces on host: with FLINK_ML_TPU_COLLECTIVE_TIMEOUT_S armed
    # the sync runs under a watchdog and a dead peer becomes a
    # retryable WorkerLost instead of a hang (parallel/elastic.py)
    boundary = elastic.guard_fetch(boundary, what="segment boundary")
    grp = metrics.group(ML_GROUP, "iteration")
    grp.counter("boundaryWaits")
    if isinstance(boundary, (tuple, list)):
        # a lone leaf has no other to share its wait with: it is read
        # as it is (starting its copy first costs 0.04 ms on the chip)
        leaves = (jax.device_get(list(boundary)) if len(boundary) > 1
                  else boundary)
        vals = [np.asarray(v) for v in leaves]
        grp.counter("boundaryFetches", len(vals))
        return vals
    vals = list(np.asarray(boundary))
    grp.counter("boundaryFetches")
    return vals


@dataclasses.dataclass
class IterationConfig:
    """Ref: iteration/IterationConfig.java + our driver knobs."""

    #: "device": one jitted lax.while_loop — zero host round-trips; fastest.
    #: "host": python loop over a jitted round — enables listeners,
    #: checkpoints and data-dependent host logic between rounds.
    mode: str = "device"

    #: checkpoint every N epochs (0 = never). Device mode runs N-round
    #: compiled segments with a snapshot between them (the fast path and
    #: fault tolerance compose); host mode snapshots between rounds.
    checkpoint_interval: int = 0
    checkpoint_manager: Optional[Any] = None

    #: host mode: reset part of the carry each round (PER_ROUND lifecycle).
    per_round_init: Optional[Callable[[Carry, int], Carry]] = None

    def __post_init__(self):
        if self.mode not in ("device", "host"):
            raise ValueError(
                f"IterationConfig.mode must be 'device' or 'host', "
                f"got {self.mode!r}")


class IterationListener:
    """Ref: iteration/IterationListener.java, extended with the restart/
    recovery events the reference gets from Flink's restart strategy
    (emitted by resilience.supervisor.run_supervised, not by the
    iteration drivers themselves)."""

    def on_epoch_watermark_incremented(self, epoch: int, carry: Carry) -> None:
        pass

    def on_iteration_terminated(self, carry: Carry) -> None:
        pass

    def on_restart(self, attempt: int, error: BaseException) -> None:
        """A supervised run failed retryably; restart ``attempt`` (1-based)
        is about to re-enter from the newest valid checkpoint."""

    def on_recovered(self, attempt: int) -> None:
        """A supervised run completed after ``attempt`` restart(s)."""


def iterate_bounded(initial_carry: Carry,
                    body: Body,
                    max_iter: int,
                    terminate: Optional[Terminate] = None,
                    config: IterationConfig = None,
                    listeners: Sequence[IterationListener] = (),
                    jit_round: bool = True,
                    donate_carry: bool = False) -> Carry:
    """Run ``body`` for up to ``max_iter`` epochs; stop early when
    ``terminate(carry, epoch)`` is True. Returns the final carry.

    The carry is an arbitrary pytree and may contain device arrays with any
    sharding — cached training data sharded over the data axis rides along
    exactly like the reference's in-loop data cache.

    ``jit_round=False`` runs the body as plain host code per round (no
    tracing) — for bodies whose math lives on host (the CSR sparse trainer:
    scipy matvecs have no XLA form) and for bodies that call a compiled
    round program of their own, built once and found again in every fit
    (SGD's host rounds). Such bodies always use the host loop.

    ``donate_carry=True`` donates the carry buffers through the compiled
    device/segment loops (the update happens in place — no fresh
    allocation per call). Opt-in because donation CONSUMES
    ``initial_carry``: only callers that build fresh carry buffers and
    never reuse them afterwards (the algorithm fast paths) may set it.
    The host loop never donates — listeners legitimately hold references
    to lagged carries (health.ConvergenceListener), which donation would
    delete out from under them."""
    config = config or IterationConfig()
    seg = device_checkpoint_segment(config, listeners)
    if jit_round and seg:
        return _segmented_device_loop(initial_carry, body, max_iter,
                                      terminate, config, seg,
                                      donate_carry=donate_carry)
    if jit_round and not needs_host_loop(config, listeners):
        return _device_loop(initial_carry, body, max_iter, terminate,
                            donate_carry=donate_carry)
    return _host_loop(initial_carry, body, max_iter, terminate, config,
                      listeners, jit_round)


def needs_host_loop(config: Optional[IterationConfig],
                    listeners: Sequence[IterationListener] = ()) -> bool:
    """True when any configured behavior requires host-driven rounds.
    The single source of truth for the device/host dispatch — algorithm fast
    paths (SGD, KMeans) must consult this instead of re-deriving it.

    Checkpointing alone no longer lands here: a device-mode fit with only
    interval checkpointing runs K-round compiled segments with a host
    snapshot between them (:func:`device_checkpoint_segment`) — fast paths
    must check that FIRST, then this."""
    if config is None:
        return bool(listeners)
    return bool(listeners) or config.mode == "host" \
        or config.checkpoint_interval != 0 \
        or config.checkpoint_manager is not None \
        or config.per_round_init is not None


def device_checkpoint_segment(
        config: Optional[IterationConfig],
        listeners: Sequence[IterationListener] = ()) -> int:
    """K (the checkpoint interval) when the ONLY host hook is interval
    checkpointing and the mode is "device": the iteration then runs as
    K-round compiled ``while_loop`` segments with the carry snapshotted on
    host between segments — fault tolerance composes with the fast path
    (ref bar: every reference job checkpoints *through* the iteration,
    Checkpoints.java:43, without leaving its execution mode).  0 when the
    configuration needs true per-round host hooks (listeners,
    per_round_init, mode="host") or no checkpointing is requested."""
    if config is None or listeners:
        return 0
    if (config.mode != "device" or config.per_round_init is not None
            or config.checkpoint_manager is None
            or config.checkpoint_interval <= 0):
        return 0
    return config.checkpoint_interval


def run_segmented(run_segment, initial_carry, max_iter: int, K: int, mgr):
    """Drive ``run_segment(carry, epoch0, limit) -> (carry, epoch, stop)``
    in K-round chunks with a checkpoint at every K-round boundary — the
    shared segment driver for the generic iteration and for algorithm fast
    paths that build their own compiled segment program (SGD does;
    KMeans rides the generic :func:`_segmented_device_loop` through
    ``iterate_bounded``, which wraps its shard_mapped round body in the
    segmented while_loop).

    ``run_segment`` implementations fetch their own boundary scalars
    (through :func:`read_boundary`, so the transfers are counted and —
    fused — cost ONE device→host round-trip per boundary) and return
    host values; legacy device scalars still work (``int``/``bool``
    coerce them, at one transfer each).

    Checkpoint cadence matches the host loop exactly: a snapshot lands
    after every K completed rounds — EXCEPT the final boundary of a
    completing run, whose snapshot ``mgr.clear()`` below would delete
    before anything could restore it: that save (a full carry
    device→host transfer) is skipped. An early stop mid-segment saves
    nothing, and a completed run clears its checkpoints. A restore
    landing off the K-grid (a snapshot from a different interval or
    mode) realigns at the first segment so later boundaries checkpoint
    on-grid again."""
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics
    from flink_ml_tpu.observability import compilestats, tracing
    iter_group = metrics.group(ML_GROUP, "iteration")

    import time as _time

    carry, epoch = initial_carry, 0
    restored = mgr.restore(carry)
    if restored is not None:
        carry, epoch = restored
    stop = False
    prev_ctx = None
    while epoch < max_iter and not stop:
        # realign to the K-grid so `epoch % K == 0` keeps firing after an
        # off-phase restore
        limit = min(epoch + K - epoch % K, max_iter)
        seg_start = _time.perf_counter()
        # each segment follows from the previous one: the explicit
        # carry-handoff edge `flink-ml-tpu-trace path` walks
        with tracing.tracer.span("segment", epoch_from=epoch,
                                 epoch_to=limit,
                                 links=([prev_ctx] if prev_ctx
                                        else None)) as sp:
            carry, e, s = run_segment(carry, epoch, limit)
            if tracing.tracer.enabled:
                # per-shard time-to-ready at the boundary: the straggler
                # surface of the segment (ml.shard readyMs with
                # shard=/device= labels, ml.skew on spread). With fusion
                # the boundary scalars synced inside run_segment, so on
                # a real TPU this measures the residual drain of the
                # carry outputs (on CPU the program was always complete
                # by now either way).
                from flink_ml_tpu.observability import meshstats
                meshstats.observe_shard_ready(carry, span=sp,
                                              phase="segment")
            rounds = int(e) - epoch
            epoch, stop = int(e), bool(s)
            sp.set_attribute("rounds", rounds)
            iter_group.counter("boundaries")
            # chaos site: the segment boundary is this mode's epoch
            # boundary
            faults.inject("epoch-boundary", epoch=epoch)
            # heartbeat + worker-loss/worker-hang chaos probe
            # (multi-process only; see parallel/elastic.py)
            from flink_ml_tpu.parallel import elastic
            elastic.on_boundary(epoch)
            done = epoch >= max_iter or stop
            if epoch % K == 0 and not done:
                mgr.save(carry, epoch)
            if tracing.tracer.enabled:
                # HBM watermark at the segment boundary (the host-sync
                # point, so the sample costs no extra device round-trip;
                # silent no-op on CPU)
                compilestats.sample_memory("segment", span=sp)
            prev_ctx = tracing.context_of(sp)
        # per-segment metrics: the host-sync boundary is already here, so
        # the counters cost no extra device round-trip
        seg_ms = (_time.perf_counter() - seg_start) * 1000.0
        iter_group.counter("rounds", rounds)
        iter_group.gauge("lastSegmentMs", seg_ms)
        iter_group.gauge("lastRoundMs", seg_ms / max(rounds, 1))
        # histories survive the fit (last-value gauges don't): per-epoch
        # duration distribution, labeled by execution mode
        iter_group.histogram(
            "epochMs", labels={"mode": "device-segment"}).observe(
            seg_ms / max(rounds, 1))
        iter_group.histogram(
            "segmentMs", labels={"mode": "device-segment"}).observe(seg_ms)
    mgr.clear()
    return carry


def _segmented_device_loop(initial_carry, body, max_iter, terminate, config,
                           K: int, donate_carry: bool = False):
    """Device-mode iteration with interval checkpointing: one jitted
    ``while_loop`` per K-round segment (epoch bounds are device scalars, so
    every segment reuses one compilation), carry snapshotted between
    segments.  Numerically identical to :func:`_device_loop` by
    construction — both build on :func:`_loop_pieces`.

    The boundary scalars (epoch, stop) come back stacked as one int32
    vector when :func:`segment_fusion_enabled` — one transfer per
    boundary; ``FLINK_ML_TPU_SEGMENT_FUSION=0`` keeps them separate.
    With ``donate_carry`` the carry buffers are donated into each
    segment (in-place update; the previous segment's output is consumed
    only after its checkpoint snapshot, so restore still sees every
    saved state)."""
    cond, step = _loop_pieces(body, terminate)
    fused = segment_fusion_enabled()

    @functools.partial(jax.jit,
                       donate_argnums=(0,) if donate_carry else ())
    def seg(carry, epoch0, limit):
        carry, epoch, stop, _ = jax.lax.while_loop(
            cond, step, (carry, epoch0, jnp.asarray(False), limit))
        if fused:
            return carry, jnp.stack([epoch, stop.astype(jnp.int32)])
        return carry, epoch, stop

    def run_segment(carry, epoch0, limit):
        out = seg(carry, jnp.int32(epoch0), jnp.int32(limit))
        boundary = out[1] if fused else out[1:]
        vals = read_boundary(boundary)
        return out[0], int(vals[0]), bool(vals[1])

    return run_segmented(run_segment, initial_carry, max_iter, K,
                         config.checkpoint_manager)


def _loop_pieces(body, terminate):
    """The shared while_loop (cond, step) over state
    ``(carry, epoch, stop, limit)`` — ONE definition of the round/stop
    structure so the full device loop and the checkpointed segment loop
    cannot drift apart numerically.  Termination is evaluated *after*
    each round on the just-completed epoch, matching _host_loop exactly —
    all modes must be numerically interchangeable (a listener or a
    checkpoint must never change the result)."""

    def cond(state):
        carry, epoch, stop, limit = state
        return jnp.logical_and(epoch < limit, jnp.logical_not(stop))

    def step(state):
        carry, epoch, _, limit = state
        new_carry = body(carry, epoch)
        stop = (jnp.asarray(terminate(new_carry, epoch), dtype=bool)
                if terminate is not None else jnp.asarray(False))
        return new_carry, epoch + 1, stop, limit

    return cond, step


def _device_loop(initial_carry, body, max_iter, terminate,
                 donate_carry: bool = False):
    """Single compiled while_loop: the whole iteration is one XLA program
    (the K=max_iter degenerate case of the segmented loop). With
    ``donate_carry`` the carry buffers update in place (the caller's
    ``initial_carry`` is consumed)."""
    cond, step = _loop_pieces(body, terminate)

    @functools.partial(jax.jit,
                       donate_argnums=(0,) if donate_carry else ())
    def run(carry):
        final_carry, _, _, _ = jax.lax.while_loop(
            cond, step,
            (carry, jnp.int32(0), jnp.asarray(False), jnp.int32(max_iter)))
        return final_carry

    return run(initial_carry)


def _host_loop(initial_carry, body, max_iter, terminate, config, listeners,
               jit_round: bool = True):
    """Host-driven rounds with listener/checkpoint hooks.

    The jitted round returns (carry, stop) so the only host sync per round is
    one scalar — the same single-bit exchange as the reference's
    GloballyAlignedEvent, minus the RPC. With ``jit_round=False`` the body
    runs as plain host code (CSR math); the stop bit is then immediate.
    """

    if jit_round:
        def round_impl(carry, epoch):
            new_carry = body(carry, epoch)
            stop = (jnp.asarray(terminate(new_carry, epoch), dtype=bool)
                    if terminate is not None else jnp.asarray(False))
            return new_carry, stop

        round_fn = jax.jit(round_impl)
    else:
        # the body runs as it is: plain host rounds (CSR math: no jnp
        # anywhere, so no device backend is ever initialized), or a round
        # that calls a compiled program of its own, whose stop bit then
        # stays on the device until the guarded fetch below
        def round_fn(carry, epoch):
            new_carry = body(carry, epoch)
            stop = (terminate(new_carry, epoch)
                    if terminate is not None else False)
            return new_carry, stop

    from flink_ml_tpu.common.metrics import ML_GROUP, metrics
    from flink_ml_tpu.observability import compilestats, tracing
    iter_group = metrics.group(ML_GROUP, "iteration")
    mode_label = {"mode": "host"}

    carry = initial_carry
    start_epoch = 0
    mgr = config.checkpoint_manager
    if mgr is not None:
        restored = mgr.restore(carry)
        if restored is not None:
            carry, start_epoch = restored

    import time as _time
    prev_ctx = None
    for epoch in range(start_epoch, max_iter):
        round_start = _time.perf_counter()
        # epoch N follows from epoch N-1: the carry-handoff edge the
        # critical-path view (`flink-ml-tpu-trace path`) walks
        with tracing.tracer.span("epoch", epoch=epoch,
                                 links=([prev_ctx] if prev_ctx
                                        else None)) as sp:
            if config.per_round_init is not None:
                carry = config.per_round_init(carry, epoch)
            carry, stop = round_fn(
                carry, np.int32(epoch) if jit_round else epoch)
            faults.inject("epoch-boundary", epoch=epoch)
            from flink_ml_tpu.parallel import elastic
            elastic.on_boundary(epoch)
            # listeners/checkpoints run while the async-dispatched device
            # round is still executing — host and device legs overlap
            host_start = _time.perf_counter()
            for lst in listeners:
                lst.on_epoch_watermark_incremented(epoch, carry)
            if mgr is not None and config.checkpoint_interval and \
                    (epoch + 1) % config.checkpoint_interval == 0:
                mgr.save(carry, epoch + 1)
            host_ms = (_time.perf_counter() - host_start) * 1000.0
            if tracing.tracer.enabled:
                # per-shard time-to-ready while the async round drains:
                # per-replica epoch attribution + straggler detection
                # (ml.shard readyMs{shard=,device=}, ml.skew events)
                from flink_ml_tpu.observability import meshstats
                meshstats.observe_shard_ready(carry, span=sp,
                                              phase="epoch")
            # guarded host sync point (device round complete): a wedged
            # inter-process reduce becomes WorkerLost past the deadline
            stop = bool(elastic.guard_fetch(stop, what="round stop bit"))
            # per-round wall time split: hostMs = listener/checkpoint
            # work, deviceMs = dispatch + residual device wait after the
            # overlap — the profiling surface the reference lacks (its
            # per-round wrapper only feeds Flink's LatencyStats)
            total_ms = (_time.perf_counter() - round_start) * 1000.0
            sp.set_attribute("host_ms", round(host_ms, 3))
            sp.set_attribute("device_ms", round(total_ms - host_ms, 3))
            if tracing.tracer.enabled:
                # per-epoch HBM watermark, taken after the stop-bit sync
                # so the round's allocations are visible (no-op on CPU)
                compilestats.sample_memory("epoch", span=sp)
            prev_ctx = tracing.context_of(sp)
        iter_group.gauge("lastRoundMs", total_ms)
        iter_group.gauge("lastRoundHostMs", host_ms)
        iter_group.gauge("lastRoundDeviceMs", total_ms - host_ms)
        # last-value gauges keep only the final epoch; the labeled
        # histograms keep the whole fit's distribution
        iter_group.histogram("epochMs", labels=mode_label).observe(
            total_ms)
        iter_group.histogram("epochHostMs", labels=mode_label).observe(
            host_ms)
        iter_group.histogram("epochDeviceMs", labels=mode_label).observe(
            total_ms - host_ms)
        iter_group.counter("rounds")
        if stop:
            break
    for lst in listeners:
        lst.on_iteration_terminated(carry)
    if mgr is not None:
        # The iteration completed: discard its checkpoints so a later run
        # against the same manager starts fresh instead of restoring this
        # run's final state (the reference likewise discards checkpoints on
        # job success). A crash skips this, leaving the resume point intact.
        mgr.clear()
    return carry


class Iterations:
    """Namespace parity with iteration/Iterations.java."""

    iterate_bounded_streams_until_termination = staticmethod(iterate_bounded)

    @staticmethod
    def iterate_unbounded_streams(*args, **kwargs):
        from flink_ml_tpu.iteration.streaming import iterate_unbounded
        return iterate_unbounded(*args, **kwargs)
