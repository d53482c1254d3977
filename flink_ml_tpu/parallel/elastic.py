"""Elastic multi-process training: survive worker loss mid-fit.

The reference's iteration runtime rides Flink's supervised dataflow — a
lost TaskManager is rescheduled and the loop resumes from the aligned
checkpoint. Our multi-process runtime (distributed.py) is SPMD lockstep
instead: one process stops answering and every survivor wedges inside
the next inter-process psum, forever. This module turns that hang into
a supervised, observable recovery in three pieces:

**Detection** — a configurable collective deadline
(``FLINK_ML_TPU_COLLECTIVE_TIMEOUT_S``): the iteration drivers guard
their boundary fetches through :func:`guard_fetch`, which runs the
device sync on a watchdog thread and, past the deadline, consults the
per-process heartbeat files (beaten at every epoch boundary via
:func:`on_boundary`) to NAME the dead/stale process index — raising a
retryable :class:`~flink_ml_tpu.resilience.policy.WorkerLost` instead
of hanging. A timeout cannot fire *inside* XLA; the boundary fetch is
the host seam where the wedged reduce leg becomes observable.

**Recovery** — :func:`run_elastic` drives a launched fit through
``resilience.run_supervised``: when a child dies (SIGKILL, crash) or
hangs (the launcher's per-child liveness grace kills it), the parent
classifies the loss, shrinks the world by one, and relaunches the
survivors as a smaller ``(dcn, data)`` mesh. The children resume from
the newest v2 checkpoint manifest with the 1/N-sharded optimizer/
accumulator slices re-placed across the CHANGED N
(``CheckpointManager(repad_dim0=True)`` — the dim-0 pad of
``update_sharding.padded_len`` is inert zeros, so trim/re-extend is
lossless). Below ``min_processes`` the elastic budget is exhausted:
:class:`~flink_ml_tpu.resilience.policy.RestartsExhausted` with
``budget="elastic"``.

**Partial participation** — straggler-aware rounds (JiT Aggregation,
arXiv:2208.09740): :class:`RoundParticipation` turns the PR 6 skew
*detector* into an *actuator*. A shard whose previous-round readiness
exceeded ``FLINK_ML_TPU_ROUND_DEADLINE_MS`` is dropped for the round —
its ``include`` flag goes to 0 and ``collective.renormalized_sum``
rescales the survivors so the update stays unbiased — with staleness
bookkeeping that force-readmits a shard after ``max_staleness``
consecutive drops (a stale contribution must eventually rejoin, and a
round never drops every shard). SPMD lockstep means inclusion is
decided on HOST from the *previous* round's timings: a shard cannot
skip a psum it is already compiled into.

Telemetry rides ``ml.elastic``: ``participation{round=}`` gauges,
``droppedContributions{shard=}`` counters, ``workerLost`` /
``relaunches`` counters, and ``elastic.worker-lost`` /
``elastic.relaunch`` / ``elastic.participation`` trace events (surfaced
in the ``mltrace summary`` timeline). :func:`provenance` feeds
``elasticEvents`` / ``participationMin`` onto benchmark rows through
``update_sharding.provenance``.
"""

from __future__ import annotations

import functools
import os
import signal
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from flink_ml_tpu.resilience import faults
from flink_ml_tpu.resilience.policy import (
    RestartsExhausted,
    RetryPolicy,
    WorkerLost,
)

#: env mapping (docs/resilience.md "Elastic recovery")
COLLECTIVE_TIMEOUT_ENV = "FLINK_ML_TPU_COLLECTIVE_TIMEOUT_S"
ROUND_DEADLINE_ENV = "FLINK_ML_TPU_ROUND_DEADLINE_MS"
HEARTBEAT_DIR_ENV = "FLINK_ML_TPU_HEARTBEAT_DIR"
#: which process index the worker-loss/worker-hang chaos sites strike
#: (every process advances the SAME deterministic schedule; only the
#: victim acts, so exactly one worker dies per scheduled fault)
CHAOS_VICTIM_ENV = "FLINK_ML_TPU_CHAOS_VICTIM"
#: how long a worker-hang victim stalls (default: well past the
#: collective deadline, which is the point)
CHAOS_HANG_ENV = "FLINK_ML_TPU_CHAOS_HANG_S"
#: set by run_elastic in every child: 0-based attempt index, so a
#: worker can tell a first launch from a post-loss relaunch (the smoke
#: disarms its one scheduled kill on relaunch)
ATTEMPT_ENV = "FLINK_ML_TPU_ELASTIC_ATTEMPT"

__all__ = [
    "COLLECTIVE_TIMEOUT_ENV", "ROUND_DEADLINE_ENV", "HEARTBEAT_DIR_ENV",
    "CHAOS_VICTIM_ENV", "CHAOS_HANG_ENV", "ATTEMPT_ENV",
    "collective_timeout_s",
    "round_deadline_ms", "beat", "stale_processes", "on_boundary",
    "guard_fetch", "wait_with_deadline", "RoundParticipation",
    "repad_or_rescale",
    "ElasticCheckpointManager", "run_elastic", "provenance",
    "reset_stats",
]

#: fit-scoped elastic provenance (reset per benchmark run like
#: update_sharding.reset_last): how many elastic events fired and the
#: worst round-participation fraction observed
_STATS = {"workerLost": 0, "relaunches": 0, "droppedRounds": 0,
          "participationMin": 1.0}


def _elastic_group():
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics

    return metrics.group(ML_GROUP, "elastic")


def _event(name: str, **attrs) -> None:
    """Best-effort trace event — telemetry must never sink the
    recovery path it describes."""
    try:
        from flink_ml_tpu.observability import tracing

        tracing.tracer.event(name, **attrs)
    except Exception:
        pass


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "%s=%r is not a number; ignoring it", name, raw)
        return None


def collective_timeout_s() -> Optional[float]:
    """The collective deadline in seconds, or None (detection off —
    the default: a deadline only makes sense where a peer can die)."""
    val = _env_float(COLLECTIVE_TIMEOUT_ENV)
    return val if val and val > 0 else None


def round_deadline_ms() -> Optional[float]:
    """The straggler round deadline in ms, or None (actuator off)."""
    val = _env_float(ROUND_DEADLINE_ENV)
    return val if val and val > 0 else None


# -- heartbeats ---------------------------------------------------------------
# ONE liveness mechanism: a "heartbeat" IS a fleet beacon
# (observability/fleet.py) written into the heartbeat dir — the elastic
# watchdog and ``mltrace fleet`` read the same stamp, so they can never
# disagree about who is dead. The beacon carries role/epoch/windowed
# metric slices on top of the liveness stamp for free.

def _hb_dir() -> Optional[str]:
    return os.environ.get(HEARTBEAT_DIR_ENV) or None


def beat(epoch: Optional[int] = None) -> None:
    """Write this process's liveness stamp — a fleet beacon (atomic
    replace, so a reader never sees a torn beat). No-op without
    ``FLINK_ML_TPU_HEARTBEAT_DIR`` — the launcher/driver opts a fit
    in. Never raises: an unwritable heartbeat dir must not kill the
    fit (the fleet writer swallows write failures)."""
    base = _hb_dir()
    if not base:
        return
    try:
        from flink_ml_tpu.observability import fleet

        fleet.write_beacon(base, role="trainer", epoch=epoch)
    except Exception:
        pass  # liveness reporting must never sink the fit it reports on


def stale_processes(timeout_s: float,
                    num_processes: Optional[int] = None) -> List[int]:
    """Process indices whose beacon stamp is missing or older than
    ``timeout_s`` — the detection side's evidence for WHO died. Empty
    when no heartbeat dir is configured (the caller then reports an
    unidentified loss)."""
    base = _hb_dir()
    if not base:
        return []
    from flink_ml_tpu.observability import fleet
    from flink_ml_tpu.parallel import distributed

    n = num_processes if num_processes is not None \
        else distributed.process_count()
    return fleet.stale_member_indices(base, timeout_s,
                                      num_processes=int(n))


# -- detection ----------------------------------------------------------------

def wait_with_deadline(tree, timeout_s: float, what: str = "collective"):
    """Block until ``tree``'s device computation is ready, but give up
    after ``timeout_s``: the sync runs on a watchdog thread, and a
    deadline miss consults the heartbeats to name the dead peer and
    raises :class:`WorkerLost` (retryable — run_supervised and the
    elastic driver both know what to do with it). The host-side seam
    where a wedged inter-process psum becomes a failure instead of a
    hang — a timeout cannot fire inside XLA itself."""
    import jax

    box = {}
    done = threading.Event()

    def work():
        try:
            jax.block_until_ready(tree)
        except Exception as e:  # noqa: BLE001 — re-raised on the caller
            box["err"] = e
        done.set()

    t = threading.Thread(target=work, daemon=True,
                         name="flink-ml-tpu-collective-watchdog")
    t.start()
    if not done.wait(timeout_s):
        stale = stale_processes(timeout_s)
        idx = stale[0] if stale else None
        _STATS["workerLost"] += 1
        _elastic_group().counter("collectiveTimeouts")
        _event("elastic.worker-lost", process=idx, timeout_s=timeout_s,
               what=what)
        raise WorkerLost(idx, f"{what} deadline exceeded",
                         timeout_s=timeout_s)
    if "err" in box:
        raise box["err"]
    return tree


def guard_fetch(tree, what: str = "boundary"):
    """The iteration drivers' hook: :func:`wait_with_deadline` when the
    collective deadline is armed, a free no-op otherwise (the default —
    single-process fits never pay a watchdog thread)."""
    timeout = collective_timeout_s()
    if timeout is None:
        return tree
    return wait_with_deadline(tree, timeout, what=what)


# -- the boundary hook (heartbeat + chaos probe) ------------------------------

def _chaos_probe(epoch: Optional[int]) -> None:
    """The worker-loss / worker-hang injection sites. Gated on a
    multi-process runtime: a SIGKILL site must never fire inside a
    single-process pytest run, however the ambient chaos env is armed.
    Every process advances the same deterministic schedule (counts stay
    in sync); only the configured victim acts."""
    from flink_ml_tpu.parallel import distributed

    if distributed.process_count() <= 1:
        return
    victim_raw = os.environ.get(CHAOS_VICTIM_ENV, "").strip()
    victim = int(victim_raw) if victim_raw.lstrip("-").isdigit() else 1
    if faults.decide("worker-loss"):
        if distributed.process_index() == victim:
            _event("elastic.chaos", site="worker-loss", epoch=epoch,
                   process=victim)
            os.kill(os.getpid(), signal.SIGKILL)
    if faults.decide("worker-hang"):
        if distributed.process_index() == victim:
            hang = _env_float(CHAOS_HANG_ENV)
            if hang is None:
                hang = 3.0 * (collective_timeout_s() or 40.0)
            _event("elastic.chaos", site="worker-hang", epoch=epoch,
                   process=victim, hang_s=hang)
            time.sleep(hang)


def on_boundary(epoch: Optional[int] = None) -> None:
    """Called by the iteration drivers at every epoch/segment boundary:
    beat the heartbeat (liveness evidence for the survivors' detection)
    and consult the worker-loss/worker-hang chaos sites. Near-free when
    neither heartbeats nor chaos are armed."""
    beat(epoch)
    if faults.active_plan() is not None:
        _chaos_probe(epoch)


# -- partial participation (the straggler actuator) ---------------------------

class RoundParticipation:
    """Straggler-aware round inclusion with JiT-style staleness
    bookkeeping (arXiv:2208.09740).

    Per round, :meth:`decide` returns the per-shard 0/1 include vector
    for ``collective.renormalized_sum``, computed from the PREVIOUS
    round's readiness timings (fed through :meth:`observe` — e.g. the
    per-shard ``ml.shard readyMs`` series of
    ``meshstats.observe_shard_ready``): a shard slower than the round
    deadline is dropped for one round, its staleness counter ticks up,
    and after ``max_staleness`` consecutive drops it is force-included
    (its next contribution is stale but the alternative is divergence
    of the dropped shard's slice — JiT's bounded-staleness rule). A
    round never drops every shard.
    """

    def __init__(self, n_shards: int, deadline_ms: Optional[float] = None,
                 max_staleness: int = 3):
        self.n_shards = int(n_shards)
        self.deadline_ms = (deadline_ms if deadline_ms is not None
                            else round_deadline_ms())
        self.max_staleness = int(max_staleness)
        self._last_ms: Optional[np.ndarray] = None
        self._staleness = np.zeros(self.n_shards, dtype=np.int64)
        self.rounds = 0
        self.dropped_rounds = 0
        self.participation_min = 1.0

    def observe(self, ready_ms: Sequence[float]) -> None:
        """Record this round's per-shard readiness (ms); informs the
        NEXT round's inclusion. Also feeds the PR 6 skew detector so
        ``ml.skew`` events keep firing alongside the actuation."""
        vals = np.asarray(list(ready_ms), dtype=np.float64)
        if vals.shape != (self.n_shards,):
            raise ValueError(
                f"expected {self.n_shards} per-shard timings, got "
                f"shape {vals.shape}")
        self._last_ms = vals
        try:
            from flink_ml_tpu.observability import meshstats

            meshstats.detect_skew("elastic-round", vals.tolist())
        except Exception:
            pass

    def decide(self, round_idx: int) -> np.ndarray:
        """The include vector (float 0/1, length ``n_shards``) for this
        round. Records ``ml.elastic participation{round=}`` and
        ``droppedContributions{shard=}``; an ``elastic.participation``
        event fires whenever a shard is dropped."""
        include = np.ones(self.n_shards, dtype=np.float64)
        if self.deadline_ms and self._last_ms is not None:
            slow = self._last_ms > float(self.deadline_ms)
            drop = slow & (self._staleness < self.max_staleness)
            if drop.all():  # never drop every shard
                drop[:] = False
            include[drop] = 0.0
            self._staleness = np.where(drop, self._staleness + 1, 0)
        else:
            self._staleness[:] = 0
        self.rounds += 1
        participating = int(include.sum())
        fraction = participating / self.n_shards
        self.participation_min = min(self.participation_min, fraction)
        _STATS["participationMin"] = min(_STATS["participationMin"],
                                         fraction)
        group = _elastic_group()
        group.gauge("participation", participating,
                    labels={"round": str(int(round_idx))})
        if participating < self.n_shards:
            self.dropped_rounds += 1
            _STATS["droppedRounds"] += 1
            dropped = [int(k) for k in np.flatnonzero(include == 0.0)]
            for k in dropped:
                group.counter("droppedContributions",
                              labels={"shard": str(k)})
            _event("elastic.participation", round=int(round_idx),
                   participating=participating, dropped=dropped,
                   staleness_max=int(self._staleness.max()))
        return include


# -- multi-process checkpointing (the re-placement seam) ----------------------

def repad_or_rescale(host: np.ndarray, target_shape) -> np.ndarray:
    """One carry leaf re-placed across a CHANGED shard count.

    Float state (coefficients, the 1/N-sharded adam m/v slices) carries
    the update-sharding layer's inert dim-0 zero padding: trim or
    re-extend it (``checkpoint.repad_leading``). A 1-D INTEGER leaf
    whose entries are all equal is per-shard round-robin progress (the
    fit carry's ``offsets``: every shard advances ``global_batch /
    n_shards`` per round over ``n / n_shards`` local rows, so the
    entries stay uniform): its global position is ``offset * n_old``,
    and the new world's per-shard offset is that divided by ``n_new`` —
    exact whenever ``n_new`` divides the global progress, else the
    checkpoint genuinely does not fit the new world
    (:class:`~flink_ml_tpu.iteration.checkpoint.CorruptCheckpoint`,
    routed to quarantine + fallback). Non-uniform integer progress
    cannot be re-placed either way."""
    from flink_ml_tpu.iteration.checkpoint import (CorruptCheckpoint,
                                                   repad_leading)

    target_shape = tuple(int(s) for s in target_shape)
    if (tuple(host.shape) == target_shape or host.ndim != 1
            or len(target_shape) != 1
            or not np.issubdtype(host.dtype, np.integer)):
        return repad_leading(host, target_shape)
    n_old, n_new = host.shape[0], target_shape[0]
    if n_old == 0 or n_new == 0:
        return repad_leading(host, target_shape)
    if np.any(host != host[0]):
        raise CorruptCheckpoint(
            f"per-shard integer progress {host.tolist()} is not uniform"
            f" — cannot re-place {n_old} shards onto {n_new}")
    progress = int(host[0]) * n_old
    if progress % n_new:
        raise CorruptCheckpoint(
            f"per-shard progress {int(host[0])} x {n_old} shards does "
            f"not divide across {n_new} shards")
    return np.full(target_shape, progress // n_new, dtype=host.dtype)


@functools.lru_cache(maxsize=8)
def _gather_program(sharding):
    """One compiled identity per target sharding (a fresh jit per leaf
    would defeat the compile cache)."""
    import jax

    return jax.jit(lambda a: a, out_shardings=sharding)


def _replicated_host(leaves) -> List[np.ndarray]:
    """Every leaf as a full host array on every process: leaves whose
    sharding spans processes are first gathered to a fully-replicated
    layout by one compiled identity program (SPMD — every process must
    reach this call in lockstep, which the symmetric iteration drivers
    guarantee), then fetched. Already-addressable leaves fetch as-is."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = []
    for x in leaves:
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            x = _gather_program(
                NamedSharding(x.sharding.mesh, P()))(x)
        out.append(np.asarray(x))
    return out


def _import_checkpoint_base():
    from flink_ml_tpu.iteration.checkpoint import CheckpointManager

    return CheckpointManager


class ElasticCheckpointManager(_import_checkpoint_base()):
    """Checkpointing that survives a mesh spanning processes AND a
    changed process count.

    Save: the carry's 1/N-sharded leaves (the sharded optimizer
    moments) are all-gathered to host (:func:`_replicated_host` — SPMD,
    so every process calls ``save`` in lockstep exactly as the
    iteration drivers do) and only process 0 writes the shared
    directory — one v2 manifest, no write races.

    Restore: every process reads the same manifest; leaves re-pad
    across a CHANGED N (``repad_dim0`` defaults ON here — the
    update-sharding pad is inert zeros) and land on the template's
    cross-process shardings via ``jax.make_array_from_callback``, each
    process placing only its addressable shards: the 1/N slice
    re-placement of the elastic recovery path."""

    def __init__(self, base_dir: str, keep: int = 2,
                 repad_dim0: bool = True):
        super().__init__(base_dir, keep=keep, repad_dim0=repad_dim0)

    def save(self, carry, epoch: int, extras=None) -> str:
        import jax

        from flink_ml_tpu.parallel import distributed

        leaves, treedef = jax.tree_util.tree_flatten(carry)
        host = _replicated_host(leaves)
        if distributed.process_index() != 0:
            return os.path.join(self.base_dir, f"ckpt-{epoch:08d}")
        host_carry = jax.tree_util.tree_unflatten(treedef, host)
        return super().save(host_carry, epoch, extras=extras)

    def clear(self) -> None:
        from flink_ml_tpu.parallel import distributed

        if distributed.process_index() == 0:
            super().clear()

    def _place(self, host, tmpl):
        import jax

        sharding = getattr(tmpl, "sharding", None)
        if sharding is None:
            return host
        if isinstance(tmpl, jax.Array) and not tmpl.is_fully_addressable:
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])
        return jax.device_put(host, sharding)

    def _repad(self, host, target_shape):
        return repad_or_rescale(host, target_shape)


# -- recovery (the supervised relaunch driver) --------------------------------

def run_elastic(argv: Sequence[str], num_processes: int,
                min_processes: int = 1, local_devices: int = 1,
                env: Optional[dict] = None, timeout: float = 900.0,
                policy: Optional[RetryPolicy] = None, listeners=(),
                heartbeat_dir: Optional[str] = None,
                child_grace_s: float = 30.0) -> List[dict]:
    """Drive a launched multi-process fit elastically: on worker loss,
    rebuild smaller and resume.

    Each attempt launches ``argv`` as the current world size through
    ``distributed.launch`` (with its per-child liveness grace). A child
    that dies by signal — SIGKILLed, crashed, or grace-killed after
    wedging its siblings — is a :class:`WorkerLost`: the world shrinks
    by one and ``run_supervised`` retries (backoff, restart/deadline
    budgets, ``on_restart`` listener events all apply), so the next
    attempt's children build an (N-1)-process ``(dcn, data)`` mesh and
    re-place their 1/N slices from the shared checkpoint dir (the
    worker script owns that — see scripts/elastic_smoke.py). A nonzero
    exit WITHOUT a signal death is an ordinary retryable failure at the
    SAME world size (the fleet is intact; the fit merely failed).

    Shrinking below ``min_processes`` exhausts the *elastic* budget:
    :class:`RestartsExhausted` with ``budget="elastic"`` — as does the
    supervisor's own restart budget running out while losses continue.

    Returns the successful attempt's launch records.
    """
    from flink_ml_tpu.parallel import distributed
    from flink_ml_tpu.resilience.supervisor import run_supervised

    if int(num_processes) < int(min_processes):
        raise ValueError(
            f"num_processes={num_processes} < min_processes="
            f"{min_processes}")
    state = {"n": int(num_processes), "attempt": 0}

    def attempt() -> List[dict]:
        n = state["n"]
        attempt_idx = state["attempt"]
        state["attempt"] += 1
        child_env = dict(env or {})
        child_env[ATTEMPT_ENV] = str(attempt_idx)
        if heartbeat_dir:
            # per-attempt subdir: a dead process's stale beat must not
            # haunt the next, smaller world's liveness evidence
            child_env[HEARTBEAT_DIR_ENV] = os.path.join(
                heartbeat_dir, f"attempt-{attempt_idx}")
        group = _elastic_group()
        group.gauge("processCount", n)
        if attempt_idx:
            _STATS["relaunches"] += 1
            group.counter("relaunches")
            _event("elastic.relaunch", attempt=attempt_idx, processes=n)
        records = distributed.launch(
            argv, n, local_devices=local_devices, env=child_env,
            timeout=timeout, child_grace_s=child_grace_s)
        failed = [r for r in records if r["returncode"] != 0]
        if not failed:
            return records
        signaled = [r for r in failed if r["returncode"] < 0]
        if not signaled:
            # the fleet is intact — this is a fit failure, not a lost
            # worker: retry at the same N under the ordinary failure classes
            raise RuntimeError(
                f"elastic attempt {attempt_idx}: {len(failed)} of {n} "
                f"processes failed (rc={failed[0]['returncode']}) "
                f"without a signal death:\n{failed[0]['stderr'][-2000:]}")
        # the FIRST signal death is the victim; later ones are the
        # launcher's grace-kills of its wedged siblings
        first = min(signaled,
                    key=lambda r: (r.get("exitOrder") is None,
                                   r.get("exitOrder") or 0))
        dead = first["process"]
        _STATS["workerLost"] += 1
        group.counter("workerLost")
        _event("elastic.worker-lost", process=dead,
               returncode=first["returncode"], processes=n)
        if n - 1 < int(min_processes):
            raise RestartsExhausted(
                attempt_idx,
                f"elastic budget exhausted: lost process {dead} at "
                f"world size {n}, floor is min_processes="
                f"{min_processes}", budget="elastic")
        state["n"] = n - 1
        raise WorkerLost(
            dead, f"child killed by signal "
            f"{-first['returncode']} at world size {n}")

    try:
        return run_supervised(attempt, policy=policy, listeners=listeners)
    except RestartsExhausted as e:
        if e.budget == "elastic":
            raise
        # the supervisor's budget ran dry while losses continued: that
        # IS the elastic budget from the caller's point of view
        raise RestartsExhausted(
            e.attempts, "elastic restart budget exhausted",
            budget="elastic") from e


# -- provenance ---------------------------------------------------------------

def provenance() -> dict:
    """The elastic fields benchmark rows carry beside ``processCount``
    (spread through ``update_sharding.provenance``): ``elasticEvents``
    (worker losses + relaunches + straggler-dropped rounds this run)
    and ``participationMin`` (the worst round-participation fraction;
    1.0 when no round dropped a shard)."""
    events = (_STATS["workerLost"] + _STATS["relaunches"]
              + _STATS["droppedRounds"])
    return {"elasticEvents": int(events),
            "participationMin": float(_STATS["participationMin"])}


def reset_stats() -> None:
    """Zero the fit-scoped elastic stats (benchmark runner calls this
    beside ``update_sharding.reset_last`` so provenance is per-run)."""
    _STATS.update(workerLost=0, relaunches=0, droppedRounds=0,
                  participationMin=1.0)
