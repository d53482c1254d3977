"""Failure classes and retry/backoff policy.

Classification mirrors the benchmark sweep's exit-code precedent
(scripts/run_benchmark_sweep.py): exit 2 = transient, RETRYABLE (wrappers
re-invoke); exit 3 = a correctness/validation regression, TERMINAL
(retrying cannot help and would burn the whole budget without progress).
The same split applies to in-process failures: infrastructure errors
(a wedged host-pool child, an injected fault, an I/O error) are retried
from the newest valid checkpoint; programming/validation errors
(ValueError, TypeError, ...) propagate immediately.

Ref parity: Flink's RestartStrategies.fixedDelayRestart — the reference
jobs recover through exactly this combination of a bounded restart count,
a fixed/backoff delay and checkpoint restore (SURVEY §5).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Type

RETRYABLE = "retryable"
TERMINAL = "terminal"


class RetryableFailure(Exception):
    """Marker base: failures that a restart from the newest valid
    checkpoint can plausibly cure (transient infra, injected chaos)."""


class TerminalFailure(Exception):
    """Marker base: failures no restart can cure (validation errors,
    exhausted budgets)."""


class WorkerTimeout(RetryableFailure):
    """A host-pool child exceeded its deadline and was SIGKILLed.

    Retryable: a wedged worker is transient infrastructure (the fork may
    have landed on a bad moment — e.g. an inherited lock); the retried
    map re-forks from a clean parent state."""

    def __init__(self, worker_index: int, timeout_s: float,
                 rows: Optional[Tuple[int, int]] = None):
        self.worker_index = worker_index
        self.timeout_s = timeout_s
        self.rows = rows
        span = f" (rows [{rows[0]}, {rows[1]}))" if rows else ""
        super().__init__(
            f"host-pool worker {worker_index}{span} exceeded its "
            f"{timeout_s:g}s deadline and was killed")


class WorkerLost(RetryableFailure):
    """A multi-process training peer stopped participating: its
    heartbeat went stale, it crashed under ``distributed.launch``, or
    the inter-process reduce leg wedged past the collective deadline
    (``FLINK_ML_TPU_COLLECTIVE_TIMEOUT_S``).

    Retryable: the elastic driver (parallel/elastic.py) answers a
    WorkerLost by rebuilding a smaller ``(dcn, data)`` mesh from the
    survivors and re-placing the 1/N-sharded optimizer slices from the
    newest v2 manifest — the restart budget bounds how many losses a
    fit may absorb."""

    def __init__(self, process_index: Optional[int], reason: str = "",
                 timeout_s: Optional[float] = None):
        self.process_index = process_index
        self.timeout_s = timeout_s
        who = (f"process {process_index}" if process_index is not None
               else "an unidentified process")
        tail = f": {reason}" if reason else ""
        after = (f" after {timeout_s:g}s" if timeout_s is not None else "")
        super().__init__(f"worker lost ({who}){after}{tail}")


class InjectedFault(RetryableFailure):
    """Raised by the chaos harness (resilience/faults.py) at an
    instrumented site; always retryable — recovery is the thing under
    test."""

    def __init__(self, site: str, count: int, detail: dict = None):
        self.site = site
        self.count = count
        self.detail = dict(detail or {})
        super().__init__(f"injected fault at {site!r} (call #{count})")


class RestartsExhausted(TerminalFailure):
    """The supervisor ran out of restart budget; the last underlying
    failure rides along as ``__cause__``.  ``budget`` names WHICH bound
    tripped — ``"restart"``/``"deadline"`` from run_supervised, or
    ``"elastic"`` when the elastic driver could not shrink the mesh any
    further (survivor count would fall below ``min_processes``)."""

    def __init__(self, attempts: int, reason: str, budget: str = "restart"):
        self.attempts = attempts
        self.budget = budget
        super().__init__(
            f"gave up after {attempts} restart(s): {reason}")


class NonFiniteState(TerminalFailure):
    """A fit's numeric state (loss or parameters) went NaN/Inf.

    Terminal: SGD-family divergence is deterministic — a restart replays
    the same batch schedule into the same overflow, so retrying burns the
    whole restart budget without progress (the exit-3 class). Raised by
    the model-health layer (observability/health.py) when its non-finite
    sentinel trips; the ``ml.health`` divergence event carries the same
    coordinates into the trace."""

    def __init__(self, algo: str, epoch: Optional[int] = None,
                 detail: str = ""):
        self.algo = algo
        self.epoch = epoch
        where = f" at epoch {epoch}" if epoch is not None else ""
        tail = f" ({detail})" if detail else ""
        super().__init__(
            f"{algo} diverged to a non-finite state{where}{tail}")


class CandidateRejected(TerminalFailure):
    """A candidate model failed the hot-swap health check (serving/
    registry.py): corrupt checkpoint data, non-finite parameters, or a
    probe transform that errored/produced non-finite predictions.

    Terminal: the candidate's data is what it is — re-validating the
    same snapshot reproduces the same rejection, so the registry rolls
    back to the serving version instead of retrying (the exit-3 class,
    same reasoning as :class:`NonFiniteState`). The next *published*
    version is a fresh candidate and is evaluated normally."""

    def __init__(self, model: str, version, reason: str, detail: str = ""):
        self.model = model
        self.version = version
        self.reason = reason
        tail = f": {detail}" if detail else ""
        super().__init__(
            f"candidate {model}@v{version} rejected ({reason}){tail}")


#: failures that indicate a bug or invalid input — retrying replays the
#: same deterministic computation into the same wall (the sweep's exit-3
#: class). NotImplementedError is a RuntimeError subclass, so it must be
#: checked before the retryable RuntimeError rule.
_DEFAULT_TERMINAL: Tuple[Type[BaseException], ...] = (
    TerminalFailure, NotImplementedError, ValueError, TypeError,
    AssertionError, AttributeError, KeyError, IndexError, ZeroDivisionError,
)

#: transient-looking failures (the sweep's exit-2 class): OS/IO errors,
#: runtime errors from the device stack (XlaRuntimeError subclasses
#: RuntimeError) and memory pressure.
_DEFAULT_RETRYABLE: Tuple[Type[BaseException], ...] = (
    OSError, RuntimeError, MemoryError,
)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Restart budget + exponential backoff + failure classification.

    ``classify`` precedence: the policy's explicit ``terminal`` types,
    then its explicit ``retryable`` types, then the marker bases and the
    default classes above. Unrecognized Exception subclasses default to
    RETRYABLE — the sweep's precedent (an unexplained failure is recorded
    and retried, never silently promoted to a verdict).
    """

    #: restarts after the first attempt (0 = fail fast, never retry)
    max_restarts: int = 3
    #: delay before restart i (1-based): backoff_s * multiplier**(i-1),
    #: capped at max_backoff_s
    backoff_s: float = 0.1
    backoff_multiplier: float = 2.0
    max_backoff_s: float = 30.0
    #: total wall budget across all restarts (None = unbounded)
    deadline_s: Optional[float] = None
    #: extra exception types, consulted before the default classes
    retryable: Tuple[Type[BaseException], ...] = ()
    terminal: Tuple[Type[BaseException], ...] = ()

    def __post_init__(self):
        if self.max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")
        if self.backoff_s < 0 or self.max_backoff_s < 0:
            raise ValueError("backoff delays must be >= 0")
        if self.backoff_multiplier < 1.0:
            raise ValueError("backoff_multiplier must be >= 1")

    def classify(self, exc: BaseException) -> str:
        if isinstance(exc, self.terminal):
            return TERMINAL
        if isinstance(exc, self.retryable):
            return RETRYABLE
        # the marker beats the class table: WorkerTimeout et al. stay
        # retryable no matter what else they subclass
        if isinstance(exc, RetryableFailure):
            return RETRYABLE
        if isinstance(exc, _DEFAULT_TERMINAL):
            return TERMINAL
        if isinstance(exc, _DEFAULT_RETRYABLE):
            return RETRYABLE
        return RETRYABLE

    def backoff(self, restart: int) -> float:
        """Delay in seconds before 1-based restart number ``restart``."""
        if restart <= 0:
            return 0.0
        delay = self.backoff_s * self.backoff_multiplier ** (restart - 1)
        return min(delay, self.max_backoff_s)
