"""Fault-tolerant execution layer: failure classes + retry policy
(:mod:`policy`), the supervised-fit driver (:mod:`supervisor`) and the
deterministic fault-injection harness (:mod:`faults`).

Ref parity: the reference delegates all of this to Flink's runtime —
RestartStrategies (fixed-delay/failure-rate restarts), checkpoint
integrity via the JobManager, and the IT-case fault injection of
BoundedAllRoundCheckpointITCase's FailingMap. Here the runtime is this
process, so the restart strategy, the recovery path (restore from the
newest checkpoint that validates, see iteration/checkpoint.py) and the
chaos harness live together in one package. docs/resilience.md is the
user guide.
"""

from flink_ml_tpu.resilience.policy import (  # noqa: F401
    RETRYABLE,
    TERMINAL,
    CandidateRejected,
    InjectedFault,
    NonFiniteState,
    RestartsExhausted,
    RetryableFailure,
    RetryPolicy,
    TerminalFailure,
    WorkerLost,
    WorkerTimeout,
)
from flink_ml_tpu.resilience.supervisor import run_supervised  # noqa: F401

__all__ = [
    "RETRYABLE",
    "TERMINAL",
    "CandidateRejected",
    "InjectedFault",
    "NonFiniteState",
    "RestartsExhausted",
    "RetryableFailure",
    "RetryPolicy",
    "TerminalFailure",
    "WorkerLost",
    "WorkerTimeout",
    "run_supervised",
]
