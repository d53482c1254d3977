"""Stamps of what a process does once, before its tracer exists.

A cold start is mostly imports, and the tracer cannot time its own
precursors: ``observability/tracing.py`` imports ``jax.profiler``, so by
the time :data:`tracing.tracer` is built ``jax`` (and most of this
package) has been imported already. This module is the stdlib-only half of
the cold spans (docs/observability.md "Cold spans"): it stamps a region
with a name, its start and end on ``perf_counter_ns``, the wall clock and
its nesting, in the span record's own format, and the tracer adopts the
finished stamps into ``tracer.cold`` when it is built — one format, one
place to read. Once the tracer exists :func:`importing` is
``tracer.cold_span`` and nothing else.

Every time here is taken from ONE pair of clock reads at this module's
import (the first lines the package runs): ``ts_us`` is that wall-clock
anchor plus monotonic time elapsed, and a record's end is computed before
its duration, so a child never lies outside its parent by a rounding.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time

_WALL0_NS = time.time_ns()
_MONO0_NS = time.perf_counter_ns()

#: the launcher's serialized trace context (tracing.TRACE_PARENT_ENV):
#: a stamp with no open stamp above it joins that trace, as a root span
#: of the tracer would
_TRACE_PARENT_ENV = "FLINK_ML_TPU_TRACE_PARENT"
_TRACING = "flink_ml_tpu.observability.tracing"

_ids = itertools.count(1)
_open: list = []      # stamps begun and not ended, outermost first
_pending: list = []   # finished records the tracer has not adopted yet


def wall_us(mono_ns: int) -> int:
    """Epoch microseconds of a ``perf_counter_ns`` reading."""
    return (_WALL0_NS + mono_ns - _MONO0_NS) // 1000


def _new_id() -> str:
    # "s" is no hex digit: never one of the tracer's own pid-counter ids
    return f"{os.getpid():x}-s{next(_ids):x}"


def _tracer():
    return getattr(sys.modules.get(_TRACING), "tracer", None)


def innermost_open():
    """``(trace_id, span_id)`` of the innermost stamp still open on this
    thread, or None: the parent of a cold span the tracer opens while an
    import that began before it is still running."""
    tid = threading.get_ident()
    for stamp in reversed(_open):
        if stamp["tid"] == tid:
            return stamp["trace"], stamp["id"]
    return None


def take_pending() -> list:
    """The finished stamps, handed over once (the tracer's adoption)."""
    taken = list(_pending)
    del _pending[:len(taken)]
    return taken


class importing:
    """``with importing("jax"): import jax`` — the region as the cold span
    ``import:jax``: through the tracer where there is one, as a stamp
    before. Only ever at a site that runs once a process (a module's top
    level)."""

    __slots__ = ("_name", "_span", "_stamp")

    def __init__(self, module: str):
        self._name = f"import:{module}"
        self._span = self._stamp = None

    def __enter__(self):
        tracer = _tracer()
        if tracer is not None:
            self._span = tracer.cold_span(self._name, kind="import")
            return self._span.__enter__()
        parent = innermost_open()
        if parent is None:
            header = os.environ.get(_TRACE_PARENT_ENV, "")
            trace_id, _, span_id = header.partition(":")
            parent = ((trace_id.strip(), span_id.strip() or None)
                      if trace_id.strip() else (_new_id(), None))
        t0 = time.perf_counter_ns()
        self._stamp = {"type": "span", "name": self._name,
                       "trace": parent[0], "id": _new_id(),
                       "parent": parent[1], "ts_us": wall_us(t0),
                       "dur_us": None, "pid": os.getpid(),
                       "tid": threading.get_ident(),
                       "attrs": {"kind": "import"}, "events": []}
        _open.append(self._stamp)
        return self._stamp

    def __exit__(self, exc_type, exc, tb):
        if self._span is not None:
            return self._span.__exit__(exc_type, exc, tb)
        stamp = self._stamp
        stamp["dur_us"] = wall_us(time.perf_counter_ns()) - stamp["ts_us"]
        if exc_type is not None:
            stamp["attrs"]["error"] = exc_type.__name__
        _open.remove(stamp)
        tracer = _tracer()
        if tracer is not None:   # built while this import ran
            tracer.adopt_cold([stamp])
        else:
            _pending.append(stamp)
        return False
