"""Compile & device telemetry: XLA compile visibility + HBM/FLOP accounting.

Two quantities govern TPU performance that the span tracer cannot see:
how often and how long XLA compiles (and why it recompiles), and how
hard the compiled programs drive the device (FLOPs, bytes, HBM
watermarks). This module makes both first-class registry metrics and
tracer events, so they land in the same trace-dir artifacts as spans and
epoch histograms (docs/observability.md) and survive unattended runs:

- **Compile telemetry.** :func:`install` subscribes to the
  ``jax.monitoring`` duration/event channels when this jax build exposes
  them, recording per-phase compile-time histograms
  (``ml.compile phaseMs{phase="backend_compile"|...}``) and channel
  counters. The monitoring channels carry no function identity, so
  :func:`instrumented_jit` / :func:`aot_compile` add the per-function
  view: compile counts and compile-time histograms labeled by function
  name, plus a **recompile-storm detector** — one function compiled for
  more than N distinct abstract signatures within one fit window fires a
  ``compile.storm`` event and counter, the dynamic complement of
  jaxlint's static recompile-hazard rule.

- **Device telemetry.** :func:`capture_cost` records
  ``compiled.cost_analysis()`` FLOPs / bytes-accessed on first compile
  (``ml.device programFlops{fn=...}``); :func:`sample_memory` samples
  ``device.memory_stats()`` watermarks at epoch boundaries and root-span
  close. On CPU ``memory_stats()`` returns ``None`` — sampling degrades
  silently to a no-op (and remembers, so a traced CPU fit pays one probe
  total, not one per epoch). It also never *initializes* a backend: a
  pure-host fit must not claim the chip just for telemetry.

``mltrace diff`` (observability/diff.py) joins these artifacts with span
durations to report compile-count deltas and gate perf regressions from
artifacts alone.
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys
import threading
import time
from typing import Dict, Optional, Set

from flink_ml_tpu.common.locks import make_lock
from flink_ml_tpu.common.metrics import ML_GROUP, MetricsRegistry, metrics
from flink_ml_tpu.observability import tracing

#: registry subgroup names: ml.compile / ml.device
COMPILE_GROUP = "compile"
DEVICE_GROUP = "device"

#: env var: distinct abstract signatures one function may compile for
#: within one fit window before the recompile-storm detector fires
STORM_ENV = "FLINK_ML_TPU_COMPILE_STORM_N"
DEFAULT_STORM_THRESHOLD = 8

#: compile-time histogram buckets (ms) — compiles are slower-tailed than
#: the latency-shaped DEFAULT_BUCKETS (a cold TPU compile can take minutes)
COMPILE_BUCKETS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                   1000.0, 2500.0, 5000.0, 15000.0, 60000.0, 300000.0)


def storm_threshold() -> int:
    try:
        return int(os.environ.get(STORM_ENV, DEFAULT_STORM_THRESHOLD))
    except ValueError:
        return DEFAULT_STORM_THRESHOLD


def _channel_tail(channel: str) -> str:
    """``/jax/core/compile/backend_compile_duration`` → ``backend_compile``."""
    tail = channel.rstrip("/").rsplit("/", 1)[-1]
    if tail.endswith("_duration"):
        tail = tail[: -len("_duration")]
    return tail


#: what jax did while a cold span was open (docs/observability.md "Cold
#: spans"): monitoring phase -> (seconds attribute, count attribute) on
#: the innermost cold span of the thread the channel fired on.
#: ``cache_load_s`` lies inside ``compile_s`` (jax times a compile request
#: around the persistent cache's look-up): beside it, never added to it
_COLD_PHASES = {
    "jaxpr_trace": ("trace_s", "traces"),
    "jaxpr_to_mlir_module": ("lower_s", None),
    "backend_compile": ("compile_s", "compiles"),
    "cache_retrieval_time_sec": ("cache_load_s", None),
}
_COLD_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _backend_ready() -> bool:
    """True when jax is imported AND a backend is already live — the
    guard that keeps telemetry from *initializing* a backend (a chip
    belongs to one process at a time; a pure-host process that only
    records telemetry must not take it)."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge
    except ImportError:
        return True  # cannot tell on this jax: assume the caller knows
    backends = getattr(xla_bridge, "_backends", None)
    if backends is None:
        return True
    return bool(backends)


class CompileStats:
    """Process-wide compile/device telemetry state (see module doc).

    Thread-safe; survives the host-pool fork like the tracer does — the
    monitoring listeners registered pre-fork keep firing in the child
    and write into the child's re-seeded registry, which ships its
    snapshot back to the driver (common/hostpool.py)."""

    def __init__(self, registry: MetricsRegistry = metrics):
        self._registry = registry
        self._lock = make_lock("observability.compilestats")
        self._installed = False
        self._enabled = False
        self._sigs: Dict[str, Set] = {}
        self._window_base: Dict[str, int] = {}
        self._window_depth = 0
        self._storm_fired: Set[str] = set()
        self._memory_unavailable = False
        # per thread: the (start, seconds) regions the cold listener has
        # counted, outermost last (see _note_cold)
        self._cold_tls = threading.local()

    # -- jax.monitoring subscription -----------------------------------------
    def install(self) -> bool:
        """Subscribe to the jax.monitoring compile channels (idempotent —
        every traced fit calls this) and record them in the registry.
        Returns True when the channels are available and subscribed;
        False on jax builds without them (the per-function
        instrumentation still works there)."""
        with self._lock:
            self._enabled = True
            return self._subscribe()

    def watch_cold(self) -> bool:
        """Subscribe the same ONE listener without arming the registry:
        what the channels report while a cold span is open lands in that
        span's attributes (:data:`_COLD_PHASES`) and nowhere else. Called
        as a process's first ``first_fit`` opens (api/stage.py); a warm
        fit fires no channel, so the listener then costs nothing."""
        with self._lock:
            return self._subscribe()

    def _subscribe(self) -> bool:
        if self._installed:
            return True
        try:
            from jax import monitoring
            register_dur = monitoring.register_event_duration_secs_listener
            register_ev = monitoring.register_event_listener
        except (ImportError, AttributeError):
            return False
        register_dur(self._on_duration)
        register_ev(self._on_event)
        self._installed = True
        return True

    def uninstall(self) -> None:
        """Disarm the monitoring listeners. jax has no public
        unregister, so they stay subscribed but become no-ops."""
        with self._lock:
            self._enabled = False

    def _on_duration(self, event: str, duration_secs: float, **kw) -> None:
        cold = tracing.tracer.cold_current()
        if cold is not None:
            self._note_cold(cold.attrs, event, float(duration_secs))
        if not self._enabled:  # jaxlint: disable=unguarded-shared-state -- lock-free bool fast path on the per-compile listener; a stale read delays disarm by one event
            return
        try:
            phase = _channel_tail(event)
            ms = float(duration_secs) * 1000.0
            grp = self._registry.group(ML_GROUP, COMPILE_GROUP)
            grp.histogram("phaseMs", buckets=COMPILE_BUCKETS,
                          labels={"phase": phase}).observe(ms)
            grp.counter("phases", labels={"phase": phase})
            if phase == "backend_compile":
                tracing.tracer.event("compile.backend", ms=round(ms, 3))
        except Exception:  # a telemetry listener must never sink a compile
            pass

    def _note_cold(self, attrs: dict, event: str, secs: float) -> None:
        """Add one monitoring duration to the open cold span's attributes
        (:data:`_COLD_PHASES`). jax reports a trace inside a trace (a
        jitted function called while another is traced) once alone and
        once within the outer one's seconds; the listener is called as a
        region ends, so the region is ``[now - secs, now]``, and what
        earlier regions of this thread already cover of it is taken off:
        ``trace_s + lower_s + compile_s`` over a span tree is time that
        passed once, never more than the tree's root."""
        named = _COLD_PHASES.get(_channel_tail(event))
        if named is None:
            return
        if named[0] != "cache_load_s":
            start = time.perf_counter() - secs
            cover = getattr(self._cold_tls, "cover", None)
            if cover is None:
                cover = self._cold_tls.cover = []
            inner = 0.0
            while cover and cover[-1][0] >= start:
                inner += cover.pop()[1]
            cover.append((start, secs))
            secs = max(0.0, secs - inner)
        attrs[named[0]] = attrs.get(named[0], 0.0) + secs
        if named[1] is not None:
            attrs[named[1]] = attrs.get(named[1], 0) + 1

    def _on_event(self, event: str, **kw) -> None:
        if event == _COLD_CACHE_HIT:
            cold = tracing.tracer.cold_current()
            if cold is not None:
                cold.attrs["cache_hits"] = cold.attrs.get("cache_hits", 0) + 1
        if not self._enabled:  # jaxlint: disable=unguarded-shared-state -- lock-free bool fast path on the per-compile listener; a stale read delays disarm by one event
            return
        try:
            channel = event.removeprefix("/jax/")
            self._registry.group(ML_GROUP, COMPILE_GROUP).counter(
                "events", labels={"channel": channel})
        except Exception:
            pass

    # -- per-function compile accounting -------------------------------------
    def note_compile(self, name: str, ms: float, sig=None,
                     approx: bool = False) -> None:
        """Record one compile of ``name``: counter + compile-time
        histogram labeled by function name, a tracer instant event, and
        (when ``sig`` is given) a distinct-signature sample for the
        storm detector. ``approx`` marks a first-call wall time standing
        in for an exact lower+compile measurement."""
        grp = self._registry.group(ML_GROUP, COMPILE_GROUP)
        grp.counter("compiles", labels={"fn": name})
        grp.histogram("compileMs", buckets=COMPILE_BUCKETS,
                      labels={"fn": name}).observe(ms)
        attrs = {"fn": name, "ms": round(ms, 3)}
        if approx:
            attrs["approx"] = "call"
        tracing.tracer.event("compile", **attrs)
        if sig is not None:
            self._note_signature(name, sig)

    def _note_signature(self, name: str, sig) -> None:
        with self._lock:
            seen = self._sigs.setdefault(name, set())
            if sig in seen:
                return
            seen.add(sig)
            distinct = len(seen) - self._window_base.get(name, 0)
            threshold = storm_threshold()
            storm = distinct > threshold and name not in self._storm_fired
            if storm:
                self._storm_fired.add(name)
        if storm:
            self._registry.group(ML_GROUP, COMPILE_GROUP).counter(
                "storms", labels={"fn": name})
            tracing.tracer.event("compile.storm", fn=name,
                                 signatures=distinct, threshold=threshold)

    @contextlib.contextmanager
    def fit_window(self):
        """Scope for the recompile-storm detector: distinct-signature
        counts rebase at the OUTERMOST window (one fit), so a long-lived
        process doesn't accumulate a slow drip of shapes into a false
        storm. With no window open, the window is the process lifetime.
        Re-entrant — nested stages (a Pipeline's members) share the
        outer fit's window."""
        with self._lock:
            self._window_depth += 1
            if self._window_depth == 1:
                self._window_base = {n: len(s)
                                     for n, s in self._sigs.items()}
                self._storm_fired = set()
        try:
            yield self
        finally:
            with self._lock:
                self._window_depth -= 1

    # -- test/embedding hook -------------------------------------------------
    def reset(self) -> None:
        """Forget signature history, fired storms, and the memory-probe
        verdict (tests; embedding across backend changes)."""
        with self._lock:
            self._sigs = {}
            self._window_base = {}
            self._storm_fired = set()
            self._memory_unavailable = False


#: default process-wide telemetry state
compile_stats = CompileStats()


def install() -> bool:
    """Module-level convenience: :meth:`CompileStats.install`."""
    return compile_stats.install()


def watch_cold() -> bool:
    """Module-level convenience: :meth:`CompileStats.watch_cold`."""
    return compile_stats.watch_cold()


def uninstall() -> None:
    compile_stats.uninstall()


def fit_window():
    """Module-level convenience: :meth:`CompileStats.fit_window`."""
    return compile_stats.fit_window()


# -- abstract signatures ------------------------------------------------------
def _sig_leaf(x):
    aval = getattr(x, "aval", None)
    if aval is not None:
        return str(aval)
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    if x is None or isinstance(x, str):
        return ("static", repr(x))
    # python scalars of one type (bools included) share one weak-typed
    # executable under jit — value-sensitive signatures here would pay a
    # duplicate XLA compile per value and report phantom recompiles.
    # type() (not isinstance) keeps bool from collapsing into int.
    if isinstance(x, (bool, int, float, complex)):
        return ("py", type(x).__name__)
    return ("static", repr(x))


def abstract_signature(args, kwargs=None):
    """Hashable abstract signature of a call: tree structure + per-leaf
    (shape, dtype) — two calls with equal signatures hit one compiled
    executable; a new signature means a compile."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    return (str(treedef),) + tuple(_sig_leaf(leaf) for leaf in leaves)


# -- instrumented jit ---------------------------------------------------------
def instrumented_jit(fn=None, *, name: Optional[str] = None,
                     stats: Optional[CompileStats] = None, **jit_kwargs):
    """``jax.jit`` with compile telemetry: per-function compile counts +
    compile-time histograms (``ml.compile compiles/compileMs{fn=...}``),
    :func:`capture_cost` on each compile, tracer instant events, and
    recompile-storm detection.

    Keeps its own signature→executable AOT cache: a new abstract
    signature compiles through ``.lower().compile()`` (timed exactly, so
    the compile never hides inside a first-call wall time); repeat
    signatures dispatch the cached executable directly. Signatures the
    AOT path can't lower fall back to the plain jitted call — the first
    call's wall time (which includes the compile) is recorded instead,
    flagged ``approx="call"`` on the tracer event."""
    if fn is None:
        return functools.partial(instrumented_jit, name=name, stats=stats,
                                 **jit_kwargs)
    import jax

    st = stats or compile_stats
    label = name or getattr(fn, "__name__", None) or "jit"
    jitted = jax.jit(fn, **jit_kwargs)
    cache: Dict = {}
    cache_lock = make_lock("observability.compilestats.aot")

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        sig = abstract_signature(args, kwargs)
        with cache_lock:
            target = cache.get(sig)
        if target is not None:
            return target(*args, **kwargs)
        t0 = time.perf_counter()
        try:
            compiled = jitted.lower(*args, **kwargs).compile()
        except Exception:
            out = jitted(*args, **kwargs)
            st.note_compile(label, (time.perf_counter() - t0) * 1000.0,
                            sig=sig, approx=True)
            with cache_lock:
                cache[sig] = jitted
            return out
        st.note_compile(label, (time.perf_counter() - t0) * 1000.0, sig=sig)
        capture_cost(compiled, label, registry=st._registry)
        try:
            out = compiled(*args, **kwargs)
            target = compiled
        except TypeError:
            # a Compiled from static_argnums takes only the dynamic args;
            # rather than re-split the argument list here, dispatch such
            # signatures through the jitted callable (its C++ cache is
            # warm — .compile() populated it)
            out = jitted(*args, **kwargs)
            target = jitted
        with cache_lock:
            cache[sig] = target
        return out

    wrapper._instrumented_jit = True
    wrapper._jitted = jitted
    return wrapper


def aot_compile(fn, *args, name: Optional[str] = None,
                stats: Optional[CompileStats] = None, **kwargs):
    """Lower+compile ``fn`` for ``args`` now, recording compile time,
    per-function counters, cost analysis and a tracer event; returns the
    ``jax.stages.Compiled`` executable. The shared API for scripts that
    used to hand-time ``.lower().compile()`` (scripts/tpu_profile_*)."""
    import jax

    st = stats or compile_stats
    label = name or getattr(fn, "__name__", None) or "aot"
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    st.note_compile(label, (time.perf_counter() - t0) * 1000.0,
                    sig=abstract_signature(args, kwargs))
    capture_cost(compiled, label, registry=st._registry)
    return compiled


# -- device telemetry ---------------------------------------------------------
def capture_cost(compiled, name: str,
                 registry: MetricsRegistry = metrics) -> Optional[dict]:
    """Record ``compiled.cost_analysis()`` FLOPs / bytes-accessed as
    ``ml.device programFlops/programBytes{fn=...}`` gauges plus a
    ``compile.cost`` tracer event — the per-program FLOP/byte accounting
    that feeds achieved-FLOP/s reporting and sharding decisions. Returns
    ``{'flops', 'bytes'}``, or None when the backend exposes no
    analysis (never raises: telemetry must not sink the compile)."""
    try:
        analysis = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    flops = float(analysis.get("flops", 0.0) or 0.0)
    nbytes = float(analysis.get("bytes accessed", 0.0) or 0.0)
    grp = registry.group(ML_GROUP, DEVICE_GROUP)
    grp.gauge("programFlops", flops, labels={"fn": name})
    grp.gauge("programBytes", nbytes, labels={"fn": name})
    tracing.tracer.event("compile.cost", fn=name, flops=flops, bytes=nbytes)
    return {"flops": flops, "bytes": nbytes}


def sample_memory(site: str, span=None,
                  registry: MetricsRegistry = metrics) -> dict:
    """Sample per-device ``memory_stats()`` watermarks into ``ml.device``
    gauges and (optionally) attributes on ``span``. Returns
    ``{'bytes_in_use', 'peak_bytes_in_use'}`` (host-wide sum / max), or
    ``{}`` where the platform exposes no stats.

    CPU degradation: ``memory_stats()`` returns None there — the first
    empty sample latches :attr:`CompileStats._memory_unavailable` so a
    traced CPU fit pays one probe total, not one per epoch. Never
    initializes a backend (see :func:`_backend_ready`)."""
    st = compile_stats
    if st._memory_unavailable or not _backend_ready():
        return {}
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return {}
    grp = registry.group(ML_GROUP, DEVICE_GROUP)
    in_use = peak = 0
    found = False
    for dev in devices:
        try:
            dev_stats = dev.memory_stats()
        except Exception:
            dev_stats = None
        if not dev_stats:
            continue
        found = True
        dev_in_use = int(dev_stats.get("bytes_in_use", 0))
        dev_peak = int(dev_stats.get("peak_bytes_in_use", dev_in_use))
        in_use += dev_in_use
        peak = max(peak, dev_peak)
        label = {"device": str(getattr(dev, "id", "?"))}
        grp.gauge("hbmBytesInUse", dev_in_use, labels=label)
        grp.gauge("hbmPeakBytes", dev_peak, labels=label)
    if not found:
        st._memory_unavailable = True
        return {}
    grp.gauge("hbmBytesInUseTotal", in_use, labels={"site": site})
    grp.gauge("hbmPeakBytesMax", peak, labels={"site": site})
    if span is not None:
        span.set_attribute("hbm_bytes_in_use", in_use)
        span.set_attribute("hbm_peak_bytes", peak)
    return {"bytes_in_use": in_use, "peak_bytes_in_use": peak}


# -- aggregates for the benchmark split and mltrace diff ----------------------
def compile_totals_split(
        snapshot: Optional[Dict[str, dict]] = None,
        registry: MetricsRegistry = metrics) -> Dict[str, dict]:
    """Compile totals per source: ``{'phase': {count, timeMs},
    'perfn': {count, timeMs}}`` — the monitoring ``backend_compile``
    channel vs the per-function ``compileMs`` series. Kept apart because
    a before/after delta must subtract within ONE source: an
    instrumented compile fires both, compiles outside instrumented
    functions fire only the monitoring channel, and mixing sources
    across a delta can go negative."""
    if snapshot is None:
        snapshot = registry.snapshot()
    gsnap = (snapshot or {}).get(f"{ML_GROUP}.{COMPILE_GROUP}", {})
    phase = {"count": 0, "timeMs": 0.0}
    perfn = {"count": 0, "timeMs": 0.0}
    for key, hist in gsnap.get("histograms", {}).items():
        if key.startswith("phaseMs") and 'phase="backend_compile"' in key:
            phase["count"] += int(hist.get("count", 0))
            phase["timeMs"] += float(hist.get("sum", 0.0))
        elif key.startswith("compileMs"):
            perfn["count"] += int(hist.get("count", 0))
            perfn["timeMs"] += float(hist.get("sum", 0.0))
    return {"phase": phase, "perfn": perfn}


def compile_totals_from_snapshot(snapshot: Optional[Dict[str, dict]]) -> dict:
    """``{'count', 'timeMs'}`` of ALL compile work in one registry
    snapshot. Prefers the monitoring ``backend_compile`` channel (it
    sees every compile); falls back to the per-function ``compileMs``
    series on jax builds without monitoring. The two are never summed —
    an instrumented compile fires both, and double counting would halve
    every 'compile share of wall time' readout. For before/after deltas
    use :func:`compile_totals_split` and subtract within one source."""
    totals = compile_totals_split(snapshot)
    src = totals["phase"] if totals["phase"]["count"] else totals["perfn"]
    return {"count": src["count"], "timeMs": src["timeMs"]}


def compile_totals(registry: MetricsRegistry = metrics) -> dict:
    """Live-registry :func:`compile_totals_from_snapshot`."""
    return compile_totals_from_snapshot(registry.snapshot())
