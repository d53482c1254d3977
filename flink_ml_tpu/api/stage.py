"""Stage / AlgoOperator / Transformer / Model / Estimator.

Ref parity: flink-ml-core/.../ml/api/*.java — the Spark-ML-style hierarchy:

    Stage (savable, has params)
      └─ AlgoOperator.transform(*tables) -> (table, ...)
           └─ Transformer (one-in-one-out semantics)
                └─ Model (.set_model_data / .get_model_data)
      └─ Estimator.fit(*tables) -> Model

Tables here are host columnar batches (flink_ml_tpu.common.table.Table); the
compute inside concrete stages is jitted XLA.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Tuple

from flink_ml_tpu.common.table import Table
from flink_ml_tpu.params.param import WithParams
from flink_ml_tpu.utils import io as rw


#: the stage classes whose first fit of this process has begun: the one
#: look-up a warm fit pays for the cold spans
_FIRST_FITS: set = set()


def _first_fit(wrapper, stage, args, kwargs):
    """The first ``fit`` of a stage class in this process, under the cold
    root ``first_fit`` (observability/tracing.py: recorded whether or not
    anybody is looking): the imports and program builds that fall inside
    it are its children, and what jax traced, lowered and compiled
    meanwhile lands in its attributes through ``compilestats``' listener,
    subscribed here and not at package import."""
    from flink_ml_tpu.observability import compilestats, tracing

    cls = type(stage)
    _FIRST_FITS.add(cls)
    compilestats.watch_cold()
    with tracing.tracer.cold_span("first_fit", kind="first_fit",
                                  stage=cls.__name__):
        return wrapper(stage, *args, **kwargs)


def _profiled(method, kind: str):
    """Wrap a fit/transform implementation with the observability hooks
    (SURVEY.md §5: run visibility is the reference's gap we close).
    Two independent, composing arms — ``FLINK_ML_TPU_PROFILE_DIR``
    records a jax.profiler trace (device/XLA internals), and the tracer
    opens the root span (host-side structure: fit→optimize→launch/fetch
    nesting, docs/observability.md) whenever it is active: a trace dir,
    the live endpoint's ring, or any running ``jax.profiler`` capture,
    whose ``.xplane.pb`` then holds the program's spans on the device
    trace's clock. Two env checks and the profiler's flag of overhead
    when all are off. Traces nest safely: a Pipeline's stages inside the
    pipeline trace record wall-time gauges only.

    A fit traced into a trace DIR also arms compile telemetry: the
    jax.monitoring subscription (compile counts/durations land in
    ``ml.compile``), a recompile-storm window scoped to the outermost
    stage call, and a device-memory watermark sampled as the ROOT span
    closes (no-op on CPU) — so peak HBM per fit is on the root span
    itself. Under a capture or the ring alone none of these run: such a
    fit is perturbed by its spans only.

    Whatever is armed, a class's first ``fit`` of the process runs under
    the cold root ``first_fit`` (:func:`_first_fit`); every later one
    pays one set look-up for it and nothing else."""
    is_fit = kind == "fit"

    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        if is_fit and type(self) not in _FIRST_FITS:
            return _first_fit(wrapper, self, args, kwargs)
        from flink_ml_tpu.common.metrics import PROFILE_DIR_ENV, profile
        from flink_ml_tpu.observability import (
            compilestats,
            server,
            tracing,
        )

        # env-armed live endpoint (FLINK_ML_TPU_METRICS_PORT): one dict
        # lookup when unarmed, and arming it flips tracer.active so
        # spans reach the /spans/recent ring even without a trace dir
        server.maybe_start()
        trace_dir = os.environ.get(PROFILE_DIR_ENV)
        tracer = tracing.tracer
        if not trace_dir and not tracer.active:
            return method(self, *args, **kwargs)
        region = f"{type(self).__name__}.{kind}"
        # a telemetry-armed run is exactly the run whose daemon threads
        # (metrics server, watchers) must not die silently
        from flink_ml_tpu.common.locks import install_thread_excepthook

        install_thread_excepthook()
        if not trace_dir and not tracer.enabled:
            # the ring or a profiler capture alone: the root span and
            # nothing heavier, so the fit is perturbed by its spans only
            with tracer.span(region, kind=kind, stage=type(self).__name__):
                return method(self, *args, **kwargs)
        try:
            with contextlib.ExitStack() as stack:
                sp = None
                if tracer.active:
                    if tracer.enabled:
                        compilestats.install()
                    sp = stack.enter_context(tracer.span(
                        region, kind=kind, stage=type(self).__name__))
                    if tracer.enabled:
                        stack.enter_context(compilestats.fit_window())
                        # FLINK_ML_TPU_PROFILE_CAPTURE=1 arms a device
                        # profile of the next traced fit (one-shot;
                        # observability/profiling.py) — a no-op context
                        # otherwise
                        from flink_ml_tpu.observability import profiling

                        stack.enter_context(
                            profiling.maybe_profile_fit(region))
                if trace_dir:
                    stack.enter_context(profile(
                        os.path.join(trace_dir, region), name=region))
                result = method(self, *args, **kwargs)
                if (tracer.enabled and sp is not None
                        and sp.parent_id is None):
                    compilestats.sample_memory(f"root:{kind}", span=sp)
                return result
        finally:
            # an outermost stage (not one nested in a Pipeline) closing
            # its root span snapshots the registry beside the spans
            tracing.maybe_dump_root_metrics()

    wrapper._profiled = True
    return wrapper


class Stage(WithParams):
    """A node with params that can be saved/loaded (ref: api/Stage.java)."""

    def save(self, path: str) -> None:
        rw.save_metadata(self, path)
        self._save_extra(path)

    @classmethod
    def load(cls, path: str):
        stage, meta = rw.load_stage_params(path)
        if not isinstance(stage, cls):
            raise TypeError(f"saved stage {type(stage).__name__} is not a {cls.__name__}")
        stage._load_extra(path, meta)
        return stage

    # hooks for subclasses with model data / nested stages
    def _save_extra(self, path: str) -> None:
        pass

    def _load_extra(self, path: str, meta: dict) -> None:
        pass


class AlgoOperator(Stage):
    """A Stage computing output tables from input tables (ref: AlgoOperator.java)."""

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        impl = cls.__dict__.get("transform")
        if impl is not None and not getattr(impl, "_profiled", False):
            cls.transform = _profiled(impl, "transform")

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        raise NotImplementedError


class Transformer(AlgoOperator):
    """Marker for record-wise transforms (ref: Transformer.java)."""


class Model(Transformer):
    """A Transformer with model data (ref: Model.java)."""

    def set_model_data(self, *model_data: Table):
        raise NotImplementedError(f"{type(self).__name__} has no model data")

    def get_model_data(self) -> Tuple[Table, ...]:
        raise NotImplementedError(f"{type(self).__name__} has no model data")


class Estimator(Stage):
    """fit(*tables) -> Model (ref: Estimator.java)."""

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        impl = cls.__dict__.get("fit")
        if impl is not None and not getattr(impl, "_profiled", False):
            cls.fit = _profiled(impl, "fit")

    def fit(self, *inputs: Table) -> Model:
        raise NotImplementedError
