"""Shared model plumbing: Table↔device extraction and linear-model bases.

Ref parity: the per-algorithm boilerplate of flink-ml-lib (XxxParams +
Xxx + XxxModel + XxxModelData + serializers) collapses here into two base
classes; concrete algorithms declare a loss and a prediction rule.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.linalg.vectors import DenseVector
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import sparse_window
from flink_ml_tpu.ops.losses import LossFunc
from flink_ml_tpu.ops.optimizer import SGD, SGDParams
from flink_ml_tpu.params.shared import (
    HasElasticNet,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasOptimizerMethod,
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasTol,
    HasWeightCol,
)
from flink_ml_tpu.utils import io as rw


def extract_labeled_points(stage, table: Table
                           ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Table → (features (n,d) dense, CSR or a device sparse column,
    labels (n,), weights (n,)|None) — the reference's
    Table→LabeledPointWithWeight map (LogisticRegression.java:72-99). A
    SparseVector column stays CSR and a device sparse column stays where it
    lies, so wide hashed features (2^18 dims) never densify (ref
    BLAS.java:78)."""
    from flink_ml_tpu.linalg import sparse

    def scalar_col(name):
        # a device-resident scalar column (device datagen / upstream device
        # stage) keeps its residency; the trainer reshards it in place
        col = table.column(name)
        return col if isinstance(col, jax.Array) else table.scalars(name)

    x = sparse.features_matrix(table, stage.features_col, device_sparse=True)
    y = scalar_col(stage.label_col)
    w = None
    if stage.weight_col is not None and stage.weight_col in table:
        w = scalar_col(stage.weight_col)
    return x, y, w


@jax.jit
def _dots(features, coeffs):
    return features @ coeffs


@jax.jit
def _sparse_dots(ids, values, coeffs):
    # a row's entries' coefficient times value, summed: the fit's margins
    return jnp.sum(sparse_window.gather(coeffs, ids) * values, axis=1)


def prediction_dtype(xp):
    """Label-column dtype per prediction path: float64 on the host (sparse)
    path — the reference's Java double — float32 on device (TPU-native
    width, docs/deviations.md dtype policy). Owned here, next to
    :func:`predict_dots`, so every linear/online model agrees."""
    return np.float64 if xp is np else jnp.float32


def predict_dots(x, coefficients):
    """Margins for a feature batch: dense input runs on device through the
    columnar path (sharded rows, replicated coefficients — the ⚙ predict
    tier of SURVEY §2.1; ref LogisticRegressionModelServable.java:106 dot),
    returning a device array so derived prediction columns stay resident;
    a device sparse column runs on device as the fit's margins do (a
    gather of the coefficients and a sum over a row's entries), rows where
    they lie; CSR input stays a host matvec (ref BLAS.hDot sparse path).

    Returns (dots, xp) where xp is the array namespace (jnp or np) the
    caller should derive its prediction columns with."""
    from flink_ml_tpu.linalg import sparse

    if sparse.is_csr(x):
        return np.asarray(x @ np.asarray(coefficients, np.float64)), np
    if sparse.is_device_sparse_column(x):
        return _sparse_dots(x.ids, x.values,
                            np.asarray(coefficients, np.float32)), jnp
    from flink_ml_tpu.ops import columnar

    xd = columnar.to_device(x)
    cd = columnar.replicated(np.asarray(coefficients, np.float32))
    return _dots(xd, cd), jnp


class LinearModelParams(HasFeaturesCol, HasPredictionCol):
    pass


class LinearTrainParams(LinearModelParams, HasLabelCol, HasWeightCol,
                        HasMaxIter, HasReg, HasElasticNet, HasLearningRate,
                        HasGlobalBatchSize, HasTol, HasRawPredictionCol,
                        HasOptimizerMethod):
    pass


class LinearModelBase(Model, LinearTrainParams):
    """A fitted linear model: coefficient vector + a prediction rule."""

    def __init__(self, coefficients: Optional[np.ndarray] = None, **kwargs):
        super().__init__(**kwargs)
        self.coefficients = (None if coefficients is None
                             else np.asarray(coefficients, np.float64))

    # -- prediction rule, overridden per algorithm ---------------------------
    def _predict_columns(self, dots, xp) -> dict:
        """Derive the prediction columns from the margins using the ``xp``
        namespace (jnp on the device path, np on the sparse host path) so
        dense outputs stay device-resident columns in the result Table."""
        raise NotImplementedError

    def transform(self, table: Table) -> Tuple[Table]:
        if self.coefficients is None:
            raise ValueError(f"{type(self).__name__} has no model data")
        from flink_ml_tpu.linalg import sparse
        x = sparse.features_matrix(table, self.features_col,
                                   device_sparse=True)
        dots, xp = predict_dots(x, self.coefficients)
        return (table.with_columns(**self._predict_columns(dots, xp)),)

    # -- model data as a Table (ref: XxxModelData POJO + table) -------------
    def set_model_data(self, model_data: Table):
        col = model_data.column("coefficient")
        self.coefficients = col[0].to_array() if col.dtype == object \
            else np.asarray(col[0])
        return self

    def get_model_data(self) -> Tuple[Table]:
        return (Table.from_columns(
            coefficient=[DenseVector(self.coefficients)]),)

    # -- persistence ---------------------------------------------------------
    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {"coefficient": self.coefficients})

    def _load_extra(self, path: str, meta: dict) -> None:
        self.coefficients = rw.load_model_arrays(path, "model")["coefficient"]


class IterationRuntimeMixin:
    """Runtime (non-Param) iteration knobs shared by iterative estimators:
    host-mode rounds, listeners and mid-fit checkpoint/resume. Ref: in the
    reference these are Flink runtime settings (checkpoint interval, restart
    strategy) configured on the environment, not stage params — hence not
    part of the JSON param map here either."""

    _iteration_config = None
    _iteration_listeners = ()
    _retry_policy = None

    def set_iteration_config(self, config, listeners=()):
        self._iteration_config = config
        self._iteration_listeners = tuple(listeners)
        return self

    def set_retry_policy(self, policy):
        """Run ``.fit`` under resilience supervision: retryable failures
        (worker timeouts, injected faults, I/O errors) restart the fit,
        which resumes from the newest checkpoint that passes integrity
        validation when a CheckpointManager is configured. Ref: Flink's
        per-job RestartStrategies — a runtime setting, not a Param."""
        self._retry_policy = policy
        return self

    def _supervised_fit(self, fit_once):
        """Route a zero-arg fit thunk through run_supervised when a
        retry policy is set; plain call otherwise (zero overhead)."""
        if self._retry_policy is None:
            return fit_once()
        from flink_ml_tpu.resilience.supervisor import run_supervised
        cfg = self._iteration_config
        mgr = cfg.checkpoint_manager if cfg is not None else None
        return run_supervised(fit_once, mgr=mgr,
                              policy=self._retry_policy,
                              listeners=self._iteration_listeners)


def _baseline_rows(x):
    """The row-capped training sample a baseline capture reads
    (``drift.sample_rows``); a device sparse column's comes to the host as
    CSR, a few thousand rows of it, so no capture copies the column or
    densifies it."""
    from flink_ml_tpu.linalg import sparse
    from flink_ml_tpu.observability import drift

    xs = drift.sample_rows(x)
    return xs.to_csr() if sparse.is_device_sparse_column(xs) else xs


def _capture_drift_baseline(estimator, model, x, coeffs) -> None:
    """The traced-fit drift seam (observability/drift.py): sketch a
    row-capped sample of the training inputs per feature plus the final
    model's predictions on that sample, attaching the
    :class:`~flink_ml_tpu.observability.drift.DriftBaseline` to the
    fitted model — ``serving.publish_model`` ships it beside the
    checkpoint manifest so live traffic is compared against the
    distribution THIS model was trained on. Armed like the rich health
    tier (trace dir or ``FLINK_ML_TPU_DRIFT``); a capture failure is
    logged and never fails the fit."""
    try:
        from flink_ml_tpu.observability import drift

        if not drift.capture_armed():
            return
        xs = _baseline_rows(x)
        dots, xp = predict_dots(xs, coeffs)
        pred = model._predict_columns(dots, xp).get(
            model.prediction_col)
        drift.capture_fit_baseline(model, type(estimator).__name__,
                                   features=xs, predictions=pred)
    except Exception:  # noqa: BLE001 — telemetry must not sink the fit
        import logging

        logging.getLogger(__name__).warning(
            "drift baseline capture failed", exc_info=True)


def _capture_quality_baseline(estimator, model, x, y, coeffs) -> None:
    """The traced-fit quality seam (observability/evaluation.py):
    sketch the final model's positive-class scores on the same
    row-capped training sample against the matching labels, attaching
    the :class:`~flink_ml_tpu.observability.evaluation.QualityBaseline`
    to the fitted model — the live-AUC anchor ``publish_model`` ships
    as ``quality-baseline.json``. Non-binary labels (regression fits)
    sketch nothing, so no baseline attaches. Armed like drift capture;
    a failure is logged and never fails the fit."""
    try:
        from flink_ml_tpu.observability import evaluation

        if not evaluation.capture_armed():
            return
        xs = _baseline_rows(x)
        ys = np.asarray(y).ravel()[:xs.shape[0]]
        dots, xp = predict_dots(xs, coeffs)
        cols = model._predict_columns(dots, xp)
        raw = cols.get(getattr(model, "raw_prediction_col", None))
        scores = evaluation.positive_scores(
            raw_values=(None if raw is None else np.asarray(raw)),
            predictions=cols.get(model.prediction_col))
        if scores is not None:
            evaluation.capture_fit_baseline(
                model, type(estimator).__name__, scores=scores,
                labels=ys)
    except Exception:  # noqa: BLE001 — telemetry must not sink the fit
        import logging

        logging.getLogger(__name__).warning(
            "quality baseline capture failed", exc_info=True)


class LinearEstimatorBase(Estimator, LinearTrainParams,
                          IterationRuntimeMixin):
    """Shared SGD fit path (ref: LogisticRegression.fit:60 → SGD.optimize)."""

    #: subclass hooks
    loss: LossFunc = None
    model_class = None

    def fit(self, table: Table):
        return self._supervised_fit(lambda: self._fit_once(table))

    def _fit_once(self, table: Table):
        from flink_ml_tpu.linalg import sparse
        with tracer.span("fit.extract"):
            x, y, w = extract_labeled_points(self, table)
        params = SGDParams(
            learning_rate=self.learning_rate,
            global_batch_size=self.global_batch_size,
            max_iter=self.max_iter, tol=self.tol, reg=self.reg,
            elastic_net=self.elastic_net,
            # stateful update rules (HasOptimizerMethod): momentum/adam
            # moment state rides the fit carry, sharded 1/N per replica
            # under FLINK_ML_TPU_UPDATE_SHARDING
            method=self.optimizer, momentum=self.momentum,
            beta1=self.beta1, beta2=self.beta2, eps=self.epsilon)
        init = np.zeros(x.shape[1], np.float32)
        sgd = SGD(params)
        # the estimator class name labels this fit's model-health
        # telemetry (ml.health series + divergence events,
        # observability/health.py) across every SGD execution path
        if sparse.is_csr(x):
            coeffs, _ = sgd.optimize_csr(
                self.loss, init, x, y, w,
                config=self._iteration_config,
                listeners=self._iteration_listeners,
                tag=type(self).__name__)
        elif sparse.is_device_sparse_column(x):
            coeffs, _ = sgd.optimize_sparse(
                self.loss, init, x, y, w,
                config=self._iteration_config,
                listeners=self._iteration_listeners,
                tag=type(self).__name__)
        else:
            coeffs, _ = sgd.optimize(
                self.loss, init, x, y, w,
                config=self._iteration_config,
                listeners=self._iteration_listeners,
                tag=type(self).__name__)
        # benchmark provenance (runner.py executionPath): which SGD
        # program shape actually trained this model
        self.last_execution_path = getattr(sgd, "last_execution_path",
                                           None)
        with tracer.span("fit.model"):
            model = self.model_class(coefficients=coeffs)
            model = self.copy_params_to(model)
            _capture_drift_baseline(self, model, x, coeffs)
            _capture_quality_baseline(self, model, x, y, coeffs)
        return model


def prediction_output(table: Table, name: str, values: np.ndarray) -> Table:
    return table.with_column(name, values)


def raw_prediction_vectors(pairs: np.ndarray) -> np.ndarray:
    """(n, k) float array → object column of DenseVectors for rawPrediction.

    Row-oriented consumers (the servable path) use this off-ramp; the batch
    transform path keeps rawPrediction as a columnar (n, k) vector column
    instead — same logical schema (a vector per row), device-resident."""
    return as_dense_vector_column(pairs)
