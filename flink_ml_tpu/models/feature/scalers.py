"""Scalers: StandardScaler, MinMaxScaler, MaxAbsScaler, RobustScaler.

Ref parity: flink-ml-lib feature/{standardscaler,minmaxscaler,maxabsscaler,
robustscaler}/ — fit computes per-dimension statistics over the input vector
column (the reference's two-phase reduce), the model applies an affine map.
Stats and transforms are single fused XLA reductions/elementwise maps.

- StandardScaler: mean/unbiased-std (StandardScaler.java:119-131:
  std = sqrt((Σx²−n·mean²)/(n−1)), 0 when n==1); withMean default false,
  withStd default true.
- MinMaxScaler: rescale to [min,max] (defaults 0,1); a constant dimension
  maps to (min+max)/2 (ref MinMaxScalerModel semantics).
- MaxAbsScaler: divide by max |x| per dimension.
- RobustScaler: center/scale by median and quantile range [lower,upper]
  (defaults 0.25/0.75) using the ε-approximate quantile summary semantics
  (relativeError param); withCentering default false, withScaling true.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax.numpy as jnp

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import columnar
from flink_ml_tpu.params.param import BooleanParam, FloatParam, ParamValidators
from flink_ml_tpu.params.shared import (
    HasInputCol,
    HasOutputCol,
    HasRelativeError,
)
from flink_ml_tpu.utils import io as rw


class _VectorStatModelBase(Model, HasInputCol, HasOutputCol):
    """A model holding named per-dimension stat arrays + an affine apply.

    The apply runs on device through the shared columnar path
    (ops/columnar.py): ``_kernel`` is a class-level pure jnp function, stats
    are replicated operands, boolean params are static jit arguments. The
    output stays a (sharded) device array inside the Table so chained
    stages skip the host round-trip. Fit-side statistics stay float64 host
    numpy (docs/deviations.md: dtype policy).
    """

    STAT_NAMES: Tuple[str, ...] = ()

    def __init__(self, **kwargs):
        stats = {name: kwargs.pop(name, None) for name in self.STAT_NAMES}
        super().__init__(**kwargs)
        for name, val in stats.items():
            setattr(self, name, None if val is None else np.asarray(val, np.float64))

    @staticmethod
    def _kernel(x, *args):
        raise NotImplementedError

    def _kernel_args(self) -> Tuple[tuple, tuple]:
        """→ (replicated stat operands, static jit args)."""
        raise NotImplementedError

    def _sparse_supported(self) -> bool:
        """Whether the CONFIGURED op is sparsity-preserving — consulted
        before any CSR conversion so unsupported cases (e.g. mean
        centering maps implicit zeros off zero) pay no wasted pass."""
        return False

    def _sparse_apply(self, m):
        """O(nnz) CSR transform (only called when _sparse_supported());
        must return a NEW scipy CSR — never alias the input's data."""
        raise NotImplementedError

    def transform(self, table: Table) -> Tuple[Table]:
        if getattr(self, self.STAT_NAMES[0]) is None:
            raise ValueError(f"{type(self).__name__} has no model data")
        from flink_ml_tpu.linalg import sparse as sp_mod

        col = table.column(self.input_col)
        if self._sparse_supported() and sp_mod.is_sparse_column(col):
            out_m = self._sparse_apply(sp_mod.column_to_csr(col))
            return (table.with_column(
                self.output_col, sp_mod.CsrVectorColumn(out_m)),)
        x = columnar.input_vectors(table, self.input_col)
        consts, static = self._kernel_args()
        out = columnar.apply(type(self)._kernel, x, consts, static)
        return (table.with_column(self.output_col, out),)

    def set_model_data(self, model_data: Table):
        for name in self.STAT_NAMES:
            setattr(self, name, model_data.vectors(name, np.float64)[0])
        return self

    def get_model_data(self) -> Tuple[Table]:
        return (Table.from_columns(**{
            name: np.asarray(getattr(self, name), np.float64)[None, :]
            for name in self.STAT_NAMES}),)

    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {
            name: getattr(self, name) for name in self.STAT_NAMES})

    def _load_extra(self, path: str, meta: dict) -> None:
        arrays = rw.load_model_arrays(path, "model")
        for name in self.STAT_NAMES:
            setattr(self, name, arrays[name])


# ---------------------------------------------------------------------------
# StandardScaler
# ---------------------------------------------------------------------------

class StandardScalerParams(HasInputCol, HasOutputCol):
    WITH_MEAN = BooleanParam(
        "withMean", "Whether centers the data with mean before scaling.",
        False)
    WITH_STD = BooleanParam(
        "withStd", "Whether scales the data with standard deviation.", True)


class StandardScalerModel(_VectorStatModelBase, StandardScalerParams):
    STAT_NAMES = ("mean", "std")

    @staticmethod
    def _kernel(x, mean, std, with_mean, with_std):
        if with_mean:
            x = x - mean
        if with_std:
            x = x / jnp.where(std > 0, std, 1.0)
        return x

    def _kernel_args(self):
        return ((self.mean, self.std),
                (bool(self.with_mean), bool(self.with_std)))

    def _sparse_supported(self) -> bool:
        return not self.with_mean  # centering densifies by necessity

    def _sparse_apply(self, m):
        import scipy.sparse as sp

        if self.with_std:
            std = np.where(self.std > 0, self.std, 1.0)
            data = m.data / std[m.indices]
        else:
            data = m.data.copy()  # never alias the input column's values
        return sp.csr_matrix((data, m.indices, m.indptr), shape=m.shape)


def _mean_varsum_kernel(x):
    """(2, d): per-dim mean and centered sum of squares — the two-pass
    form of the reference's Σx²−n·mean² (identical in exact arithmetic,
    stable in float32)."""
    mean = jnp.mean(x, axis=0)
    return jnp.stack([mean, jnp.sum((x - mean[None, :]) ** 2, axis=0)])


def mean_and_std(table, input_col):
    """Per-dimension (mean, unbiased std) — ON device for device-resident
    columns (no table off-ramp); the float64 host branch keeps the
    reference's exact Σx²−n·mean² formula (StandardScaler.java:119-131).
    Sparse columns reduce over stored values, O(nnz), never densified."""
    from flink_ml_tpu.linalg import sparse as sp_mod

    col = table.column(input_col)
    if sp_mod.is_sparse_column(col):
        m = sp_mod.column_to_csr(col)
        n = m.shape[0]
        mean = np.asarray(m.sum(axis=0)).ravel() / max(n, 1)
        if n > 1:
            sq = np.asarray(m.multiply(m).sum(axis=0)).ravel()
            std = np.sqrt(np.maximum((sq - n * mean * mean) / (n - 1), 0.0))
        else:
            std = np.zeros_like(mean)
        return mean, std
    x, xp = columnar.fit_vectors(table, input_col)
    n = x.shape[0]
    if xp is jnp:
        stats = np.asarray(columnar.apply(_mean_varsum_kernel, x),
                           np.float64)
        mean, varsum = stats[0], stats[1]
        std = (np.sqrt(varsum / (n - 1)) if n > 1
               else np.zeros_like(mean))
        return mean, std
    mean = x.mean(axis=0)
    if n > 1:
        # ref formula: sqrt((Σx² − n·mean²)/(n−1))
        std = np.sqrt(np.maximum(
            ((x * x).sum(axis=0) - n * mean * mean) / (n - 1), 0.0))
    else:
        std = np.zeros_like(mean)
    return mean, std


class StandardScaler(Estimator, StandardScalerParams):
    def fit(self, table: Table) -> StandardScalerModel:
        mean, std = mean_and_std(table, self.input_col)
        model = StandardScalerModel(mean=mean, std=std)
        return self.copy_params_to(model)


# ---------------------------------------------------------------------------
# MinMaxScaler
# ---------------------------------------------------------------------------

class MinMaxScalerParams(HasInputCol, HasOutputCol):
    MIN = FloatParam("min", "Lower bound of the output feature range.", 0.0)
    MAX = FloatParam("max", "Upper bound of the output feature range.", 1.0)


class MinMaxScalerModel(_VectorStatModelBase, MinMaxScalerParams):
    STAT_NAMES = ("data_min", "data_max")

    @staticmethod
    def _kernel(x, lo, hi, out_min, out_max):
        span = hi - lo
        return jnp.where(
            span > 0,
            (x - lo) / jnp.where(span > 0, span, 1.0) * (out_max - out_min)
            + out_min,
            (out_min + out_max) / 2.0)  # constant dims map to midpoint

    def _kernel_args(self):
        return ((self.data_min, self.data_max,
                 np.float32(self.min), np.float32(self.max)), ())


def _minmax_kernel(x):
    return jnp.stack([jnp.min(x, axis=0), jnp.max(x, axis=0)])


class MinMaxScaler(Estimator, MinMaxScalerParams):
    def fit(self, table: Table) -> MinMaxScalerModel:
        from flink_ml_tpu.linalg import sparse as sp_mod

        col = table.column(self.input_col)
        if sp_mod.is_sparse_column(col):
            # scipy's sparse min/max include implicit zeros, O(nnz)
            m = sp_mod.column_to_csr(col)
            model = MinMaxScalerModel(
                data_min=np.asarray(m.min(axis=0).todense()).ravel(),
                data_max=np.asarray(m.max(axis=0).todense()).ravel())
            return self.copy_params_to(model)
        x, xp = columnar.fit_vectors(table, self.input_col)
        if xp is jnp:
            lo_hi = np.asarray(columnar.apply(_minmax_kernel, x),
                               np.float64)
            lo, hi = lo_hi[0], lo_hi[1]
        else:
            lo, hi = x.min(axis=0), x.max(axis=0)
        model = MinMaxScalerModel(data_min=lo, data_max=hi)
        return self.copy_params_to(model)


# ---------------------------------------------------------------------------
# MaxAbsScaler
# ---------------------------------------------------------------------------

class MaxAbsScalerParams(HasInputCol, HasOutputCol):
    pass


class MaxAbsScalerModel(_VectorStatModelBase, MaxAbsScalerParams):
    STAT_NAMES = ("max_abs",)

    @staticmethod
    def _kernel(x, max_abs):
        return x / jnp.where(max_abs > 0, max_abs, 1.0)

    def _kernel_args(self):
        return ((self.max_abs,), ())

    def _sparse_supported(self) -> bool:
        return True

    def _sparse_apply(self, m):
        import scipy.sparse as sp

        scale = np.where(self.max_abs > 0, self.max_abs, 1.0)
        return sp.csr_matrix((m.data / scale[m.indices], m.indices,
                              m.indptr), shape=m.shape)


def _maxabs_kernel(x):
    return jnp.max(jnp.abs(x), axis=0)


class MaxAbsScaler(Estimator, MaxAbsScalerParams):
    def fit(self, table: Table) -> MaxAbsScalerModel:
        from flink_ml_tpu.linalg import sparse as sp_mod

        col = table.column(self.input_col)
        if sp_mod.is_sparse_column(col):
            # |x| >= 0, so the stored-value max IS the column max, O(nnz)
            m = sp_mod.column_to_csr(col)
            max_abs = np.asarray(abs(m).max(axis=0).todense()).ravel()
            return self.copy_params_to(MaxAbsScalerModel(max_abs=max_abs))
        x, xp = columnar.fit_vectors(table, self.input_col)
        max_abs = (np.asarray(columnar.apply(_maxabs_kernel, x), np.float64)
                   if xp is jnp else np.abs(x).max(axis=0))
        model = MaxAbsScalerModel(max_abs=max_abs)
        return self.copy_params_to(model)


# ---------------------------------------------------------------------------
# RobustScaler
# ---------------------------------------------------------------------------

class RobustScalerParams(HasInputCol, HasOutputCol, HasRelativeError):
    LOWER = FloatParam("lower", "Lower quantile to calculate quantile range.",
                       0.25, ParamValidators.in_range(0, 1, False, False))
    UPPER = FloatParam("upper", "Upper quantile to calculate quantile range.",
                       0.75, ParamValidators.in_range(0, 1, False, False))
    WITH_CENTERING = BooleanParam(
        "withCentering", "Whether to center the data with median before "
        "scaling.", False)
    WITH_SCALING = BooleanParam(
        "withScaling", "Whether to scale the data to quantile range.", True)


class RobustScalerModel(_VectorStatModelBase, RobustScalerParams):
    STAT_NAMES = ("medians", "ranges")

    @staticmethod
    def _kernel(x, medians, ranges, with_centering, with_scaling):
        if with_centering:
            x = x - medians
        if with_scaling:
            x = x / jnp.where(ranges > 0, ranges, 1.0)
        return x

    def _kernel_args(self):
        return ((self.medians, self.ranges),
                (bool(self.with_centering), bool(self.with_scaling)))


class RobustScaler(Estimator, RobustScalerParams):
    """``medians`` and ``ranges = upper - lower`` per dimension, each an
    element of the column: the one of 0-based rank ``floor(q (n - 1))``
    (docs/deviations.md). A device-resident column is selected from where
    it lies, exactly (``ops/quantile.select_on_device``: path
    ``select-device``): a first guess from a sample, counting passes that
    narrow a proven bracket around every wanted element, and, once a
    bracket holds a few dozen elements, one pass that takes them out and
    picks the wanted one (four reads of a smooth 12M-row table); a host
    column by ``np.quantile`` (``host-quantiles``), to the same model."""

    def fit(self, table: Table) -> RobustScalerModel:
        x, xp = columnar.fit_vectors(table, self.input_col)
        probs = [self.lower, 0.5, self.upper]
        if xp is jnp:
            from flink_ml_tpu.ops.quantile import select_on_device
            from flink_ml_tpu.parallel import update_sharding as _upd
            from flink_ml_tpu.parallel.mesh import (
                data_shard_count, default_mesh)

            qs, _ = select_on_device(x, probs)
            # (the fit's state is its answers: the brackets that led to
            # them are the programs')
            _upd.record_state_bytes("RobustScaler", [qs],
                                    data_shard_count(default_mesh()), False)
            self.last_execution_path = "select-device"
        else:
            from flink_ml_tpu.ops.quantile import approx_quantiles

            # (no program to enqueue: the host's one read of the column is
            # the blocking part, and ``select.fetch`` is what names passes)
            with tracer.span("select.fetch", path="host-quantiles",
                             rows=x.shape[0], d=x.shape[1], probs=probs,
                             passes=1):
                qs = approx_quantiles(x, probs,
                                      relative_error=self.relative_error)
            self.last_execution_path = "host-quantiles"
        with tracer.span("fit.model"):
            lo, med, hi = np.asarray(qs, np.float64)
            model = RobustScalerModel(medians=med, ranges=hi - lo)
            return self.copy_params_to(model)
