"""Online (unbounded-stream) algorithms.

Ref parity:
- OnlineLogisticRegression (classification/logisticregression/
  OnlineLogisticRegression.java:75): FTRL-proximal per global batch —
  per-coordinate gradient g_i = Σ (σ(x·w)−y)·x_i normalized by the
  per-coordinate sample count (the reference's dense-vector branch, which
  ignores the weight column; CalculateLocalGradient:364-388, UpdateModel:
  295-319): σ=(√(n+g²)−√n)/α; z+=g−σw; n+=g²; w_i = 0 if |z_i|≤l1 else
  (sign(z)l1−z)/((β+√n)/α+l2), l1=elasticNet·reg, l2=(1−elasticNet)·reg;
  model version increments per emitted model (CreateLrModelData:235-258).
- OnlineKMeans (clustering/kmeans/OnlineKMeans.java:76): mini-batch
  k-means — weights *= decayFactor (per task: /parallelism; host runtime is
  the 1-task case), for non-empty clusters weight += count, λ=count/weight,
  centroid = (1−λ)·centroid + λ·mean(points) (ModelDataLocalUpdater:
  295-324).
- OnlineStandardScaler (feature/standardscaler/OnlineStandardScaler.java):
  per window, cumulative mean/std over all data seen, emitted as versioned
  model data; the model stamps predictions with modelVersionCol
  (OnlineStandardScalerModel.java:202-210 metric gauges ≙ version/timestamp
  tracking here).

The unbounded runtime is flink_ml_tpu.iteration.streaming: fit() consumes a
StreamTable (or a bounded Table chopped into global batches) and the fitted
model records every versioned snapshot — the host-side equivalent of the
reference's unbounded model-data stream.
"""

from __future__ import annotations

import math
import time
from typing import List, Optional, Tuple, Union

import numpy as np

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.iteration.streaming import (
    StreamCheckpointer,
    StreamTable,
    generate_batches,
)
from flink_ml_tpu.models.common import IterationRuntimeMixin
from flink_ml_tpu.linalg.distance import DistanceMeasure
from flink_ml_tpu.models.clustering.kmeans import KMeansModel, KMeansModelParams
from flink_ml_tpu.params.param import FloatParam, ParamValidators
from flink_ml_tpu.params.shared import (
    HasBatchStrategy,
    HasDecayFactor,
    HasElasticNet,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasInputCol,
    HasLabelCol,
    HasMaxAllowedModelDelayMs,
    HasModelVersionCol,
    HasOutputCol,
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasSeed,
    HasWeightCol,
    HasWindows,
)
from flink_ml_tpu.utils import io as rw


def _as_stream(data: Union[Table, StreamTable], batch_size: int):
    if isinstance(data, Table):
        data = StreamTable.from_table(data, batch_size)
    return generate_batches(data, batch_size)


#: max per-batch model snapshots kept on device before draining to host in
#: one stacked transfer (keeps async dispatch across batches while bounding
#: HBM held by history on unbounded streams)
_HISTORY_DEV_CAP = 128


import functools


def _ftrl_apply(xp, g, coeffs, z, n, alpha, beta, l1, l2):
    """The FTRL-proximal elementwise update (UpdateModel:295-319), shared
    by the dense device program, the sparse device program and the host
    CSR engine — ``xp`` is jnp or np; one copy of the math keeps the
    three paths in lockstep by construction."""
    sigma = (xp.sqrt(n + g * g) - xp.sqrt(n)) / alpha
    z = z + g - sigma * coeffs
    n = n + g * g
    coeffs = xp.where(
        xp.abs(z) <= l1, 0.0,
        (xp.sign(z) * l1 - z) / ((beta + xp.sqrt(n)) / alpha + l2))
    return coeffs, z, n


@functools.lru_cache(maxsize=32)
def _ftrl_program(mesh, alpha: float, beta: float, l1: float, l2: float,
                  health: bool = False, sharded: bool = False):
    """ONE FTRL global-batch update as a compiled map-reduce program
    (parallel/mapreduce.py): batch *partitioned* over the mesh's data
    axes, the per-shard gradient partials the *map*, one *reduce*, the
    FTRL-proximal rule the *update* — the dense-branch math of
    CalculateLocalGradient:364-388 + UpdateModel:295-319 with the TPU
    doing the batch matmul instead of a host numpy loop (the round-2
    'online fits leave the device idle' gap).

    With ``sharded`` (update_sharding.py) the update is cross-replica
    sharded: the gradient *reduce-scatters* so each replica owns a
    ``1/N`` slice of the coefficients AND of the z/n accumulators —
    which stay sharded across batches (``1/N`` optimizer memory per
    replica) — then the fresh coefficients all-gather for the next
    forward pass. The (z, n) carries are donated through
    ``instrumented_jit``, so the accumulator update happens in place.

    With ``health`` (observability/health.py) the program additionally
    returns the batch's mean logloss — the per-batch convergence/health
    scalar computed *inside* the jitted step from the dots it already
    has (DrJAX-style first-class output; a NaN anywhere in the state
    poisons it, so it doubles as the non-finite sentinel). The host
    drains these scalars in stacked transfers, never per batch."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import mapreduce as mr
    from flink_ml_tpu.parallel import update_sharding as _upd

    # name (→ instrumented_jit) only for the sharded build: the
    # replicated per-batch hot loop keeps plain jit's C++ dispatch cache
    prog = mr.MapReduceProgram(mesh,
                               name="ftrl.dense" if sharded else None)
    axes, spec0 = prog.axes, prog.spec0

    def map_fn(xl, yl, n_valid, coeffs, z, n):
        d = xl.shape[1]  # true dim; coeffs may be padded (sharded)
        vl = mr.local_valid_mask(axes, xl.shape[0], n_valid, xl.dtype)
        dots = xl @ coeffs[:d]
        p = 1.0 / (1.0 + jnp.exp(-dots))
        partials = {"grad": _upd.pad_leading(((p - yl) * vl) @ xl,
                                             coeffs.shape[0])}
        if health:
            # stable binary logloss from the margins: log(1+e^d) - y·d
            xent = jnp.logaddexp(0.0, dots) - yl * dots
            partials["loss"] = jnp.sum(vl * xent)
        return partials

    def update_fn(red, xl, yl, n_valid, coeffs, z, n):
        # dense-path reference semantics: weight sum = batch row count
        # at every coordinate. In sharded mode `red["grad"]` is this
        # replica's scattered slice and (z, n) are its carried slices —
        # the same expression updates 1/N of the state per replica.
        g = red["grad"] / jnp.maximum(n_valid.astype(red["grad"].dtype),
                                      1.0)
        if sharded:
            w2, z2, n2 = _ftrl_apply(jnp, g, _upd.owned_slice(coeffs, axes),
                                     z, n, alpha, beta, l1, l2)
            out = (mr.all_gather(w2, axes), z2, n2)
        else:
            out = _ftrl_apply(jnp, g, coeffs, z, n, alpha, beta, l1, l2)
        if health:
            loss = red["loss"] / jnp.maximum(n_valid, 1.0)
            return out + (loss,)
        return out

    zspec = P(spec0) if sharded else P()
    reduce = {"grad": mr.reduce_scatter if sharded else mr.reduce_sum}
    if health:
        reduce["loss"] = mr.reduce_sum
    # the (z, n) accumulator carries donate in EVERY build (in-place
    # update; each batch's inputs are the previous batch's outputs, and
    # to_host()/history read only the CURRENT state, never a consumed
    # input). The coefficient carry does NOT donate — every version's w
    # buffer lives on in the model history. Unsharded builds keep plain
    # jit's C++ dispatch cache (map_shards: donation without a name).
    return prog.build(
        map_fn, update_fn,
        in_specs=(P(spec0, None), P(spec0), P(), P(), zspec, zspec),
        out_specs=(P(), zspec, zspec) + ((P(),) if health else ()),
        reduce=reduce,
        donate_argnums=(4, 5))


@functools.lru_cache(maxsize=32)
def _ftrl_sparse_program(mesh, alpha: float, beta: float, l1: float,
                         l2: float, health: bool = False,
                         sharded: bool = False, use_kernel: bool = False):
    """ONE sparse-batch FTRL update as a compiled map-reduce program —
    the device twin of the host CSR branch (ref CalculateLocalGradient:
    364-388: gradient and weight sums accumulate ONLY at a sample's
    non-zero coordinates, unlike the dense program's batch-count
    denominator).

    The CSR batch arrives as per-shard padded quads (values, column ids,
    local row ids, validity) *partitioned* over the mesh's data axes
    plus per-shard (y, w) row blocks; the *map* is the forward matvec
    and the per-coordinate segment-sums over the shard's nnz; the
    *reduce* crosses shards (reduce-scattered per-coordinate in
    ``sharded`` mode — the z/n accumulator slices stay sharded like the
    dense program's); the FTRL elementwise rule is the *update*. Padded
    nnz slots carry validity 0 so they contribute nothing; padded rows
    own no nnz so their p never enters a sum.

    With ``use_kernel`` (TPU, small segment domains — fit() gates on
    ``segment_reduce_fits``) the three segment-sums run the fused pallas
    segment-reduce: the per-coordinate gradient and weight sums share
    ONE kernel pass over the nnz (stacked into two value columns)
    instead of two serialized XLA scatters, and the forward per-row sum
    is a third; the cross-shard reduce and the FTRL rule are unchanged,
    so results match the XLA program up to float reassociation in the
    per-tile partial sums."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import mapreduce as mr
    from flink_ml_tpu.parallel import update_sharding as _upd

    prog = mr.MapReduceProgram(mesh)
    axes, spec0 = prog.axes, prog.spec0

    def map_fn(vals, col, row, valid, yb, wb, coeffs, z, n):
        vals, col, row, valid = vals[0], col[0], row[0], valid[0]
        yb, wb = yb[0], wb[0]
        rows_s = yb.shape[0]
        d_pad = coeffs.shape[0]
        if use_kernel:
            from flink_ml_tpu.ops.pallas_kernels import segment_reduce_sum
            dots = segment_reduce_sum(vals * coeffs[col] * valid, row,
                                      rows_s)
            p = 1.0 / (1.0 + jnp.exp(-dots))
            # grad and wsum share one fused pass: two value columns,
            # one scatter domain (the nnz column ids)
            gw = segment_reduce_sum(
                jnp.stack([vals * (p - yb)[row] * valid,
                           wb[row] * valid], axis=1), col, d_pad)
            partials = {"grad": gw[:, 0], "wsum": gw[:, 1]}
        else:
            dots = jax.ops.segment_sum(vals * coeffs[col] * valid, row,
                                       num_segments=rows_s)
            p = 1.0 / (1.0 + jnp.exp(-dots))
            partials = {
                "grad": jax.ops.segment_sum(vals * (p - yb)[row] * valid,
                                            col, num_segments=d_pad),
                "wsum": jax.ops.segment_sum(wb[row] * valid, col,
                                            num_segments=d_pad),
            }
        if health:
            # per-batch mean logloss, weighted by the sample weights
            # (padded rows carry weight 0, so they contribute nothing)
            xent = jnp.logaddexp(0.0, dots) - yb * dots
            partials["lossNum"] = jnp.sum(wb * xent)
            partials["lossDen"] = jnp.sum(wb)
        return partials

    def update_fn(red, vals, col, row, valid, yb, wb, coeffs, z, n):
        grad, wsum = red["grad"], red["wsum"]
        g = jnp.where(wsum != 0, grad / jnp.where(wsum != 0, wsum, 1.0),
                      0.0)
        if sharded:
            w2, z2, n2 = _ftrl_apply(jnp, g, _upd.owned_slice(coeffs, axes),
                                     z, n, alpha, beta, l1, l2)
            out = (mr.all_gather(w2, axes), z2, n2)
        else:
            out = _ftrl_apply(jnp, g, coeffs, z, n, alpha, beta, l1, l2)
        if health:
            loss = red["lossNum"] / jnp.maximum(red["lossDen"], 1e-30)
            return out + (loss,)
        return out

    zspec = P(spec0) if sharded else P()
    coord_reduce = mr.reduce_scatter if sharded else mr.reduce_sum
    reduce = {"grad": coord_reduce, "wsum": coord_reduce}
    if health:
        reduce["lossNum"] = mr.reduce_sum
        reduce["lossDen"] = mr.reduce_sum
    return prog.build(
        map_fn, update_fn,
        in_specs=(P(spec0, None),) * 6 + (P(), zspec, zspec),
        out_specs=(P(), zspec, zspec) + ((P(),) if health else ()),
        reduce=reduce)


def _pack_csr_shards(x, y, w, n_shards: int):
    """Split a scipy CSR batch into ``n_shards`` row ranges and pack each
    as padded (values, col, local row, valid) rows of one (S, nnz_s)
    quad plus (S, rows_s) y/w blocks — the host marshalling for
    :func:`_ftrl_sparse_program`. nnz_s / rows_s round up to powers of
    two so jit recompiles per size bucket, not per batch."""
    n_rows = x.shape[0]
    base, rem = divmod(n_rows, n_shards)
    bounds, lo = [], 0
    for i in range(n_shards):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    max_nnz = max((x.indptr[hi] - x.indptr[lo] for lo, hi in bounds),
                  default=0)
    max_rows = max((hi - lo for lo, hi in bounds), default=0)
    nnz_s = 1 << max(3, int(max_nnz - 1).bit_length())
    rows_s = 1 << max(3, int(max_rows - 1).bit_length())
    vals = np.zeros((n_shards, nnz_s), np.float32)
    col = np.zeros((n_shards, nnz_s), np.int32)
    row = np.zeros((n_shards, nnz_s), np.int32)
    valid = np.zeros((n_shards, nnz_s), np.float32)
    yb = np.zeros((n_shards, rows_s), np.float32)
    wb = np.zeros((n_shards, rows_s), np.float32)
    for s, (lo, hi) in enumerate(bounds):
        a, b = x.indptr[lo], x.indptr[hi]
        nz = b - a
        vals[s, :nz] = x.data[a:b]
        col[s, :nz] = x.indices[a:b]
        row[s, :nz] = np.repeat(np.arange(hi - lo, dtype=np.int32),
                                np.diff(x.indptr[lo:hi + 1]))
        valid[s, :nz] = 1.0
        yb[s, : hi - lo] = y[lo:hi]
        wb[s, : hi - lo] = w[lo:hi]
    return vals, col, row, valid, yb, wb


#: sparse batches with at least this many stored values update on device
#: (below it, per-batch dispatch overhead beats the segment-sum win and
#:  the float64 host math preserves the fine-grained reference semantics
#:  the unit tests pin); override with FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ
_FTRL_SPARSE_MIN_NNZ = 4096


def _ftrl_sparse_min_nnz() -> int:
    import os

    env = os.environ.get("FLINK_ML_TPU_FTRL_SPARSE_MIN_NNZ")
    try:
        return int(env) if env else _FTRL_SPARSE_MIN_NNZ
    except ValueError:
        return _FTRL_SPARSE_MIN_NNZ


# ---------------------------------------------------------------------------
# OnlineLogisticRegression (FTRL)
# ---------------------------------------------------------------------------

class OnlineLogisticRegressionModelParams(HasFeaturesCol, HasPredictionCol,
                                          HasRawPredictionCol,
                                          HasModelVersionCol,
                                          HasMaxAllowedModelDelayMs):
    pass


class OnlineLogisticRegressionParams(OnlineLogisticRegressionModelParams,
                                     HasLabelCol, HasWeightCol,
                                     HasBatchStrategy, HasGlobalBatchSize,
                                     HasReg, HasElasticNet):
    ALPHA = FloatParam("alpha", "The alpha parameter of ftrl.", 0.1,
                       ParamValidators.gt(0.0))
    BETA = FloatParam("beta", "The beta parameter of ftrl.", 0.1,
                      ParamValidators.gt(0.0))


class OnlineLogisticRegressionModel(Model,
                                    OnlineLogisticRegressionModelParams):
    def __init__(self, coefficients: Optional[np.ndarray] = None,
                 model_version: int = 0, **kwargs):
        super().__init__(**kwargs)
        self.coefficients = (None if coefficients is None
                             else np.asarray(coefficients, np.float64))
        self.model_version = int(model_version)
        #: all versioned snapshots recorded during fit: [(version, coeffs)]
        self.history: List[Tuple[int, np.ndarray]] = []

    def transform(self, table: Table) -> Tuple[Table]:
        if self.coefficients is None:
            raise ValueError(
                "OnlineLogisticRegressionModel has no model data")
        from flink_ml_tpu.linalg import sparse
        from flink_ml_tpu.models.common import predict_dots, prediction_dtype
        x = sparse.features_matrix(table, self.features_col)
        # dense batches score on device through the columnar path (ref
        # predict of OnlineLogisticRegressionModel.java:67-95); CSR stays
        # a host matvec
        dots, xp = predict_dots(x, self.coefficients)
        prob = 1.0 / (1.0 + xp.exp(-dots))
        return (table.with_columns(**{
            self.prediction_col: (dots >= 0).astype(prediction_dtype(xp)),
            self.raw_prediction_col: xp.stack([1 - prob, prob], axis=1),
            self.model_version_col: np.full(table.num_rows,
                                            self.model_version, np.int64)}),)

    def transform_stream(self, stream: StreamTable, model_stream=None,
                         timestamp_col: Optional[str] = None):
        """Unbounded predict: each chunk is scored with the latest model
        version available at that point (the reference's model-broadcast
        join); returns a generator of output Tables.

        With ``model_stream`` (an iterable of ``(timestamp_ms, version,
        coefficients)``) and ``timestamp_col`` (event-time column on the
        data), the bounded model-delay join of the reference applies
        (HasMaxAllowedModelDelayMs, used by
        OnlineLogisticRegressionModel.java:67-95): a record with event time
        ``t`` is held until a model with timestamp ``>= t -
        maxAllowedModelDelayMs`` has arrived, then scored with the latest
        model received — data never runs ahead of the model by more than
        the configured delay. If the model stream ends, remaining chunks
        are scored with the final model (a bounded fixture's end-of-stream;
        the reference's unbounded job would instead keep waiting).
        """
        # validate eagerly (this is a plain function returning a generator,
        # so the error surfaces at the call site, not at first iteration)
        if (model_stream is None) != (timestamp_col is None):
            raise ValueError(
                "model_stream and timestamp_col must be given together for "
                "the event-time model-delay join")
        return self._transform_stream_impl(stream, model_stream,
                                           timestamp_col)

    def _transform_stream_impl(self, stream, model_stream, timestamp_col):
        if model_stream is None:
            versions = iter(self.history or [(self.model_version,
                                              self.coefficients)])
            for chunk in stream:
                advanced = next(versions, None)
                if advanced is not None:
                    self.model_version, self.coefficients = advanced
                yield self.transform(chunk)[0]
            return

        max_delay = self.max_allowed_model_delay_ms
        models = iter(model_stream)
        model_ts = None
        pending = None  # one-model peek buffer

        def take(nxt):
            nonlocal model_ts
            model_ts, self.model_version, self.coefficients = (
                nxt[0], nxt[1], np.asarray(nxt[2], np.float64))

        for chunk in stream:
            newest_data_ts = int(np.max(chunk.column(timestamp_col)))
            # 1) every model that has already arrived (ts <= data time) is
            #    applied — scoring always uses the LATEST arrived model
            while True:
                if pending is None:
                    pending = next(models, None)
                if pending is None or pending[0] > newest_data_ts:
                    break
                take(pending)
                pending = None
            # 2) the delay bound: data is held until a model fresh enough
            #    (ts >= t - maxDelay) exists; pull forward if necessary
            while (model_ts is None or model_ts < newest_data_ts - max_delay):
                nxt = pending or next(models, None)
                pending = None
                if nxt is None:
                    break  # stream over: score with what we have
                take(nxt)
            yield self.transform(chunk)[0]

    def set_model_data(self, model_data: Table):
        col = model_data.column("coefficient")
        self.coefficients = (col[0].to_array() if col.dtype == object
                             else np.asarray(col[0]))
        if "modelVersion" in model_data:
            self.model_version = int(model_data.column("modelVersion")[0])
        # only the version gauge: LR model data carries no timestamp, and a
        # wall-clock substitute would clobber other models' real timestamps
        from flink_ml_tpu.common.metrics import metrics
        from flink_ml_tpu.common.metrics import VERSION_GAUGE
        metrics.model_group().gauge(VERSION_GAUGE, self.model_version)
        return self

    def get_model_data(self) -> Tuple[Table]:
        return (Table.from_columns(
            coefficient=as_dense_vector_column(self.coefficients[None, :]),
            modelVersion=np.asarray([self.model_version], np.int64)),)

    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {
            "coefficient": self.coefficients,
            "modelVersion": np.asarray([self.model_version])})

    def _load_extra(self, path: str, meta: dict) -> None:
        arrays = rw.load_model_arrays(path, "model")
        self.coefficients = arrays["coefficient"]
        self.model_version = int(arrays["modelVersion"][0])


class OnlineLogisticRegression(Estimator, OnlineLogisticRegressionParams,
                               IterationRuntimeMixin):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._initial_model_data: Optional[Table] = None

    def set_initial_model_data(self, model_data: Table):
        """Ref: OnlineLogisticRegression.setInitialModelData:440."""
        self._initial_model_data = model_data
        return self

    def warm_start(self, model, model_version: Optional[int] = None):
        """Seed the next fit from an already-serving model — THE
        incremental-refit seam the ops controller uses
        (serving/controller.py): a drift-triggered retrain continues
        FTRL from the live coefficients over recent traffic instead of
        re-learning from zeros.

        ``model`` is a fitted :class:`OnlineLogisticRegressionModel`
        (its coefficients + model_version seed the fit) or a bare
        coefficient vector; ``model_version`` overrides the seed
        version (e.g. the registry's published version, which is the
        authoritative counter once serving owns the model)."""
        if hasattr(model, "coefficients"):
            coeffs = np.asarray(model.coefficients, np.float64)
            version = int(getattr(model, "model_version", 0))
        else:
            coeffs = np.asarray(model, np.float64)
            version = 0
        if coeffs.ndim != 1:
            raise ValueError(
                f"warm_start expects a 1-D coefficient vector, got "
                f"shape {coeffs.shape}")
        if model_version is not None:
            version = int(model_version)
        return self.set_initial_model_data(Table.from_columns(
            coefficient=as_dense_vector_column(coeffs[None, :]),
            modelVersion=np.asarray([version], np.int64)))

    def fit(self, data: Union[Table, StreamTable]
            ) -> OnlineLogisticRegressionModel:
        if self._initial_model_data is None:
            raise ValueError("initial model data must be set before fit "
                             "(setInitialModelData)")
        col = self._initial_model_data.column("coefficient")
        coeffs = np.array(col[0].to_array() if col.dtype == object
                          else col[0], np.float64)
        d = coeffs.shape[0]  # true dim; device state may pad (sharded)
        version = (int(self._initial_model_data.column("modelVersion")[0])
                   if "modelVersion" in self._initial_model_data else 0)

        alpha, beta = self.alpha, self.beta
        l1 = self.elastic_net * self.reg
        l2 = (1.0 - self.elastic_net) * self.reg
        z = np.zeros_like(coeffs)
        n = np.zeros_like(coeffs)

        model = OnlineLogisticRegressionModel()
        self.copy_params_to(model)
        history: List[Tuple[int, np.ndarray]] = []

        ckpt = StreamCheckpointer(self._iteration_config,
                                  self._iteration_listeners)

        # Dense batches keep (w, z, n) ON DEVICE between updates: the whole
        # batch loop then dispatches asynchronously with zero per-batch
        # syncs (each np.asarray here is a blocking D2H — at 100 batches
        # that latency, not the math, dominated).
        # State comes back to host float64 only when something actually
        # needs it: a sparse batch, a due checkpoint/listener, or fit end.
        # float32→float64→float32 round-trips are exact, so host and
        # device residency produce identical numbers.
        # With the cross-replica sharded update armed
        # (parallel/update_sharding.py) the device triple is
        # (w replicated+padded, z sharded, n sharded): each replica
        # carries only its 1/N accumulator slice between batches. The
        # host view stays the trimmed (d,) float64 arrays either way, so
        # checkpoints are byte-compatible across modes and a sharded fit
        # can resume a replicated one's snapshot (and vice versa).
        state_dev = None  # (coeffs, z, n) float32 device triple, or None

        def to_host():
            nonlocal coeffs, z, n, state_dev
            if state_dev is not None:
                coeffs, z, n = (np.asarray(a, np.float64)[:d]
                                for a in state_dev)
                state_dev = None

        # indices of history entries still holding device snapshots; they
        # drain to host in one stacked D2H. Capped: past _HISTORY_DEV_CAP
        # pending snapshots they drain eagerly, so an unbounded stream pins
        # O(cap·d), not O(stream·d), of HBM.
        dev_pending: List[int] = []

        def materialize_history():
            if dev_pending:
                import jax.numpy as jnp
                stacked = np.asarray(
                    jnp.stack([history[i][1] for i in dev_pending]),
                    np.float64)
                for j, i in enumerate(dev_pending):
                    # [:d] trims the sharded-update padding (no-op when
                    # the device state is unpadded)
                    history[i] = (history[i][0], stacked[j][:d])
                dev_pending.clear()

        def pack():
            # history rides in the checkpoint as two stacked arrays so the
            # state pytree has a fixed leaf count regardless of its length
            to_host()
            materialize_history()
            hv = np.asarray([v for v, _ in history], np.int64)
            hc = (np.stack([c for _, c in history])
                  if history else np.zeros((0,) + coeffs.shape))
            return coeffs, z, n, version, hv, hc

        restored = ckpt.restore(pack())
        if restored is not None:
            coeffs, z, n, version, hv, hc = restored[0]
            version = int(version)
            history[:] = [(int(v), c) for v, c in zip(hv, hc)]

        from flink_ml_tpu.linalg import sparse
        from flink_ml_tpu.observability import health as _mlhealth
        from flink_ml_tpu.parallel import update_sharding as _upd
        from flink_ml_tpu.parallel.collective import ensure_on_mesh
        from flink_ml_tpu.parallel.mesh import (data_axes,
                                                data_shard_count,
                                                default_mesh)

        # cross-replica sharded optimizer state (update_sharding.py):
        # z/n accumulators live sharded on device, 1/N per replica
        sharded = _upd.enabled()

        # per-batch model-health telemetry (observability/health.py):
        # device batches return their mean logloss as a program output;
        # the scalars stay on device and drain in stacked transfers at
        # the same cadence as the history snapshots, so the async batch
        # pipeline keeps zero per-batch syncs
        health_on = _mlhealth.armed()
        algo = type(self).__name__
        loss_pending: List = []  # device loss scalars awaiting drain
        loss_series: List[float] = []

        def drain_losses():
            if loss_pending:
                import jax.numpy as jnp

                vals = np.asarray(jnp.stack(loss_pending), np.float64)
                loss_pending.clear()
                loss_series.extend(float(v) for v in vals)

        def check_losses(final=False):
            """Drain pending device losses; fail fast on a non-finite
            batch (records the series, raises NonFiniteState)."""
            drain_losses()
            if loss_series and not all(np.isfinite(loss_series)):
                _mlhealth.check_fit(algo, {"loss": loss_series},
                                    finite=False)
            elif final:
                _mlhealth.check_fit(algo, {"loss": loss_series},
                                    finite=True)

        # the mesh initializes the device backend — only on the first
        # device-eligible batch (dense, or sparse above the nnz gate), so
        # a small-sparse stream trains with no device at all
        mesh = axes = None
        # provenance (executionPath)
        n_dense = n_sparse = n_sparse_dev = n_sparse_kernel = 0
        self.last_execution_path = None  # a zero-batch refit must not
        # inherit the previous fit's label

        def device_state():
            """(coeffs, z, n) as the float32 device triple WITHOUT
            committing it to state_dev — callers assign state_dev only
            after their device step succeeds, so a failed attempt leaves
            the float64 host state untruncated for the host engine.
            Sharded mode pads to the shard multiple and places w
            replicated, z/n dim-0-sharded (1/N slice per replica)."""
            import jax
            import jax.numpy as jnp

            if state_dev is not None:
                return state_dev
            if sharded:
                from jax.sharding import NamedSharding, PartitionSpec as P

                dp = _upd.padded_len(d, data_shard_count(mesh))
                pad = dp - d
                w = jax.device_put(
                    np.pad(coeffs, (0, pad)).astype(np.float32),
                    NamedSharding(mesh, P()))
                zs, ns = _upd.place_opt_state(
                    mesh, (np.pad(z, (0, pad)).astype(np.float32),
                           np.pad(n, (0, pad)).astype(np.float32)))
                return (w, zs, ns)
            return (jnp.asarray(coeffs, jnp.float32),
                    jnp.asarray(z, jnp.float32),
                    jnp.asarray(n, jnp.float32))

        state_recorded = False

        def commit_device_state(new_state):
            """Shared device-batch bookkeeping (dense + sparse paths):
            adopt the new state, version it, snapshot coefficients into
            the history (drained in stacked D2H past the cap), checkpoint."""
            nonlocal state_dev, version, state_recorded
            state_dev = new_state
            if not state_recorded:
                # per-replica optimizer-state accounting (benchmark
                # provenance + the BENCH_mapreduce 1/N gate), MEASURED
                # from the committed z/n device buffers — a regression
                # that silently replicates the 'sharded' slices shows
                # up as real bytes here, not as arithmetic
                state_recorded = True
                _upd.record_state_bytes(
                    algo, new_state[1:], data_shard_count(mesh), sharded)
            version += 1
            dev_pending.append(len(history))
            history.append((version, state_dev[0]))
            if len(dev_pending) >= _HISTORY_DEV_CAP:
                materialize_history()
            ckpt.after_batch(pack)

        for batch in _as_stream(data, self.global_batch_size):
            # float32 request: a device-resident dense column passes
            # through untouched (no D2H off-ramp); the CSR branch is
            # always float64 regardless (features_matrix contract)
            x = sparse.features_matrix(batch, self.features_col, np.float32)
            if not sparse.is_csr(x):
                # dense batches update on device: one compiled SPMD step
                # per batch; state stays device-resident across consecutive
                # dense batches (see to_host above)
                import jax.numpy as jnp

                if mesh is None:
                    mesh = default_mesh()
                    axes = data_axes(mesh)
                program = _ftrl_program(mesh, alpha, beta, l1, l2,
                                        health=health_on, sharded=sharded)
                xb, n_rows = ensure_on_mesh(mesh, x, axes, jnp.float32)
                ycol = batch.column(self.label_col)  # device col stays put
                if isinstance(ycol, np.ndarray):
                    ycol = batch.scalars(self.label_col)
                yb, _ = ensure_on_mesh(mesh, ycol, axes, jnp.float32)
                out = program(xb, yb, jnp.float32(n_rows),
                              *device_state())
                if health_on:
                    *state, batch_loss = out
                    loss_pending.append(batch_loss)
                    if len(loss_pending) >= _HISTORY_DEV_CAP:
                        check_losses()
                    out = tuple(state)
                commit_device_state(out)
                n_dense += 1
                continue
            y = batch.scalars(self.label_col, np.float64)
            w_col = (batch.scalars(self.weight_col, np.float64)
                     if self.weight_col is not None
                     and self.weight_col in batch
                     else np.ones(x.shape[0], np.float64))
            if x.nnz >= _ftrl_sparse_min_nnz():
                # large sparse batches update ON DEVICE: segment-sums
                # over the sharded nnz (the device twin of the host CSR
                # branch below); state stays device-resident like the
                # dense path. The nnz gate is the whole choice between
                # the engines: a failed device step raises.
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                from flink_ml_tpu.ops.pallas_kernels import (
                    pallas_supported,
                    segment_reduce_fits,
                )
                from flink_ml_tpu.parallel.mesh import data_pspec

                if mesh is None:
                    mesh = default_mesh()
                    axes = data_axes(mesh)
                packed = _pack_csr_shards(x, y, w_col,
                                          data_shard_count(mesh))
                rows_s = packed[4].shape[1]
                # fused pallas segment-reduce for the shapes whose
                # one-hot block fits VMEM (small coordinate domains;
                # hashed 2^18 features keep the XLA scatter). The
                # coordinate domain the program scatters over is the
                # PADDED model dim (sharded mode pads to the shard
                # multiple).
                d_dom = (_upd.padded_len(d, data_shard_count(mesh))
                         if sharded else d)
                use_kernel = (pallas_supported()
                              and segment_reduce_fits(d_dom, 2)
                              and segment_reduce_fits(rows_s, 1))
                sh = NamedSharding(mesh, P(data_pspec(mesh), None))
                program = _ftrl_sparse_program(
                    mesh, alpha, beta, l1, l2, health=health_on,
                    sharded=sharded, use_kernel=use_kernel)
                out = program(*(jax.device_put(a, sh) for a in packed),
                              *device_state())
                if health_on:
                    *new_state, batch_loss = out
                    new_state = tuple(new_state)
                else:
                    new_state, batch_loss = out, None
                commit_device_state(new_state)
                if health_on:
                    loss_pending.append(batch_loss)
                    if len(loss_pending) >= _HISTORY_DEV_CAP:
                        check_losses()
                n_sparse_dev += 1
                n_sparse_kernel += use_kernel
                continue
            to_host()  # sparse math is host numpy against float64 state
            # sparse branch (ref CalculateLocalGradient:364-388): the
            # gradient and the weight sum accumulate ONLY at a sample's
            # non-zero coordinates; weightSum adds the sample weight
            # there (dense adds 1.0 everywhere). Never densifies: CSR
            # matvec + bincount scatter at 2^18 dims stays O(nnz).
            dots = x @ coeffs
            p = 1.0 / (1.0 + np.exp(-dots))
            if health_on:
                xent = np.logaddexp(0.0, dots) - y * dots
                loss_series.append(
                    float(np.sum(w_col * xent)
                          / max(float(w_col.sum()), 1e-30)))
                if not math.isfinite(loss_series[-1]):
                    check_losses()
            row_nnz = np.diff(x.indptr)
            # NOT `d`: the fit-wide `d` is the model dim owning the
            # sharded-padding/trim contract (to_host/[:d]); rebinding it
            # to a batch's CSR width would silently corrupt that
            n_cols = x.shape[1]
            grad = np.bincount(
                x.indices,
                weights=x.data * np.repeat(p - y, row_nnz),
                minlength=n_cols)
            weight_sum = np.bincount(
                x.indices, weights=np.repeat(w_col, row_nnz),
                minlength=n_cols)
            g = np.where(weight_sum != 0, grad / np.where(weight_sum != 0,
                                                          weight_sum, 1), 0)
            coeffs, z, n = _ftrl_apply(np, g, coeffs, z, n, alpha, beta,
                                       l1, l2)
            version += 1
            n_sparse += 1
            history.append((version, coeffs.copy()))
            ckpt.after_batch(pack)

        ckpt.complete(pack)
        to_host()
        materialize_history()
        if health_on:
            # end-of-stream drain: the full per-batch loss series lands
            # in ml.health (+ convergence events), a non-finite batch
            # raises the terminal NonFiniteState
            check_losses(final=True)
        # the batch loss is computed from PRE-update coefficients, so a
        # divergence on the very last update only shows in the state:
        # the cheap final guard covers it on every path
        _mlhealth.guard_final_state(algo, coeffs)
        # benchmark provenance (runner.py executionPath): where the FTRL
        # batch updates actually ran
        parts = (("device", n_dense),
                 ("device-csr", n_sparse_dev - n_sparse_kernel),
                 ("device-csr-pallas", n_sparse_kernel),
                 ("host-csr", n_sparse))
        active = [(k, v) for k, v in parts if v]
        if len(active) > 1:
            self.last_execution_path = "mixed(" + ",".join(
                f"{k}={v}" for k, v in active) + ")"
        elif active:
            self.last_execution_path = f"{active[0][0]}-batches"
        model.coefficients = coeffs
        model.model_version = version
        model.history = history
        # drift baseline (observability/drift.py): sketch a row-capped
        # sample of the training inputs + the FINAL model's predictions
        # on it, so publish_model ships the distribution this exact
        # snapshot was trained on (the train-and-serve handoff's other
        # half). Table path only — an unbounded stream has no finite
        # "training set" to summarize; its consumers publish per
        # snapshot from the batch view instead.
        try:
            from flink_ml_tpu.observability import drift as _mldrift

            if _mldrift.capture_armed() and isinstance(data, Table):
                from flink_ml_tpu.linalg import sparse as _sparse
                from flink_ml_tpu.models.common import predict_dots

                xs = _mldrift.sample_rows(
                    _sparse.features_matrix(data, self.features_col))
                fdots, _xp = predict_dots(xs, coeffs)
                pred = (np.asarray(fdots, np.float64)
                        >= 0).astype(np.float64)
                _mldrift.capture_fit_baseline(
                    model, algo, features=xs, predictions=pred,
                    version=version)
        except Exception:  # noqa: BLE001 — telemetry must not sink
            # the fit that just produced a valid model
            import logging

            logging.getLogger(__name__).warning(
                "drift baseline capture failed", exc_info=True)
        # quality baseline (observability/evaluation.py): the FINAL
        # model's positive-class probabilities on the same row-capped
        # sample vs the training labels — the live-AUC anchor the
        # canary verdict's quality stage judges against. Same Table-
        # path-only rationale as drift above.
        try:
            from flink_ml_tpu.observability import drift as _mldrift
            from flink_ml_tpu.observability import (
                evaluation as _mlquality,
            )

            if _mlquality.capture_armed() and isinstance(data, Table):
                from flink_ml_tpu.linalg import sparse as _sparse
                from flink_ml_tpu.models.common import predict_dots

                xs = _mldrift.sample_rows(
                    _sparse.features_matrix(data, self.features_col))
                ys = np.asarray(
                    data.scalars(self.label_col, np.float64)
                )[:xs.shape[0]]
                fdots, _xp = predict_dots(xs, coeffs)
                prob = 1.0 / (1.0 + np.exp(
                    -np.asarray(fdots, np.float64)))
                _mlquality.capture_fit_baseline(
                    model, algo, scores=prob, labels=ys,
                    version=version)
        except Exception:  # noqa: BLE001 — see the drift capture
            import logging

            logging.getLogger(__name__).warning(
                "quality baseline capture failed", exc_info=True)
        return model


# ---------------------------------------------------------------------------
# OnlineKMeans
# ---------------------------------------------------------------------------

class OnlineKMeansParams(KMeansModelParams, HasBatchStrategy,
                         HasGlobalBatchSize, HasDecayFactor, HasSeed):
    pass


class OnlineKMeansModel(KMeansModel):
    """Ref: OnlineKMeansModel.java — a KMeansModel fed by a stream of
    versioned model data; prediction logic is identical, the model data is
    whatever snapshot was consumed last."""


class OnlineKMeans(Estimator, OnlineKMeansParams, IterationRuntimeMixin):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._initial_model_data: Optional[Table] = None

    def set_initial_model_data(self, model_data: Table):
        """Ref: OnlineKMeans.setInitialModelData:345."""
        self._initial_model_data = model_data
        return self

    def fit(self, data: Union[Table, StreamTable]) -> "OnlineKMeansModel":
        if self._initial_model_data is None:
            raise ValueError("initial model data must be set before fit "
                             "(setInitialModelData)")
        seed_model = KMeansModel().set_model_data(self._initial_model_data)
        centroids = np.array(seed_model.centroids, np.float64)
        weights = np.array(seed_model.weights, np.float64)
        k = centroids.shape[0]
        measure = DistanceMeasure.get_instance(self.distance_measure)
        decay = self.decay_factor

        ckpt = StreamCheckpointer(self._iteration_config,
                                  self._iteration_listeners)
        restored = ckpt.restore((centroids, weights))
        if restored is not None:
            centroids, weights = restored[0]

        for batch in _as_stream(data, self.global_batch_size):
            x = batch.vectors(self.features_col, np.float64)
            dists = np.asarray(measure.pairwise(x, centroids))
            assign = np.argmin(dists, axis=1)
            counts = np.bincount(assign, minlength=k).astype(np.float64)
            sums = np.zeros_like(centroids)
            np.add.at(sums, assign, x)

            weights = weights * decay  # 1-task case of decay/parallelism
            hit = counts > 0  # empty clusters keep weight and position
            weights = np.where(hit, weights + counts, weights)
            lam = np.where(hit, counts / np.where(hit, weights, 1.0), 0.0)
            means = sums / np.maximum(counts, 1.0)[:, None]
            centroids = np.where(
                hit[:, None],
                (1.0 - lam)[:, None] * centroids + lam[:, None] * means,
                centroids)
            ckpt.after_batch(lambda: (centroids, weights))

        ckpt.complete(lambda: (centroids, weights))
        model = OnlineKMeansModel(centroids=centroids, weights=weights)
        return self.copy_params_to(model)


# ---------------------------------------------------------------------------
# OnlineStandardScaler
# ---------------------------------------------------------------------------

class OnlineStandardScalerModelParams(HasInputCol, HasOutputCol,
                                      HasModelVersionCol,
                                      HasMaxAllowedModelDelayMs):
    pass


class OnlineStandardScalerParams(OnlineStandardScalerModelParams, HasWindows):
    from flink_ml_tpu.params.param import BooleanParam as _B
    WITH_MEAN = _B("withMean",
                   "Whether centers the data with mean before scaling.",
                   False)
    WITH_STD = _B("withStd",
                  "Whether scales the data with standard deviation.", True)


class OnlineStandardScalerModel(Model, OnlineStandardScalerModelParams):
    def __init__(self, mean=None, std=None, model_version: int = 0,
                 timestamp: int = 0, with_mean=False, with_std=True,
                 **kwargs):
        super().__init__(**kwargs)
        self.mean = None if mean is None else np.asarray(mean, np.float64)
        self.std = None if std is None else np.asarray(std, np.float64)
        self.model_version = int(model_version)
        self.timestamp = int(timestamp)
        self._with_mean, self._with_std = with_mean, with_std
        self.history: List[Tuple[int, np.ndarray, np.ndarray]] = []
        #: per-snapshot timestamps (window end for time windows): the
        #: (timestamp, version, data) stream the model-delay join consumes
        self.history_timestamps: List[int] = []

    def transform(self, table: Table) -> Tuple[Table]:
        if self.mean is None:
            raise ValueError("OnlineStandardScalerModel has no model data")
        x = table.vectors(self.input_col, np.float64)
        if self._with_mean:
            x = x - self.mean
        if self._with_std:
            x = x / np.where(self.std > 0, self.std, 1.0)
        out = {self.output_col: x}
        if self.model_version_col is not None:
            out[self.model_version_col] = np.full(
                len(x), self.model_version, np.int64)
        return (table.with_columns(**out),)

    def set_model_data(self, model_data: Table):
        self.mean = model_data.vectors("mean", np.float64)[0]
        self.std = model_data.vectors("std", np.float64)[0]
        if "modelVersion" in model_data:
            self.model_version = int(model_data.column("modelVersion")[0])
        if "timestamp" in model_data:
            self.timestamp = int(model_data.column("timestamp")[0])
        # ref OnlineStandardScalerModel.java:202-210: consuming model data
        # publishes the ml.model version/timestamp gauges
        from flink_ml_tpu.common.metrics import metrics
        metrics.report_model(self.model_version, self.timestamp)
        return self

    def get_model_data(self) -> Tuple[Table]:
        return (Table.from_columns(
            mean=self.mean[None, :], std=self.std[None, :],
            modelVersion=np.asarray([self.model_version], np.int64),
            timestamp=np.asarray([self.timestamp], np.int64)),)

    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {
            "mean": self.mean, "std": self.std,
            "version": np.asarray([self.model_version]),
            "timestamp": np.asarray([self.timestamp]),
            "flags": np.asarray([self._with_mean, self._with_std])})

    def _load_extra(self, path: str, meta: dict) -> None:
        arrays = rw.load_model_arrays(path, "model")
        self.mean, self.std = arrays["mean"], arrays["std"]
        self.model_version = int(arrays["version"][0])
        self.timestamp = int(arrays["timestamp"][0])
        self._with_mean, self._with_std = (bool(v) for v in arrays["flags"])


class OnlineStandardScaler(Estimator, OnlineStandardScalerParams,
                           IterationRuntimeMixin):
    def fit(self, data: Union[Table, StreamTable],
            batch_size: int = 1000,
            timestamp_col: Optional[str] = None
            ) -> OnlineStandardScalerModel:
        from flink_ml_tpu.common.window import (
            CountTumblingWindows,
            EventTimeSessionWindows,
            EventTimeTumblingWindows,
            ProcessingTimeSessionWindows,
            ProcessingTimeTumblingWindows,
        )
        windows = self.windows
        timed = isinstance(windows, (EventTimeTumblingWindows,
                                     ProcessingTimeTumblingWindows,
                                     EventTimeSessionWindows,
                                     ProcessingTimeSessionWindows))
        if isinstance(windows, CountTumblingWindows):
            batch_size = windows.size
        if isinstance(data, Table):
            data = StreamTable.from_table(data, batch_size)
        elif isinstance(windows, CountTumblingWindows):
            # pre-chunked stream: re-group to the count-window size so one
            # model is emitted per `size` rows regardless of chunking
            data = StreamTable(generate_batches(data, batch_size,
                                                drop_remainder=False))
        if timed:
            # time-windowed model emission: one versioned model per
            # tumbling window, stamped with the window end time (ref
            # OnlineStandardScaler window semantics)
            from flink_ml_tpu.iteration.streaming import window_stream
            data = window_stream(data, windows, timestamp_col,
                                 with_end_ts=True)

        total = sq_total = None
        count = 0
        version = 0
        history = []
        history_timestamps = []
        mean = std = None
        ckpt = StreamCheckpointer(self._iteration_config,
                                  self._iteration_listeners)

        def moments():
            m = total / count
            if count > 1:
                s = np.sqrt(np.maximum(
                    (sq_total - count * m * m) / (count - 1), 0.0))
            else:
                s = np.zeros_like(m)
            return m, s

        def pack():
            hv = np.asarray([v for v, _, _ in history], np.int64)
            hm = (np.stack([m for _, m, _ in history])
                  if history else np.zeros((0, 0)))
            hs = (np.stack([s for _, _, s in history])
                  if history else np.zeros((0, 0)))
            hts = np.asarray(history_timestamps, np.int64)
            return total, sq_total, count, version, hv, hm, hs, hts

        # restore before consuming the stream (shapes come from the saved
        # arrays, the zero-size template only fixes the pytree structure)
        restored = ckpt.restore(
            (np.zeros(0), np.zeros(0), 0, 0,
             np.zeros(0, np.int64), np.zeros((0, 0)), np.zeros((0, 0)),
             np.zeros(0, np.int64)))
        if restored is not None:
            total, sq_total, count, version, hv, hm, hs, hts = restored[0]
            count, version = int(count), int(version)
            history[:] = [(int(v), m, s) for v, m, s in zip(hv, hm, hs)]
            history_timestamps[:] = [int(t) for t in hts]

        for item in data:
            if timed:
                window_end_ms, chunk = item
            else:
                window_end_ms, chunk = None, item
            x = chunk.vectors(self.input_col, np.float64)
            if total is None:
                total = np.zeros(x.shape[1])
                sq_total = np.zeros(x.shape[1])
            total += x.sum(axis=0)
            sq_total += (x * x).sum(axis=0)
            count += x.shape[0]
            mean, std = moments()
            history.append((version, mean.copy(), std.copy()))
            # per-model timestamp: the window end for time windows (what
            # the reference stamps and the model-delay join consumes),
            # wall clock otherwise
            history_timestamps.append(
                window_end_ms if window_end_ms is not None
                else int(time.time() * 1000))
            version += 1
            ckpt.after_batch(pack)
        if count == 0:
            raise ValueError("empty input stream")
        if mean is None:  # resumed onto an already-exhausted stream
            mean, std = moments()
        ckpt.complete(pack)
        model = OnlineStandardScalerModel(
            mean=mean, std=std, model_version=version - 1,
            timestamp=(history_timestamps[-1] if history_timestamps
                       else int(time.time() * 1000)),
            with_mean=self.with_mean, with_std=self.with_std)
        self.copy_params_to(model)
        model.history = history
        model.history_timestamps = history_timestamps
        return model
