"""K-nearest-neighbors classifier.

Ref parity: flink-ml-lib classification/knn/{Knn.java, KnnModel.java,
KnnModelData.java} — fit caches the train matrix (+ precomputed squared
norms, KnnModelData), predict brute-forces distances and majority-votes the
k nearest (KnnModel.java predictLabel: ‖x‖²−2Xᵀx+‖X_i‖² then top-k).

TPU design: the whole test batch is scored at once — one (n_test, d) x
(d, n_train) matmul on the MXU + ``lax.top_k``, instead of the reference's
per-row gemv loop. Ties in the vote go to the smallest label (the
reference's hash-map iteration order is unspecified there).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.params.param import IntParam, ParamValidators
from flink_ml_tpu.params.shared import (
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
)
from flink_ml_tpu.utils import io as rw


class KnnModelParams(HasFeaturesCol, HasPredictionCol):
    K = IntParam("k", "The number of nearest neighbors.", 5,
                 ParamValidators.gt(0))


class KnnParams(KnnModelParams, HasLabelCol):
    pass


def _vote(idx, label_idx, num_classes):
    """Majority vote over neighbor indices; argmax → smallest label on
    ties (the reference's hash-map iteration order is unspecified there).
    The single tie-break rule shared by the XLA and pallas paths."""
    votes = jax.nn.one_hot(label_idx[idx], num_classes).sum(axis=1)
    return jnp.argmax(votes, axis=1)


@functools.lru_cache(maxsize=8)
def _build_knn_program(k: int, num_classes: int):
    @jax.jit
    def predict(x_test, x_train, norms_train, label_idx):
        # ‖x−t‖² = ‖x‖² − 2 x·tᵀ + ‖t‖² (KnnModel.java predictLabel)
        cross = x_test @ x_train.T
        d2 = (jnp.sum(x_test * x_test, axis=1, keepdims=True)
              - 2.0 * cross + norms_train[None, :])
        kk = min(k, x_train.shape[0])
        _, idx = jax.lax.top_k(-d2, kk)
        return _vote(idx, label_idx, num_classes)
    return predict


@functools.lru_cache(maxsize=8)
def _build_vote_program(num_classes: int):
    @jax.jit
    def vote(idx, label_idx):
        return _vote(idx, label_idx, num_classes)
    return vote


#: bound on the (chunk, n_train) distance block a single XLA predict call
#: may materialize in HBM (the pallas path never materializes it at all)
_MAX_DIST_ELEMS = 64 << 20

#: test rows per kernel call. The kernel's HBM operands are lane-padded to
#: 128: a narrow (rows, d) input and the (rows, k) index and distance
#: outputs each take rows x 512 bytes whatever d and k are — 1.5 GB at
#: this chunk, where the vendored 10M x 32 predict in ONE call asked for
#: 14.3 GB of a 16 GB chip (my chip run, PR 21)
_KERNEL_CHUNK_ROWS = 1 << 20


class KnnModel(Model, KnnModelParams):
    def __init__(self, features: Optional[np.ndarray] = None,
                 labels: Optional[np.ndarray] = None, **kwargs):
        super().__init__(**kwargs)
        self.features = None if features is None else np.asarray(features)
        self.labels = None if labels is None else np.asarray(labels)

    def transform(self, table: Table) -> Tuple[Table]:
        if self.features is None:
            raise ValueError("KnnModel has no model data")
        x = table.vectors(self.features_col)
        classes, label_idx = np.unique(self.labels, return_inverse=True)
        n, n_train = x.shape[0], self.features.shape[0]
        train = jnp.asarray(self.features, jnp.float32)
        label_idx_d = jnp.asarray(label_idx)

        from flink_ml_tpu.ops.pallas_kernels import (
            KNN_VMEM_BUDGET_BYTES,
            _knn_step_vmem_bytes,
            knn_topk_indices,
            pallas_supported,
        )
        # n_train is streamed over the kernel's second grid axis, so only
        # the per-step working set gates (d would have to reach thousands)
        if (pallas_supported() and _knn_step_vmem_bytes(
                train.shape[1], self.k) <= KNN_VMEM_BUDGET_BYTES):
            # fused distance+top-k kernel: the (n, n_train) matrix never
            # exists, even tile-wise, outside VMEM
            vote = _build_vote_program(len(classes))
            chunk = _KERNEL_CHUNK_ROWS

            def predict_chunk(xc):
                return vote(knn_topk_indices(xc, train, self.k), label_idx_d)

            self.last_execution_path = "pallas"
        else:
            # XLA path, memory-bounded: test rows in chunks so no
            # (chunk, n_train) block exceeds _MAX_DIST_ELEMS
            predict = _build_knn_program(self.k, len(classes))
            norms = jnp.sum(train * train, axis=1)
            chunk = max(1, min(n, _MAX_DIST_ELEMS // max(n_train, 1)))

            def predict_chunk(xc):
                return predict(xc, train, norms, label_idx_d)

            self.last_execution_path = "xla-chunked"
        parts = [np.asarray(predict_chunk(
            jnp.asarray(x[s:s + chunk], jnp.float32)))
            for s in range(0, n, chunk)]
        pred_idx = np.concatenate(parts) if parts else np.zeros(0, int)
        return (table.with_column(self.prediction_col, classes[pred_idx]),)

    def set_model_data(self, model_data: Table):
        self.features = model_data.vectors("packedFeatures", np.float64)
        self.labels = model_data.scalars("labels", np.float64)
        return self

    def get_model_data(self) -> Tuple[Table]:
        return (Table.from_columns(
            packedFeatures=np.asarray(self.features, np.float64),
            labels=np.asarray(self.labels, np.float64)),)

    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {
            "features": self.features, "labels": self.labels})

    def _load_extra(self, path: str, meta: dict) -> None:
        arrays = rw.load_model_arrays(path, "model")
        self.features, self.labels = arrays["features"], arrays["labels"]


class Knn(Estimator, KnnParams):
    """Trivial fit: the model IS the cached training data (ref: Knn.java)."""

    def fit(self, table: Table) -> KnnModel:
        model = KnnModel(features=table.vectors(self.features_col, np.float64),
                         labels=table.scalars(self.label_col, np.float64))
        return self.copy_params_to(model)
