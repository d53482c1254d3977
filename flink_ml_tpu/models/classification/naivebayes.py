"""Multinomial naive Bayes over categorical feature values.

Ref parity: flink-ml-lib classification/naivebayes/{NaiveBayes.java:59,
NaiveBayesModel.java, NaiveBayesModelData.java}. For rows ``i < n``,
features ``j < d``, labels ``l`` and values ``v`` (GenerateModelFunction):

- ``count[j, l, v] = sum_i [y_i = l] [x_ij = v]`` and
  ``doc[l] = sum_i [y_i = l]``;
- ``theta[l, j, v] = log(count[j, l, v] + s) - log(doc[l] + s * V_j)`` with
  ``V_j`` the number of distinct values present in feature ``j`` and ``s``
  the ``smoothing``;
- ``pi[l] = log(doc[l] * d + s) - log(n * d + L * s)``;
- predict: ``argmax_l pi[l] + sum_j theta[l, j, x_j]``
  (NaiveBayesModel.calculateProb).

The counts are the whole fit. A device-resident table of whole-number
values takes them in one exact pass on the MXU (``ops/contingency.py``:
path ``mxu-counts``); any other table on the host (``host-counts``), to
the same model: both hand integer counts to one float64 ``_finalize``.

Deviations (documented, docs/deviations.md): the model data is arrays —
``theta (L, d, V)``, ``values (d, V)`` and ``floors (L, d)`` beside
``piArray`` and ``labels`` — where upstream holds
``Map<Double, Double>[][]``; and a feature value unseen at fit time
scores the smoothed floor ``log(s) - log(doc[l] + s * V_j)`` at predict
time where the reference throws a NullPointerException.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

from flink_ml_tpu.api.stage import Estimator, Model
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.params.param import FloatParam, ParamValidators, StringParam
from flink_ml_tpu.params.shared import (
    HasFeaturesCol,
    HasPredictionCol,
)
from flink_ml_tpu.params.shared import HasLabelCol, HasWeightCol
from flink_ml_tpu.utils import io as rw


class NaiveBayesModelParams(HasFeaturesCol, HasPredictionCol):
    MODEL_TYPE = StringParam(
        "modelType", "The model type.", "multinomial",
        ParamValidators.in_array("multinomial"))


class NaiveBayesParams(NaiveBayesModelParams, HasLabelCol, HasWeightCol):
    SMOOTHING = FloatParam("smoothing", "The smoothing parameter.", 1.0,
                           ParamValidators.gt_eq(0.0))


class NaiveBayesModel(Model, NaiveBayesModelParams):
    """``theta[l, j, k]`` is the log-probability of feature ``j``'s ``k``-th
    value ``values[j, k]`` under label ``labels[l]``. A feature's values
    are in ascending order; a feature with fewer than ``V`` of them is
    padded with NaN, and ``theta`` holds the floor there."""

    def __init__(self, theta=None, values=None, pi=None, labels=None,
                 floors=None, **kwargs):
        super().__init__(**kwargs)

        def f64(a):
            return None if a is None else np.asarray(a, np.float64)

        self.theta = f64(theta)      # (L, d, V)
        self.values = f64(values)    # (d, V)
        self.pi = f64(pi)            # (L,)
        self.labels = f64(labels)    # (L,)
        self.floors = f64(floors)    # (L, d)

    def transform(self, table: Table) -> Tuple[Table]:
        if self.theta is None:
            raise ValueError("NaiveBayesModel has no model data")
        x = table.vectors(self.features_col, np.float64)
        n, d = x.shape
        width = self.values.shape[1]
        probs = np.tile(self.pi, (n, 1))
        for j in range(d):
            # the feature's values are sorted and NaN sorts last: one
            # binary search a row, then one gather for all labels
            at = np.minimum(np.searchsorted(self.values[j], x[:, j]),
                            width - 1)
            seen = self.values[j, at] == x[:, j]
            probs += np.where(seen[:, None], self.theta[:, j, at].T,
                              self.floors[:, j])
        pred = self.labels[np.argmax(probs, axis=1)]
        return (table.with_column(self.prediction_col, pred),)

    _COLUMNS = {"theta": "theta", "values": "values", "piArray": "pi",
                "labels": "labels", "floors": "floors"}

    def set_model_data(self, model_data: Table):
        for column, attr in self._COLUMNS.items():
            setattr(self, attr, np.asarray(model_data.column(column),
                                           np.float64)[0])
        return self

    def get_model_data(self) -> Tuple[Table]:
        """One row of numeric columns: ``theta (L, d, V)``, ``values
        (d, V)``, ``piArray (L,)``, ``labels (L,)``, ``floors (L, d)``."""
        return (Table.from_columns(**{
            column: getattr(self, attr)[None]
            for column, attr in self._COLUMNS.items()}),)

    def _save_extra(self, path: str) -> None:
        rw.save_model_arrays(path, "model", {
            attr: getattr(self, attr) for attr in self._COLUMNS.values()})

    def _load_extra(self, path: str, meta: dict) -> None:
        if os.path.exists(os.path.join(path, "data", "model.npz")):
            arrays = rw.load_model_arrays(path, "model")
        else:   # saved before the model data was arrays: dicts, as JSON
            arrays = _arrays_of_dicts(rw.load_model_json(path, "model"))
        for attr in self._COLUMNS.values():
            setattr(self, attr, np.asarray(arrays[attr], np.float64))


def _arrays_of_dicts(data: dict) -> dict:
    """The array model data of a model saved in the dict form:
    ``data["theta"][l][j]`` maps a value (as a string) to its
    log-probability."""
    floors = np.asarray(data["floors"], np.float64)
    num_labels, d = floors.shape
    per_feature = [sorted(float(v) for v in data["theta"][0][j])
                   for j in range(d)]
    width = max(map(len, per_feature))
    values = np.full((d, width), np.nan)
    theta = np.repeat(floors[:, :, None], width, axis=2)
    for j, vals in enumerate(per_feature):
        values[j, :len(vals)] = vals
        for li in range(num_labels):
            by_value = {float(v): lp
                        for v, lp in data["theta"][li][j].items()}
            theta[li, j, :len(vals)] = [by_value[v] for v in vals]
    return {"theta": theta, "values": values, "pi": data["pi"],
            "labels": data["labels"], "floors": floors}


#: the device path counts values and labels that are whole numbers in
#: [0, _MAX_DEVICE_ARITY): its one-hot has a row for each
_MAX_DEVICE_ARITY = 4096
#: and a count tensor of at most this many entries (int32 on the device)
_MAX_DEVICE_COUNTS = 50_000_000
#: rows of every shard a fit looks at for its guess at the table's range
_LOOK_ROWS = 4096


class NaiveBayes(Estimator, NaiveBayesParams):
    def _finalize(self, counts, n, values=None, labels=None
                  ) -> "NaiveBayesModel":
        """The model from integer counts — the single home of the
        smoothing, floor and pi math, in float64, shared by the host and
        device counting paths. ``counts`` is ``(d, L, V)``; ``labels`` is
        ``(L,)`` and ``values`` ``(d, V)``: feature ``j``'s distinct values
        in ascending order, NaN from its ``V_j``-th on, where ``counts`` is
        0. Without them the counts are over every candidate label ``0 ..
        L - 1`` and value ``0 .. V - 1``, which may be sparse: the ones
        present are kept, in ascending order."""
        with tracer.span("nb.finalize"):
            if labels is None:
                present = np.nonzero(counts[0].sum(axis=1) > 0)[0]
                labels, counts = present.astype(np.float64), counts[:, present]
                seen = counts.sum(axis=1) > 0                      # (d, V)
                order = np.argsort(~seen, axis=1, kind="stable")
                order = order[:, :max(1, int(seen.sum(axis=1).max()))]
                values = np.where(np.take_along_axis(seen, order, axis=1),
                                  order.astype(np.float64), np.nan)
                counts = np.take_along_axis(counts, order[:, None, :], axis=2)
            s = self.smoothing
            d, num_labels, _ = counts.shape
            doc_counts = counts[0].sum(axis=1).astype(np.float64)
            distinct = np.sum(~np.isnan(values), axis=1)          # V_j
            with np.errstate(divide="ignore"):
                denom = np.log(doc_counts[:, None]
                               + s * distinct[None, :])           # (L, d)
                theta = (np.log(counts.transpose(1, 0, 2) + s)
                         - denom[:, :, None])
                floors = np.log(s) - denom
                pi = (np.log(doc_counts * d + s)
                      - np.log(n * d + num_labels * s))
        with tracer.span("fit.model"):
            model = NaiveBayesModel(theta=theta, values=values, pi=pi,
                                    labels=labels, floors=floors)
            return self.copy_params_to(model)

    def _count_on_device(self, x, y) -> Optional[np.ndarray]:
        """``counts[j, l, v]`` (int64) over every candidate label ``l < L``
        and value ``v < V`` of a device-resident table, or None when the
        table does not qualify (an entry that is not a whole number, a
        negative one, too wide a range): the caller counts on the host.

        A fit knows nothing of its table: upstream's job is one fit on a
        table it has not seen. The first rows of every shard give a guess
        at ``L`` and ``V``; one exact pass counts with it; and the counts
        themselves say whether the guess held, because an entry out of
        range is counted nowhere: they add up to ``n * d`` or the table
        holds something the first rows did not show. Only then is every
        row looked at (a pass of its own: 14 ms at 12M x 100 where the
        counting pass takes 11, PERF.md section 6, PR 33) and the table
        counted again, or handed to the host."""
        from flink_ml_tpu.iteration.iteration import read_boundary
        from flink_ml_tpu.ops import contingency
        from flink_ml_tpu.ops.pallas_kernels import (
            counts_kernel_fits, pallas_supported)
        from flink_ml_tpu.parallel import update_sharding as _upd
        from flink_ml_tpu.parallel.collective import ensure_on_mesh
        from flink_ml_tpu.parallel.mesh import (
            data_axes, data_shard_count, default_mesh)

        n, d = x.shape
        mesh = default_mesh()
        axes = data_axes(mesh)
        with tracer.span("nb.place_inputs"):
            xs, _ = ensure_on_mesh(mesh, x, axes, np.float32)
            ys, _ = ensure_on_mesh(mesh, y, axes, np.float32)

        def look(rows):
            """``(L, V)`` by the first ``rows`` rows of every shard (None:
            by every row), or None where they rule the device path out."""
            with tracer.span("nb.check", rows=min(rows or n, n)):
                lo, x_hi, y_hi, whole = np.asarray(read_boundary(
                    contingency.look_program(mesh, rows)(xs, ys)),
                    np.float64)
            if not whole or lo < 0 or max(x_hi, y_hi) >= _MAX_DEVICE_ARITY:
                return None
            num_labels, num_values = int(y_hi) + 1, int(x_hi) + 1
            if d * num_labels * num_values > _MAX_DEVICE_COUNTS:
                return None
            return num_labels, num_values

        def count(num_labels, num_values, passes):
            with tracer.span("nb.build_program"):
                use_kernel = pallas_supported() and counts_kernel_fits(
                    d, num_labels, num_values)
                program = contingency.counts_program(
                    mesh, num_labels, num_values, use_kernel)
            with tracer.span("nb.launch", path="mxu-counts", rows=n, d=d,
                             labels=num_labels, values=num_values,
                             passes=passes,
                             program="pallas" if use_kernel else "xla"):
                # (the row count rides the call as a host scalar)
                counted = program(xs, ys, np.int32(n))
            _upd.record_state_bytes("NaiveBayes", (counted,),
                                    data_shard_count(mesh), False)
            with tracer.span("nb.fetch"):
                # the blocking read, where the wait for the pass falls
                return np.asarray(read_boundary((counted,))[0],
                                  np.int64).transpose(2, 1, 0)

        shape = look(_LOOK_ROWS)
        if shape is None:
            return None
        counts = count(*shape, passes=1)
        if counts.sum() != n * d:
            shape = look(None)
            if shape is None:
                return None
            counts = count(*shape, passes=3)
        self.last_execution_path = "mxu-counts"
        return counts

    def _fit_host(self, x, y) -> "NaiveBayesModel":
        """The host counting path: any values, one ``unique`` a feature."""
        with tracer.span("nb.launch", path="host-counts", rows=x.shape[0],
                         d=x.shape[1], passes=1):
            n, d = x.shape
            labels, y_idx = np.unique(y, return_inverse=True)
            num_labels = len(labels)
            per_feature = []
            for j in range(d):
                vals, codes = np.unique(x[:, j], return_inverse=True)
                per_feature.append((vals, np.bincount(
                    y_idx * len(vals) + codes,
                    minlength=num_labels * len(vals)).reshape(
                        num_labels, len(vals))))
            width = max(len(vals) for vals, _ in per_feature)
            values = np.full((d, width), np.nan)
            counts = np.zeros((d, num_labels, width), np.int64)
            for j, (vals, by_label) in enumerate(per_feature):
                values[j, :len(vals)] = vals
                counts[j, :, :len(vals)] = by_label
        self.last_execution_path = "host-counts"
        return self._finalize(counts, n, values, labels)

    def fit(self, table: Table) -> NaiveBayesModel:
        from flink_ml_tpu.ops import columnar

        xd, xp = columnar.fit_vectors(table, self.features_col)
        ycol = table.column(self.label_col)
        if xp is not np and not isinstance(ycol, np.ndarray):
            counts = self._count_on_device(xd, ycol)
            if counts is not None:
                return self._finalize(counts, xd.shape[0])
        x = xd if xp is np else table.vectors(self.features_col, np.float64)
        return self._fit_host(x, table.scalars(self.label_col, np.float64))
