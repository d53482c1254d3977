"""Param-driven random data generators.

Ref parity: flink-ml-benchmark/.../datagenerator/common/*.java —
DenseVectorGenerator, DenseVectorArrayGenerator, LabeledPointWithWeightGenerator
(featureArity/labelArity semantics, LabeledPointWithWeightGenerator.java:50-75),
RandomStringGenerator, RandomStringArrayGenerator, DoubleGenerator,
KMeansModelDataGenerator.

Numeric generators produce their columns ON DEVICE (jax.random, float32,
already sharded over the mesh's data axis) whenever the row count divides
the shard count — the generated table then flows into fit/transform without
ever crossing the host↔device link. The reference likewise generates data
inside the measured job (InputTableGenerator is a Flink source feeding the
benchmarked stage directly), so device-side generation is parity, not a
shortcut; string/ragged generators stay host-side by design (SURVEY.md §7).
"""

from __future__ import annotations

import functools

import numpy as np

from flink_ml_tpu.common.table import Table, as_dense_vector_column
from flink_ml_tpu.params.param import (
    ArrayArrayParam,
    IntParam,
    ParamValidators,
    WithParams,
)
from flink_ml_tpu.params.shared import HasSeed

_GENERATORS = {}


@functools.lru_cache(maxsize=None)
def _rand_program(shape, arity: int, sharding):
    import jax
    import jax.numpy as jnp

    def gen(key):
        u = jax.random.uniform(key, shape, jnp.float32)
        return jnp.floor(u * arity) if arity else u

    # the output keeps the device's default layout: an array pinned to
    # another one breaks every consumer loaded from the persistent compile
    # cache (a deserialized executable expects default parameter layouts
    # under jax 0.9 / libtpu 0.0.34 — PERF.md, PR 21)
    return jax.jit(gen, out_shardings=sharding)


def _device_random(seed: int, shape, arity: int = 0, stream: int = 0):
    """Uniform [0,1) (arity=0) or integer-valued floor(u·arity) column,
    generated directly sharded on the default mesh. ``stream`` decorrelates
    multiple columns drawn from one generator seed."""
    import jax

    from flink_ml_tpu.parallel.collective import _dim0_layout
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    mesh = default_mesh()
    _, sharding = _dim0_layout(mesh, data_axes(mesh), len(shape))
    key = jax.random.fold_in(jax.random.key(seed), stream)
    return _rand_program(tuple(shape), int(arity), sharding)(key)


# Below this table size host generation + one put wins: a tiny table is
# dispatch-latency-bound, while past it the float32 H2D transfer dominates
# and on-device generation removes it entirely.
_DEVICE_DATAGEN_MIN_BYTES = 8 << 20


def _code_dtype(k: int):
    """Narrowest integer dtype for codes in [0, k) — the shared ladder."""
    from flink_ml_tpu.common.functions import narrow_uint

    return narrow_uint(k)


def _codes_to_strings(ints: np.ndarray, k: int) -> np.ndarray:
    """Integer codes → fixed-width '<U' string array: one str() per
    DISTINCT value then one vectorized gather — a 10M-row column never
    pays 10M Python str() calls, and a sparse draw from a huge domain
    (k >> draws) only materializes the codes actually drawn."""
    if ints.size == 0:
        return np.zeros(ints.shape, dtype="<U1")
    if k > ints.size:
        uniq = np.unique(ints)
        strs = np.array([str(v) for v in uniq])
        return _string_gather(strs, np.searchsorted(uniq, ints))
    tokens = np.array([str(v) for v in range(k)])
    return _string_gather(tokens, ints)


def _string_gather(tokens: np.ndarray, ints: np.ndarray) -> np.ndarray:
    """``tokens[ints]`` through an integer view of the fixed-width string
    buffer: numpy's fancy indexing on '<U' dtypes copies element-wise and
    is ~25-40% slower than the same gather on the int64/int32 view — at
    the billion-token benchmark configs (10M rows × 100 tokens) that is
    seconds of measured datagen.

    The gather itself runs as chunked ``np.take(mode='clip', out=...)``
    into one preallocated buffer: at 1e9 tokens the one-shot fancy index
    measured 26 s on this page-fault-punishing host, the ~8M-element
    chunked take 5.6 s (the output chunk stays cache/TLB-resident).
    mode='clip' skips take's per-call bounds pass; codes come from
    rng.integers/searchsorted so they are in range by construction — and
    the one-time assert below makes that construction-time claim fail
    loudly if a future datagen change breaks it, instead of clip
    clamping to the last token and producing a silently wrong corpus
    (ADVICE r5 #5). One O(n) max over int codes, negligible next to the
    gather itself."""
    it = tokens.dtype.itemsize  # '<U' itemsize is 4·width: always %4 == 0
    unit, step = (np.int64, it // 8) if it % 8 == 0 else (np.int32, it // 4)
    tv = np.ascontiguousarray(tokens.view(unit).reshape(len(tokens), step))
    flat = ints.reshape(-1)
    assert flat.size == 0 or (int(flat.max()) < len(tokens)
                              and int(flat.min()) >= 0), (
        f"token codes out of range: [{flat.min()}, {flat.max()}] vs "
        f"{len(tokens)} tokens — clip would silently clamp these")
    out = np.empty((flat.shape[0], step), unit)
    chunk = 8 << 20
    if step == 1:
        # 1-D take is ~4x faster than the same take along axis 0 of a
        # (k, 1) table (measured 25 s vs 6 s at 1e9) — tokens of <= 8
        # bytes (every numeric-string benchmark corpus) hit this path
        tv1, out1 = tv.reshape(-1), out.reshape(-1)
        for lo in range(0, flat.shape[0], chunk):
            np.take(tv1, flat[lo:lo + chunk], mode="clip",
                    out=out1[lo:lo + chunk])
    else:
        for lo in range(0, flat.shape[0], chunk):
            np.take(tv, flat[lo:lo + chunk], axis=0, mode="clip",
                    out=out[lo:lo + chunk])
    return out.view(tokens.dtype).reshape(ints.shape)


def _use_device_gen(n: int, total_elems: int) -> bool:
    from flink_ml_tpu.parallel.mesh import data_shard_count, default_mesh

    return (total_elems * 4 >= _DEVICE_DATAGEN_MIN_BYTES
            and n > 0 and n % data_shard_count(default_mesh()) == 0)


def _register(cls):
    _GENERATORS[cls.__name__] = cls
    return cls


def resolve_generator(class_name: str):
    """Accepts our class name or the reference's fully-qualified Java name."""
    short = class_name.rsplit(".", 1)[-1]
    try:
        return _GENERATORS[short]
    except KeyError:
        raise ValueError(f"unknown data generator {class_name!r}; "
                         f"known: {sorted(_GENERATORS)}")


class InputTableGenerator(HasSeed):
    """Base: numValues rows, named columns (ref: InputTableGenerator.java)."""

    COL_NAMES = ArrayArrayParam(
        "colNames", "Column names of the generated tables.", None)
    NUM_VALUES = IntParam(
        "numValues", "Number of data rows to generate.", 10,
        ParamValidators.gt(0))

    def _rng(self):
        return np.random.default_rng(self.get_seed_or_default())

    def _col_names(self, table_idx=0):
        names = self.col_names
        if names is None:
            raise ValueError(f"{type(self).__name__} needs colNames")
        return list(names[table_idx])

    def get_data(self) -> Table:
        raise NotImplementedError


class HasVectorDim(WithParams):
    VECTOR_DIM = IntParam("vectorDim", "Dimension of generated vectors.", 1,
                          ParamValidators.gt(0))


class HasArraySize(WithParams):
    ARRAY_SIZE = IntParam("arraySize", "Size of generated arrays.", 1,
                          ParamValidators.gt(0))


class HasNumDistinctValues(WithParams):
    NUM_DISTINCT_VALUES = IntParam(
        "numDistinctValues", "Number of distinct values of the data.", 10,
        ParamValidators.gt(0))


@_register
class DenseVectorGenerator(InputTableGenerator, HasVectorDim):
    """Uniform [0,1) dense vectors (ref: DenseVectorGenerator.java:34-53)."""

    def get_data(self) -> Table:
        (name,) = self._col_names()
        n, d = self.num_values, self.vector_dim
        if _use_device_gen(n, n * d):
            return Table.from_columns(**{name: _device_random(
                self.get_seed_or_default(), (n, d))})
        values = self._rng().random((n, d), dtype=np.float64)
        # raw (n, d) array IS a vector column — no per-row objects
        return Table.from_columns(**{name: values})


@_register
class DenseVectorArrayGenerator(InputTableGenerator, HasVectorDim,
                                HasArraySize):
    def get_data(self) -> Table:
        rng = self._rng()
        (name,) = self._col_names()
        col = np.empty(self.num_values, dtype=object)
        for i in range(self.num_values):
            col[i] = [  # array of DenseVectors per row
                v for v in as_dense_vector_column(
                    rng.random((self.array_size, self.vector_dim)))]
        return Table.from_columns(**{name: col})


@_register
class LabeledPointWithWeightGenerator(InputTableGenerator, HasVectorDim):
    """Ref: LabeledPointWithWeightGenerator.java — featureArity/labelArity:
    0 → continuous double in [0,1); positive k → integer in [0, k)."""

    FEATURE_ARITY = IntParam(
        "featureArity", "Arity of each feature (0 = continuous).", 2,
        ParamValidators.gt_eq(0))
    LABEL_ARITY = IntParam(
        "labelArity", "Arity of label (0 = continuous).", 2,
        ParamValidators.gt_eq(0))

    def get_data(self) -> Table:
        n, d = self.num_values, self.vector_dim
        f_name, l_name, w_name = self._col_names()
        if _use_device_gen(n, n * (d + 2)):
            seed = self.get_seed_or_default()
            return Table.from_columns(**{
                f_name: _device_random(seed, (n, d), self.feature_arity, 0),
                l_name: _device_random(seed, (n,), self.label_arity, 1),
                w_name: _device_random(seed, (n,), 0, 2)})
        rng = self._rng()

        def values(arity, shape):
            if arity == 0:
                return rng.random(shape, dtype=np.float64)
            return np.floor(rng.random(shape) * arity)

        features = values(self.feature_arity, (n, d))
        label = values(self.label_arity, (n,))
        weight = rng.random(n, dtype=np.float64)
        return Table.from_columns(**{
            f_name: features, l_name: label, w_name: weight})


@_register
class RandomStringGenerator(InputTableGenerator, HasNumDistinctValues):
    """Strings drawn from numDistinctValues distinct tokens
    (ref: RandomStringGenerator.java)."""

    def get_data(self) -> Table:
        rng = self._rng()
        k = self.num_distinct_values
        cols = {name: _codes_to_strings(
                    rng.integers(0, k, self.num_values,
                                 dtype=_code_dtype(k)), k)
                for name in self._col_names()}
        return Table.from_columns(**cols)


@_register
class RandomStringArrayGenerator(InputTableGenerator, HasNumDistinctValues,
                                 HasArraySize):
    def get_data(self) -> Table:
        rng = self._rng()
        k = self.num_distinct_values
        # token-matrix representation: an (n, arraySize) fixed-width string
        # array IS a token-array column (row i = document i) — the
        # vectorized form the text ops' fast paths consume; the reference's
        # String[] rows stay available as the ragged object-column form
        cols = {name: _codes_to_strings(
                    rng.integers(0, k, (self.num_values, self.array_size),
                                 dtype=_code_dtype(k)), k)
                for name in self._col_names()}
        return Table.from_columns(**cols)


@_register
class DoubleGenerator(InputTableGenerator):
    """arity 0 → uniform [0,1) doubles; arity > 0 → random integers in
    [0, arity) as doubles (ref: DoubleGenerator.java:37-66)."""

    ARITY = IntParam("arity", "Arity of generated values.", 0,
                     ParamValidators.gt_eq(0))

    def get_data(self) -> Table:
        arity = self.ARITY
        names = self._col_names()
        n = self.num_values
        if _use_device_gen(n, n * len(names)):
            # same on-device policy as DenseVectorGenerator: big scalar
            # columns are generated sharded in HBM (f32, the dtype every
            # device consumer computes in) — the 100M-row Bucketizer
            # config stops shipping 400 MB over the host link; host
            # consumers (FeatureHasher, SQLTransformer) pay one
            # symmetric D2H instead of the device consumers' H2D
            seed = self.get_seed_or_default()
            return Table.from_columns(**{
                name: _device_random(seed, (n,), arity, stream)
                for stream, name in enumerate(names)})
        rng = self._rng()
        if arity > 0:
            cols = {name: rng.integers(0, arity, n).astype(np.float64)
                    for name in names}
        else:
            cols = {name: rng.random(n, dtype=np.float64)
                    for name in names}
        return Table.from_columns(**cols)


@_register
class LogisticRegressionModelDataGenerator(HasSeed, HasVectorDim):
    """Zero-initialized LR model data (coefficient vector + modelVersion 0)
    — the initial model the online trainer requires
    (OnlineLogisticRegression.java:440 setInitialModelData; its tests seed
    exactly this shape). The reference ships no online benchmark config, so
    this generator backs OUR onlinelogisticregression benchmark; zeros make
    the measured fit independent of the seed."""

    def get_data(self) -> Table:
        return Table.from_columns(
            coefficient=as_dense_vector_column(
                np.zeros((1, self.vector_dim))),
            modelVersion=np.asarray([0], np.int64))


@_register
class KnnModelDataGenerator(HasSeed, HasVectorDim, HasArraySize):
    """Random KNN model data: arraySize cached train points of vectorDim
    dims with integer labels (KnnModel.set_model_data schema:
    packedFeatures + labels). Backs OUR knn benchmark — the reference
    ships no KNN config; KnnModel.java predict is the matched surface."""

    LABEL_ARITY = IntParam("labelArity", "Number of distinct labels.", 2,
                           ParamValidators.gt(0))

    def get_data(self) -> Table:
        rng = np.random.default_rng(self.get_seed_or_default())
        n = self.array_size
        return Table.from_columns(
            packedFeatures=rng.random((n, self.vector_dim)),
            labels=np.floor(rng.random(n) * self.label_arity))


@_register
class KMeansModelDataGenerator(HasSeed, HasVectorDim, HasArraySize):
    """Random KMeans model data; arraySize = number of centroids
    (ref: datagenerator/clustering/KMeansModelDataGenerator.java)."""

    def get_data(self) -> Table:
        rng = np.random.default_rng(self.get_seed_or_default())
        k = self.array_size
        centroids = rng.random((k, self.vector_dim))
        return Table.from_columns(
            centroid=as_dense_vector_column(centroids),
            weight=np.ones(k))
