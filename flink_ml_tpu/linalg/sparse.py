"""Sparse vector columns: host CSR, and the fixed-width device column.

Ref parity: the reference trains/predicts on `SparseVector` input without
densifying — BLAS.hDot (flink-ml-servable-core/.../linalg/BLAS.java:78)
and the sparse gradient branch of FTRL
(OnlineLogisticRegression.java:364-388). A HashingTF/FeatureHasher column
at the default 2^18 dims would blow up memory if stacked dense
(10M rows × 262144 × 8B ≈ 20 TB), so neither form ever densifies:

- ``CsrVectorColumn``: one scipy CSR matrix for the whole column, matvecs
  through scipy's C kernels, per-coordinate scatters via np.bincount. What
  ``HashingTF``/``FeatureHasher``/``CountVectorizer`` produce, and what the
  host stages (FTRL, online LR, the feature stages) take, in float64.
- ``DeviceSparseColumn`` (:func:`device_sparse_column`): ``k`` entries a
  row as two ``(n, k)`` device arrays, ids and values, kept as they lie
  (row-sharded, the TPU's column-major layout, no copy). The linear models'
  SGD fit and their predict take it on the device
  (``SGD.optimize_sparse``, ``models/common.py::predict_dots``); every host
  consumer reaches it through its one off-ramp, :meth:`~DeviceSparseColumn.
  to_csr`.

docs/deviations.md is not affected: sparse semantics match the reference
exactly (two entries of a row in one bucket add, as a CSR sum adds them).
"""

from __future__ import annotations

import functools

import numpy as np

from flink_ml_tpu.linalg.vectors import SparseVector, Vector
from flink_ml_tpu.observability.tracing import cold_build, tracer


class CsrVectorColumn:
    """A sparse vector column stored as ONE scipy CSR matrix.

    The producer-side twin of ``column_to_csr``: ops that compute a whole
    sparse output at once (HashingTF/FeatureHasher/CountVectorizer at
    n=10M rows) hand their (indptr, indices, data) arrays straight to the
    table instead of looping 10M ``SparseVector`` constructions — and
    sparse trainers (``features_matrix``) get the CSR back without
    re-assembling it. Row access (``col[i]``, iteration) materializes
    ``SparseVector`` views lazily, so per-row consumers (BLAS, the
    reference's ``instanceof SparseVector`` dispatch) see the same objects
    an object column would hold.
    """

    is_csr_vector_column = True  # duck-type marker (Table, is_sparse_column)
    #: quacks like numpy's object-column dtype for code that branches on it
    dtype = np.dtype(object)
    ndim = 1

    def __init__(self, matrix):
        self.matrix = matrix.tocsr()

    def __len__(self):
        return self.matrix.shape[0]

    @property
    def shape(self):
        return (self.matrix.shape[0],)

    def _row(self, i: int) -> SparseVector:
        m = self.matrix
        lo, hi = m.indptr[i], m.indptr[i + 1]
        return SparseVector._unchecked(
            m.shape[1], m.indices[lo:hi].astype(np.int64),
            m.data[lo:hi].astype(np.float64))

    def __getitem__(self, key):
        if isinstance(key, slice):
            return CsrVectorColumn(self.matrix[key])
        if np.ndim(key) == 0:
            i = int(key)
            n = self.matrix.shape[0]
            if i < 0:
                i += n
            if not 0 <= i < n:
                raise IndexError(
                    f"row {key} out of bounds for column of {n} rows")
            return self._row(i)
        return CsrVectorColumn(self.matrix[np.asarray(key)])

    def __iter__(self):
        for i in range(len(self)):
            yield self._row(i)

    def to_csr(self):
        return self.matrix

    def to_object_column(self) -> np.ndarray:
        return csr_to_column(self.matrix)

    def to_dense(self, dtype=np.float64) -> np.ndarray:
        # narrow BEFORE densifying: no full-size float64 temporary
        m = self.matrix if self.matrix.dtype == dtype \
            else self.matrix.astype(dtype)
        return m.toarray()

    def concat(self, other) -> "CsrVectorColumn":
        import scipy.sparse as sp

        o = other.matrix if isinstance(other, CsrVectorColumn) \
            else column_to_csr(other)
        return CsrVectorColumn(sp.vstack([self.matrix, o], format="csr"))

    def concat_after(self, other) -> "CsrVectorColumn":
        """``other`` (object/dense vector column) followed by this column —
        the right-hand-side twin of ``concat``, keeping CSR backing however
        the operands are ordered."""
        import scipy.sparse as sp

        return CsrVectorColumn(
            sp.vstack([column_to_csr(other), self.matrix], format="csr"))

    def __repr__(self):
        return (f"CsrVectorColumn({self.matrix.shape[0]} rows, "
                f"size={self.matrix.shape[1]}, nnz={self.matrix.nnz})")


def is_csr_column(col) -> bool:
    return getattr(col, "is_csr_vector_column", False)


class DeviceSparseColumn:
    """A sparse vector column as it lies on the device: ``k`` entries a
    row, ``ids`` ``(n, k)`` int32 and ``values`` ``(n, k)`` float32, two
    ``jax.Array`` objects kept exactly as they were handed over (their
    sharding, their layout, no copy). A padding entry has value 0 and any id in
    ``[0, size)``; two entries of a row in one bucket add.

    ``hot`` and ``narrow`` are the index :func:`device_sparse_column` made:
    ``hot`` is ``((entry, bucket), ...)`` for each entry position whose id
    is one bucket on every row (a numeric field, which ``FeatureHasher``
    hashes by its name); ``narrow`` is ``((entry, slots), ...)`` for each
    other position whose ids lie in at most ``sparse_window.NARROW_MAX``
    buckets over the whole table, its dictionary the first ``slots`` of its
    row of ``dicts``, a ``(k, NARROW_MAX)`` int32 device array (``NARROW_MAX``
    up to a multiple of ``CHUNK``) of each position's buckets, padded with
    -1. The device fit sums hot entries as
    columns and takes narrow ones by their dictionaries instead of
    gathering and scattering them (``ops/sparse_window.py``). A row subset
    keeps the index: its ids are a subset.

    Row slices and takes stay on the device. Nothing here copies the
    column to the host but :meth:`to_csr`, the explicit off-ramp: an
    implicit ``np.asarray`` raises."""

    #: duck-type marker (Table, is_sparse_column)
    is_device_sparse_column = True

    def __init__(self, ids, values, size: int, hot=(), narrow=(),
                 dicts=None):
        if narrow and dicts is None:
            raise ValueError("a narrow index comes with its dictionaries")
        self.ids, self.values = ids, values
        self.size = int(size)
        self.hot = tuple(hot)
        self.narrow, self.dicts = tuple(narrow), dicts

    def __len__(self):
        return self.ids.shape[0]

    @property
    def shape(self):
        """``(rows, size)``: the matrix the column stands for."""
        return (self.ids.shape[0], self.size)

    @property
    def entries(self) -> int:
        """``k``, the entries a row."""
        return self.ids.shape[1]

    def __array__(self, *args, **kwargs):
        raise TypeError("a device sparse column is not copied to the host "
                        "implicitly: to_csr() is its off-ramp")

    def _rows(self, take) -> "DeviceSparseColumn":
        return DeviceSparseColumn(take(self.ids), take(self.values),
                                  self.size, self.hot, self.narrow,
                                  self.dicts)

    def __getitem__(self, key):
        from flink_ml_tpu.ops import columnar

        if isinstance(key, slice):
            start, stop, step = key.indices(len(self))
            if step == 1:
                size = max(0, stop - start)
                return self._rows(
                    lambda a: columnar.dynamic_rows(a, start, size))
            key = np.arange(start, stop, step)
        rows = np.asarray(key)
        return self._rows(lambda a: a[rows])

    def to_csr(self):
        """The column on the host as one canonical scipy CSR matrix,
        float64: duplicate ids of a row summed, padding entries dropped."""
        import scipy.sparse as sp

        ids = np.array(self.ids)  # a copy: the CSR sorts it in place
        n, k = ids.shape
        m = sp.csr_matrix(
            (np.asarray(self.values, np.float64).ravel(), ids.ravel(),
             np.arange(0, n * k + 1, k)), shape=(n, self.size))
        m.sum_duplicates()
        m.eliminate_zeros()
        return m

    def to_object_column(self) -> np.ndarray:
        return csr_to_column(self.to_csr())

    def to_dense(self, dtype=np.float64) -> np.ndarray:
        return self.to_csr().astype(dtype).toarray()

    def __repr__(self):
        return (f"DeviceSparseColumn({len(self)} rows, size={self.size}, "
                f"entries={self.entries}, hot={len(self.hot)}, "
                f"narrow={len(self.narrow)})")


def is_device_sparse_column(col) -> bool:
    return getattr(col, "is_device_sparse_column", False)


#: rows at the head of a column whose ids propose each position's
#: dictionary; every row of the table is then checked against it
SAMPLE_ROWS = 1 << 20
#: dictionary slots compared with a position's ids at a time in the check
CHUNK = 128


def dict_slots(buckets: int) -> int:
    """A dictionary of ``buckets`` as the round program takes it: to the
    sublane tile of 8."""
    return -(-buckets // 8) * 8


@functools.lru_cache(maxsize=None)
@cold_build("sparse_index")
def _index_program():
    """``index(ids) -> (min, max, same, first, narrow, buckets, dicts)``:
    the range of the ids; per entry position whether every row holds the
    first row's id (``same``, ``first``); and per position whether its ids
    lie in at most ``NARROW_MAX`` buckets over the whole table
    (``narrow``), how many buckets its sample holds (``buckets``) and those
    buckets, ascending and padded with -1 (``dicts``, ``(k,
    NARROW_MAX)``, the width up to a multiple of ``CHUNK``). A position's candidates are the distinct ids of the
    column's first ``SAMPLE_ROWS`` rows (sorted on the device); it is
    narrow only if every row's id is one of them, counted by comparing each
    row with each candidate. Hot positions are not narrow."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.ops.sparse_window import NARROW_MAX

    width = -(-NARROW_MAX // CHUNK) * CHUNK

    def sparse_index(ids):
        n, k = ids.shape
        first = ids[0]
        same = jnp.all(ids == first[None, :], axis=0)
        ordered = jax.lax.sort(ids[:min(n, SAMPLE_ROWS)].T, dimension=1)
        new = jnp.concatenate([jnp.ones((k, 1), bool),
                               ordered[:, 1:] != ordered[:, :-1]], axis=1)
        buckets = jnp.sum(new, axis=1, dtype=jnp.int32)
        # slot s holds the ids' (s + 1)-th distinct value: where the count
        # of distinct values so far reaches s + 1
        slot = jnp.arange(1, width + 1, dtype=jnp.int32)
        at = jax.vmap(lambda seen: jnp.searchsorted(seen, slot))(
            jnp.cumsum(new, axis=1, dtype=jnp.int32))
        dicts = jnp.where(slot[None, :] <= buckets[:, None],
                          jnp.take_along_axis(
                              ordered, jnp.minimum(at, ordered.shape[1] - 1),
                              axis=1), -1)
        candidate = jnp.logical_and(buckets <= NARROW_MAX,
                                    jnp.logical_not(same))
        # the check, one chunk of one candidate's dictionary a step: step
        # t takes chunk c of position j, read from the ids as they lie
        trips = jnp.where(candidate, -(-buckets // CHUNK), 0)
        ends = jnp.cumsum(trips)
        lanes = ids.T

        def chunk(t, seen):
            j = jnp.sum(ends <= t)
            c = t - (ends[j] - trips[j])
            row = jax.lax.dynamic_index_in_dim(lanes, j, keepdims=False)
            d = jax.lax.dynamic_slice(dicts, (j, c * CHUNK), (1, CHUNK))
            hits = jnp.sum(row[None, :] == d[0][:, None], dtype=jnp.int32)
            return seen + jnp.where(jnp.arange(k) == j, hits, 0)

        matched = jax.lax.fori_loop(0, ends[-1], chunk,
                                    jnp.zeros((k,), jnp.int32))
        narrow = jnp.logical_and(candidate, matched == n)
        return (jnp.min(ids), jnp.max(ids), same, first, narrow, buckets,
                dicts)

    return jax.jit(sparse_index)


def device_sparse_column(ids, values, size: int) -> DeviceSparseColumn:
    """The one entry for a sparse column made on the device: ``ids`` and
    ``values``, ``(n, k)`` ``jax.Array`` objects of int32 and float32, and
    the vectors' ``size``. The arrays are kept as they are; one program reads
    the ids, refuses an id outside ``[0, size)`` and finds the entry
    positions that hold one bucket on every row (``DeviceSparseColumn.
    hot``) and those that hold few (``narrow``, with their dictionaries,
    which stay on the device, replicated where the ids lie), a few numbers
    to the host.

    Both indexes key on entry positions, so they pay only where a column
    keeps one field at each position of every row, as the click-through
    benchmark's generator (``benchmarks/harness/generators/
    CriteoHashedGenerator.py``) lays its hashed fields. A column whose entries are sorted by bucket and merged, as
    ``FeatureHasher``'s CSR rows are, holds no position of one field: no
    position is hot or narrow there, and every entry is gathered and
    scattered."""
    import jax

    if not (isinstance(ids, jax.Array) and isinstance(values, jax.Array)):
        raise TypeError("device_sparse_column takes two jax.Arrays; a host "
                        "sparse column is a CsrVectorColumn")
    if ids.ndim != 2 or ids.shape != values.shape or not ids.shape[1]:
        raise ValueError(f"ids {ids.shape} and values {values.shape} must "
                         f"be one (rows, entries) shape, entries > 0")
    if ids.dtype != np.int32 or values.dtype != np.float32:
        raise TypeError(f"ids must be int32 and values float32, not "
                        f"{ids.dtype} and {values.dtype}")
    size = int(size)
    hot, narrow, dicts = (), (), None
    if ids.shape[0]:
        with tracer.span("sparse.index", rows=ids.shape[0],
                         entries=ids.shape[1]) as sp:
            *found, dicts = _index_program()(ids)
            lo, hi, same, first, is_narrow, buckets = jax.device_get(found)
            sp.set_attribute("narrow", int(np.sum(is_narrow)))
        if lo < 0 or hi >= size:
            raise ValueError(f"ids lie in [{lo}, {hi}], outside the "
                             f"column's [0, {size})")
        hot = tuple((int(j), int(first[j])) for j in np.flatnonzero(same))
        narrow = tuple((int(j), dict_slots(int(buckets[j])))
                       for j in np.flatnonzero(is_narrow))
        if not narrow:
            dicts = None
        elif isinstance(ids.sharding, jax.sharding.NamedSharding):
            # whole on every device of the ids' mesh, as the fit takes them
            from flink_ml_tpu.parallel.collective import replicate

            dicts = replicate(ids.sharding.mesh, dicts)
    return DeviceSparseColumn(ids, values, size, hot, narrow, dicts)


def column_moments(m):
    """Per-column (mean, centered sum of squares, stored-count) of a CSR
    matrix in O(nnz), TWO-PASS (cancellation-stable): implicit zeros
    contribute (n − nnz_col)·mean² to the centered sum. Callers needing
    the reference's one-pass Σx²−n·mean² parity (StandardScaler) should
    NOT use this — that formula is a documented parity choice, this one
    is the numerically stable default."""
    n = m.shape[0]
    mean = np.asarray(m.sum(axis=0)).ravel() / max(n, 1)
    centered = m.data - mean[m.indices]
    nnz_col = np.asarray(m.getnnz(axis=0)).ravel()
    varsum = (np.bincount(m.indices, weights=centered * centered,
                          minlength=m.shape[1])
              + (n - nnz_col) * mean * mean)
    return mean, varsum, nnz_col


def build_csr_column(n: int, size: int, sorted_row_ids, col_idx,
                     values) -> CsrVectorColumn:
    """Row-major (row, column, value) triples → a CSR-backed column.

    ``sorted_row_ids`` must be ascending. O(n) searchsorted + zero copies:
    the triples ARE the CSR buffers — no per-row SparseVector loop."""
    import scipy.sparse as sp

    indptr = np.searchsorted(sorted_row_ids,
                             np.arange(n + 1, dtype=np.int64))
    return CsrVectorColumn(sp.csr_matrix(
        (np.asarray(values, np.float64), np.asarray(col_idx, np.int64),
         indptr), shape=(n, size)))


def is_sparse_column(col) -> bool:
    """True for a CSR-backed column, a device sparse column, or an object
    column holding at least one SparseVector row.

    The reference dispatches per row (``instanceof SparseVector``,
    OnlineLogisticRegression.java:375); a column with any sparse row takes
    the CSR path here — the scan short-circuits at the first sparse row.
    """
    if is_csr_column(col) or is_device_sparse_column(col):
        return True
    return (getattr(col, "dtype", None) == object and len(col) > 0
            and isinstance(col[0], Vector)
            and any(isinstance(v, SparseVector) for v in col))


def _row_parts(v):
    if isinstance(v, SparseVector):
        return v.indices, v.values
    arr = v.to_array() if isinstance(v, Vector) else np.asarray(v)
    return np.arange(arr.shape[0], dtype=np.int64), arr


def column_to_csr(col, dtype=np.float64):
    """Object column of Vectors → one scipy CSR matrix (n, size).

    One concatenate over the per-row index/value arrays; no per-element
    Python beyond the row loop the column already implies. Dense rows in a
    mixed column become fully-present sparse rows (every coordinate
    listed), so their gradient contribution matches the reference's dense
    branch; their FTRL weightSum contribution uses the row weight at every
    coordinate (the reference adds 1.0 — see docs/deviations.md only if a
    weighted mixed column ever matters; unweighted they coincide). Row
    sizes must agree; a mismatch raises instead of silently scattering out
    of bounds.
    """
    import scipy.sparse as sp

    if is_csr_column(col) or is_device_sparse_column(col):
        m = col.to_csr()
        return m if m.dtype == dtype else m.astype(dtype)

    n = len(col)
    parts = [_row_parts(v) for v in col]
    size = int(col[0].size if isinstance(col[0], Vector)
               else len(parts[0][1]))
    for i, v in enumerate(col):
        vsize = int(v.size if isinstance(v, Vector) else len(parts[i][1]))
        if vsize != size:
            raise ValueError(
                f"row {i} has size {vsize}, expected {size} (ragged vector "
                "column cannot form a CSR batch)")
    nnz = np.fromiter((len(p[0]) for p in parts), np.int64, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(nnz, out=indptr[1:])
    if indptr[-1]:
        indices = np.concatenate([p[0] for p in parts])
        data = np.concatenate([p[1] for p in parts]).astype(dtype)
    else:
        indices = np.zeros(0, np.int64)
        data = np.zeros(0, dtype)
    return sp.csr_matrix((data, indices, indptr), shape=(n, size))


def csr_to_column(matrix) -> np.ndarray:
    """CSR matrix → object column of SparseVectors (the inverse off-ramp)."""
    m = matrix.tocsr()
    n, size = m.shape
    out = np.empty(n, dtype=object)
    for i in range(n):
        lo, hi = m.indptr[i], m.indptr[i + 1]
        out[i] = SparseVector._unchecked(
            size, m.indices[lo:hi].astype(np.int64),
            m.data[lo:hi].astype(np.float64))
    return out


def features_matrix(table, col_name: str, dtype=np.float32,
                    device_sparse: bool = False):
    """Table column → dense (n, d) array OR scipy CSR, preserving sparsity.

    The shared Table→trainer boundary for fits/predicts that support both
    representations (linear models, FTRL). ``dtype`` applies to the dense
    branch only; the CSR branch is always float64 — its math runs on host
    where float64 is free and matches the reference's double precision.

    A :class:`DeviceSparseColumn` comes back as it lies where the caller
    takes it on the device (``device_sparse``: the linear models' SGD fit
    and predict), else through its host off-ramp as the CSR branch: it
    never reaches a host trainer unconverted, nor a device one converted.
    """
    col = table.column(col_name)
    if device_sparse and is_device_sparse_column(col):
        return col
    if is_sparse_column(col):
        return column_to_csr(col, dtype=np.float64)
    return table.vectors(col_name, dtype)


def is_csr(x) -> bool:
    import scipy.sparse as sp

    return sp.issparse(x)
