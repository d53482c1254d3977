"""Host-side columnar Table.

The reference's API boundary is the Flink ``Table`` (lazy dataflow). On TPU the
equivalent boundary is a host-resident columnar batch: numeric columns are
numpy arrays ready to ship to device; string/object columns stay host-side
(XLA-hostile data is handled on host by design, see SURVEY.md §7 "Ragged/
sparse ETL ops"). Bounded tables are materialized; unbounded streams are
modeled by ``flink_ml_tpu.iteration.streaming.StreamTable`` (an iterator of
Tables), mirroring the bounded/unbounded split of the reference.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence

import numpy as np

from flink_ml_tpu.linalg.vectors import DenseVector, Vector, stack_vectors


def _is_device_column(values) -> bool:
    """A jax.Array column (device-resident, possibly sharded) — kept as-is so
    chained device stages hand buffers to each other without a host
    round-trip (see flink_ml_tpu.ops.columnar). Duck-typed to avoid
    importing jax here."""
    return (not isinstance(values, np.ndarray)
            and hasattr(values, "ndim") and hasattr(values, "dtype")
            and hasattr(values, "__array__"))


def _is_csr_column(values) -> bool:
    """A CsrVectorColumn (one scipy CSR backing a whole sparse vector
    column — see flink_ml_tpu.linalg.sparse). Duck-typed so this module
    needs neither scipy nor a linalg import at column-normalization time."""
    return getattr(values, "is_csr_vector_column", False)


def _is_device_sparse_column(values) -> bool:
    """A DeviceSparseColumn (ids and values of a sparse vector column as
    they lie on the device — see flink_ml_tpu.linalg.sparse): kept as it
    is, sliced on the device, and copied to the host only through its own
    ``to_csr()``."""
    return getattr(values, "is_device_sparse_column", False)


def _is_sparse_vector_column(values) -> bool:
    """A whole-column sparse form (host CSR or device), which converts to
    rows, dense arrays and other columns by its own methods."""
    return _is_csr_column(values) or _is_device_sparse_column(values)


def _slice_rows(col, start: int, stop: int):
    """``col[start:stop]`` with device columns routed through ONE
    compiled dynamic-slice program per (shape, dtype, length): the start
    rides as a traced scalar, so a streaming fit's batch loop reuses a
    single compiled program instead of recompiling per offset. Host
    columns (numpy, object, CSR) slice natively."""
    if _is_device_column(col):
        from flink_ml_tpu.ops import columnar

        return columnar.dynamic_rows(col, start, stop - start)
    return col[start:stop]


def _as_column(values) -> np.ndarray:
    """Normalize a column. Numeric 2-D arrays are kept as-is — a (n, d) array
    IS a vector column (row i = vector i); this is the fast path that avoids
    materializing n DenseVector objects for large tables."""
    if isinstance(values, np.ndarray) or _is_device_column(values) \
            or _is_sparse_vector_column(values):
        return values
    values = list(values)
    if values and isinstance(values[0], (Vector,)):
        arr = np.empty(len(values), dtype=object)
        for i, v in enumerate(values):
            arr[i] = v
        return arr
    try:
        arr = np.asarray(values)
    except ValueError:
        # ragged nested sequences stay host-side as object columns
        arr = np.empty(len(values), dtype=object)
        for i, v in enumerate(values):
            arr[i] = v
        return arr
    if arr.ndim == 2 and arr.dtype.kind == "f":
        return arr  # list of equal-length numeric rows → vector column
    if arr.dtype.kind in "OU" or arr.ndim > 1:
        out = np.empty(len(values), dtype=object)
        for i, v in enumerate(values):
            out[i] = v
        return out
    return arr


class Table:
    """An ordered set of named columns of equal length."""

    def __init__(self, columns: Dict[str, np.ndarray]):
        self._columns: Dict[str, np.ndarray] = {}
        n = None
        for name, col in columns.items():
            col = _as_column(col)
            if n is None:
                n = len(col)
            elif len(col) != n:
                raise ValueError(
                    f"column {name!r} has {len(col)} rows, expected {n}")
            self._columns[name] = col
        self._num_rows = n or 0

    # -- constructors --------------------------------------------------------
    @staticmethod
    def from_columns(**columns) -> "Table":
        return Table(columns)

    @staticmethod
    def from_rows(rows: Iterable[Sequence], names: Sequence[str]) -> "Table":
        rows = list(rows)
        cols = {name: [row[i] for row in rows] for i, name in enumerate(names)}
        return Table(cols)

    @staticmethod
    def from_data_frame(df) -> "Table":
        """From a servable DataFrame (flink_ml_tpu.servable)."""
        return Table({name: df.get(name).values for name in df.column_names})

    @staticmethod
    def from_csv(path: str, header: bool = True, delimiter: str = ",",
                 names: Sequence[str] = None) -> "Table":
        """Load a delimiter-separated file (the dataset-ingest role of the
        reference's Flink connectors). All-numeric files take the native
        C++ parse fast path; otherwise columns are inferred per column
        (float64 when every cell parses, object/string otherwise).
        ``names`` overrides the column names; with ``header=True`` the
        header row is still skipped."""
        import csv as _csv

        with open(path, "rb") as f:
            data = f.read()
        first_nl = data.find(b"\n")
        first_line = (data if first_nl < 0 else data[:first_nl]) \
            .decode().rstrip("\r")
        # quote-aware header parse (a quoted cell may contain the delimiter)
        header_cells = next(_csv.reader([first_line], delimiter=delimiter),
                            [])
        n_cols = len(header_cells)
        if header:
            if names is None:
                names = [c.strip() for c in header_cells]
            data = b"" if first_nl < 0 else data[first_nl + 1:]
        elif names is None:
            names = [f"c{i}" for i in range(n_cols)]
        names = list(names)
        if len(names) != n_cols:
            raise ValueError(f"{len(names)} names for {n_cols} columns")

        from flink_ml_tpu import native
        parsed = native.csv_parse_numeric(data, n_cols, delimiter) \
            if data else np.empty((0, n_cols))
        if parsed is not None:
            return Table({name: parsed[:, i].copy()
                          for i, name in enumerate(names)})

        # general path: per-column dtype inference
        import io as _io
        rows = list(_csv.reader(_io.StringIO(data.decode()),
                                delimiter=delimiter))
        rows = [r for r in rows if r]
        cols = {}
        for i, name in enumerate(names):
            raw = [r[i] if i < len(r) else "" for r in rows]
            try:
                cols[name] = np.asarray([float(v) for v in raw])
            except ValueError:
                cols[name] = np.asarray(raw, dtype=object)
        return Table(cols)

    def to_csv(self, path: str, header: bool = True,
               delimiter: str = ",") -> None:
        """Write scalar columns as delimiter-separated text (vector columns
        are rejected — save/load model data keeps its binary format)."""
        import csv as _csv
        names = self.column_names
        for name in names:
            if _is_sparse_vector_column(self._columns[name]):
                # rejected without materializing 10M SparseVector rows
                raise ValueError(
                    f"column {name!r} is not scalar; to_csv writes scalar "
                    "columns only")
            col = self._host_column(name)
            if col.ndim != 1 or (
                    col.dtype == object and len(col)
                    and isinstance(col[0], (Vector, list, tuple, np.ndarray))):
                raise ValueError(
                    f"column {name!r} is not scalar; to_csv writes scalar "
                    "columns only")
        with open(path, "w", newline="") as f:
            writer = _csv.writer(f, delimiter=delimiter)
            if header:
                writer.writerow(names)
            writer.writerows(zip(*(self._host_column(n) for n in names)))

    # -- schema / access -----------------------------------------------------
    @property
    def column_names(self) -> List[str]:
        return list(self._columns)

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def __len__(self):
        return self._num_rows

    def __contains__(self, name):
        return name in self._columns

    def column(self, name: str) -> np.ndarray:
        try:
            return self._columns[name]
        except KeyError:
            raise KeyError(
                f"no column {name!r}; available: {self.column_names}")

    def __getitem__(self, name: str) -> np.ndarray:
        return self.column(name)

    def vectors(self, name: str, dtype=np.float32) -> np.ndarray:
        """Column of vectors stacked into one (n, dim) array — the device
        on-ramp; equivalent of the reference's Table→DataStream map.

        A device-array column whose dtype already matches is returned
        as-is (residency preserved for chained device stages — though
        those normally use columnar.input_vectors directly). A device
        column requested at a DIFFERENT dtype — typically a float64 fit
        path downstream of a float32 device transform — is off-ramped to
        a mutable host array at the requested precision, so fit-time
        statistics keep their float64 contract.
        """
        col = self.column(name)
        if _is_sparse_vector_column(col):
            # dense off-ramp, same semantics as stacking SparseVectors
            return col.to_dense(dtype)
        if _is_device_column(col):
            if col.dtype == np.dtype(dtype):
                return col if col.ndim == 2 else col[:, None]
            arr = np.asarray(col, dtype=dtype)
            return arr[:, None] if arr.ndim == 1 else arr
        if col.dtype != object:
            arr = np.asarray(col, dtype=dtype)
            return arr[:, None] if arr.ndim == 1 else arr
        return stack_vectors(col, dtype=dtype)

    def scalars(self, name: str, dtype=np.float32) -> np.ndarray:
        """Always a host numpy array (the off-ramp for scalar columns)."""
        return np.asarray(self.column(name), dtype=dtype)

    # -- functional ops ------------------------------------------------------
    def with_column(self, name: str, values) -> "Table":
        cols = dict(self._columns)
        cols[name] = values
        return Table(cols)

    def with_columns(self, **named_values) -> "Table":
        cols = dict(self._columns)
        cols.update(named_values)
        return Table(cols)

    def select(self, *names: str) -> "Table":
        return Table({n: self.column(n) for n in names})

    def drop(self, *names: str) -> "Table":
        return Table({n: c for n, c in self._columns.items() if n not in names})

    def rename(self, mapping: Dict[str, str]) -> "Table":
        return Table({mapping.get(n, n): c for n, c in self._columns.items()})

    def take(self, indices) -> "Table":
        """Row subset. A unit-step ``slice`` takes the fast path: device
        columns slice through ONE compiled dynamic-slice program per
        (shape, length) — eager ``col[indices]`` on a mesh-sharded array
        lowers to a gather that measured ~1.5 s WARM per call on the
        8-device mesh, which dominated every streaming fit's batch loop
        (same pathology as columnar.head_rows). Array indices keep the
        general gather path.

        ALIASING CONTRACT: the slice path returns host columns that are
        VIEWS (``col[start:stop]``) of this table's buffers — the copy
        the old arange path paid was the dominant batch-loop cost, so it
        is deliberately gone. Mutating a slice-take/``head`` column in
        place silently corrupts the source table and every sibling
        batch; callers must ``.copy()`` a column before writing to it
        (mirrors the IN-PLACE note on text.py ``_rowwise_counts``; lint
        rule ``alias-mutation`` in flink_ml_tpu.analysis enforces this
        at the call site). Array-index takes copy, as numpy fancy
        indexing always does."""
        if isinstance(indices, slice):
            start, stop, step = indices.indices(self._num_rows)
            if step == 1:
                return Table({n: _slice_rows(c, start, stop)
                              for n, c in self._columns.items()})
            indices = np.arange(start, stop, step)
        return Table({n: c[indices] for n, c in self._columns.items()})

    def head(self, n: int) -> "Table":
        """First ``n`` rows via the slice-take fast path. Host columns of
        the result are VIEWS of this table's buffers — see the aliasing
        contract on :meth:`take`; copy before mutating."""
        # clamp below too: slice(0, -1) would mean "all but the last row",
        # while head(-1) has always meant 0 rows
        return self.take(slice(0, max(0, min(n, self._num_rows))))

    def concat(self, other: "Table") -> "Table":
        if set(self.column_names) != set(other.column_names):
            raise ValueError("cannot concat tables with different schemas")
        if self._num_rows == 0:
            # keep self's column ordering (cheap dict re-keying); also
            # sidesteps representation mismatch vs empty columns
            return Table({n: other.column(n) for n in self.column_names})
        if other.num_rows == 0:
            return self

        def cat(a, b):
            if _is_csr_column(a):
                return a.concat(b)
            if _is_csr_column(b):
                return b.concat_after(a)  # keep CSR backing either way
            return np.concatenate([a, b])

        return Table({n: cat(self._columns[n], other.column(n))
                      for n in self.column_names})

    # -- row view (collect parity with table.execute().collect()) -----------
    def _host_column(self, name: str) -> np.ndarray:
        col = self._columns[name]
        if _is_sparse_vector_column(col):
            return col.to_object_column()
        return np.asarray(col) if _is_device_column(col) else col

    def rows(self) -> List[tuple]:
        names = self.column_names
        cols = [self._host_column(n) for n in names]
        return [tuple(c[i] for c in cols) for i in range(self._num_rows)]

    def to_dict(self) -> Dict[str, list]:
        return {n: list(self._host_column(n)) for n in self._columns}

    def __repr__(self):
        return f"Table({self.column_names}, num_rows={self._num_rows})"


def as_dense_vector_column(arr: np.ndarray) -> np.ndarray:
    """(n, d) float array → object column of DenseVectors (device off-ramp)."""
    out = np.empty(arr.shape[0], dtype=object)
    for i in range(arr.shape[0]):
        out[i] = DenseVector(np.asarray(arr[i], dtype=np.float64))
    return out
