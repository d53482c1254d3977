"""The system under test, and nothing of the yardstick: the one module of
the harness that drives ``flink_ml_tpu`` (``program_spans.py`` and
``cold_spans.py`` read its tracer's spans; ``tools/aot_memory*.py`` compile
its builders). It builds the mesh, wraps the generated columns in the
program's ``Table``, builds a stage from the class name and paramMap of a
configuration, and runs one fit to model data on the host."""

from __future__ import annotations

import numpy as np


def configure_compile_cache() -> str:
    """The program's own (and the tree's only) setter of the cache directory:
    a fixed path inside the checkout unless the machine's environment names
    one."""
    from flink_ml_tpu.utils import compile_cache

    return compile_cache.configure()


def configure_mesh(devices):
    """A 1-D data mesh over ``devices``, set as the program's default."""
    from flink_ml_tpu.parallel.mesh import create_mesh, set_default_mesh

    mesh = create_mesh(devices=list(devices))
    set_default_mesh(mesh)
    return mesh


def row_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.parallel.mesh import data_pspec

    def sharding(ndim: int):
        if ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(
            mesh, P(data_pspec(mesh), *([None] * (ndim - 1))))

    return sharding


def make_table(columns: dict):
    """The program's ``Table`` over the generated columns, as they lie on
    the device. A sparse column (``{"ids", "values", "size"}``) goes
    through the program's one entry for it,
    ``flink_ml_tpu.linalg.sparse.device_sparse_column(ids, values, size)``,
    as the two device arrays; a program without that entry cannot take
    one, and says so at once."""
    from flink_ml_tpu.common.table import Table

    sparse = {name: col for name, col in columns.items()
              if isinstance(col, dict)}
    if sparse:
        from flink_ml_tpu.linalg import sparse as program_sparse

        entry = getattr(program_sparse, "device_sparse_column", None)
        if entry is None:
            raise NotImplementedError(
                "flink_ml_tpu.linalg.sparse.device_sparse_column is missing: "
                f"this program has no device path for the sparse column "
                f"{sorted(sparse)}")
        columns = dict(columns, **{
            name: entry(col["ids"], col["values"], int(col["size"]))
            for name, col in sparse.items()})
    return Table.from_columns(**columns)


def build_stage(class_name: str, params: dict):
    from flink_ml_tpu.benchmark.runner import resolve_stage

    stage = resolve_stage(class_name)()
    stage.params_from_json(params, strict=True)
    return stage


def _column_values(table, name: str) -> np.ndarray:
    col = table.column(name)
    if getattr(col, "dtype", None) == object:
        return np.asarray(table.vectors(name, dtype=np.float64))
    return np.asarray(col)


def fit(stage, table):
    """The call the window times first: ``stage.fit(table)``."""
    return stage.fit(table)


def model_to_host(stage, model):
    """``model.get_model_data()`` with every column on the host, and the
    execution path the fit reported."""
    answer = {}
    for model_table in model.get_model_data():
        for name in model_table.column_names:
            answer[name] = _column_values(model_table, name)
    return answer, getattr(stage, "last_execution_path", None)
