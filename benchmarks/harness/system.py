"""The system under test, and nothing of the yardstick: the one module of
the benchmark that imports ``flink_ml_tpu``. It builds the mesh, wraps the
generated columns in the program's ``Table``, builds a stage from the class
name and paramMap of a configuration, and runs one fit to model data on
the host."""

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
        return NamedSharding(
            mesh, P(data_pspec(mesh), *([None] * (ndim - 1))))

    return sharding


def make_table(columns: dict):
    from flink_ml_tpu.common.table import Table

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
