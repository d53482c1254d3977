"""The benchmark's own table generators: the upstream benchmark's generator
classes (``flink-ml-benchmark/.../datagenerator/common``), made on the
device in ONE jitted call from the run's seed, in float32, row-sharded.

A configuration names its generator by the upstream class name:
``generators/<ClassName>.py`` holds ``build(params) -> (gen(key) ->
{column: array}, {column: rank})``. The generator's own ``seed`` parameter,
where the upstream file has one, is replaced by ``--seed``.

A sparse vector column is ``{"ids": (n, k) int32, "values": (n, k)
float32, "size": int32 scalar}``: ``k`` entries a row, a padding entry
with value 0, ``size`` the vector's. Its ranks are ``{"ids": 2, "values":
2, "size": 0}``: both arrays row-sharded as a dense rank-2 column is, the
size replicated.

(The program has generators of the same semantics in
``flink_ml_tpu/benchmark/datagen.py``; the yardstick does not call them.)
"""

from __future__ import annotations

import importlib


def values(key, shape, arity: int):
    """Uniform [0, 1) doubles, or for a positive arity the integers
    ``floor(u * arity)`` — both as float32 (the upstream generators'
    featureArity / labelArity semantics)."""
    import jax
    import jax.numpy as jnp

    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.floor(u * arity) if arity else u


def make_columns(class_name: str, params: dict, seed: int, row_sharding):
    """``{column: device array}`` (a sparse column: ``{"ids", "values",
    "size"}``); ``row_sharding(ndim)`` gives the sharding of a column of
    that rank, replicated at rank 0."""
    import jax

    short = class_name.rsplit(".", 1)[-1]
    try:
        module = importlib.import_module(f"{__name__}.{short}")
    except ModuleNotFoundError:
        raise KeyError(f"no generator {class_name!r} under "
                       f"harness/generators/") from None
    gen, ranks = module.build(params)
    shardings = jax.tree.map(row_sharding, ranks)
    columns = jax.jit(gen, out_shardings=shardings)(jax.random.key(seed))
    return jax.block_until_ready(columns)
