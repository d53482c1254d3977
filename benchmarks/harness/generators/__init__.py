"""The benchmark's own table generators: the upstream benchmark's generator
classes (``flink-ml-benchmark/.../datagenerator/common``), made on the
device in ONE jitted call from the run's seed, in float32, row-sharded.

A configuration names its generator by the upstream class name:
``generators/<ClassName>.py`` holds ``build(params) -> (gen(key) ->
{column: array}, {column: rank})``. The generator's own ``seed`` parameter,
where the upstream file has one, is replaced by ``--seed``.

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
    """``{column: device array}``; ``row_sharding(ndim)`` gives the
    sharding of a column of that rank."""
    import jax

    short = class_name.rsplit(".", 1)[-1]
    try:
        module = importlib.import_module(f"{__name__}.{short}")
    except ModuleNotFoundError:
        raise KeyError(f"no generator {class_name!r} under "
                       f"harness/generators/") from None
    gen, ranks = module.build(params)
    shardings = {name: row_sharding(rank) for name, rank in ranks.items()}
    columns = jax.jit(gen, out_shardings=shardings)(jax.random.key(seed))
    return jax.block_until_ready(columns)
