"""Where XLA's persistent compilation cache lives.

A chip call starts from a clean machine and the first compile of the
SGD program or of each serving bucket costs tens of seconds;
the persistent cache lets a second process on the same machine reuse
them. The cache directory is part of nothing's key but must not MOVE
between processes that want to share it, so it is either where the
operator put it (``JAX_COMPILATION_CACHE_DIR``, which JAX reads by
itself) or one fixed path inside the checkout.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — resolved from this package's own path
#: (the checkout is the parent of ``flink_ml_tpu/``); listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Point JAX at the persistent compile cache; returns the directory
    in use. Call before the first compile. With
    ``JAX_COMPILATION_CACHE_DIR`` set this touches nothing — JAX reads
    the variable itself; otherwise the cache goes to :data:`DEFAULT_DIR`.
    The only writer of ``jax_compilation_cache_dir`` in the tree."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def entry_count(directory: str) -> int:
    """Number of executables cached under ``directory`` (JAX names them
    ``<module>-<key>-cache``; 0 when the directory is absent)."""
    try:
        return sum(name.endswith("-cache") for name in os.listdir(directory))
    except FileNotFoundError:
        return 0
