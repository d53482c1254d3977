"""Test env: simulate an 8-device mesh on CPU.

The TPU analog of the reference's MiniCluster test strategy (SURVEY.md §4):
multi-node is simulated by multi-device parallelism inside one process via
XLA's host-platform device-count flag. Must run before jax initializes.
"""

import os

# Force CPU: tier-1 runs on the simulated 8-device CPU mesh wherever it is
# launched (on a chip host JAX would otherwise claim the TPU).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def mesh8():
    from flink_ml_tpu.parallel import create_mesh
    return create_mesh()


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(monkeypatch):
    """Entry points (runner.main, the serving scripts, bench.py) point JAX
    at <checkout>/.jax_cache; tier-1 calls them in-process and must neither
    write there nor have later tests served from it. The helper itself is
    tested in subprocesses (tests/test_compile_cache.py)."""
    from flink_ml_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "configure", lambda: None)


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """Every Pallas kernel interpreted and the backend gate open, so the
    kernel call sites run on the CPU mesh; the cached program builders are
    cleared around the test so no other test sees a program built under
    the patch."""
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.ops import contingency
    from flink_ml_tpu.ops import pallas_kernels as pk

    def clear():
        km._build_lloyd_program.cache_clear()
        km._build_lloyd_segment_program.cache_clear()
        km._build_assign_program.cache_clear()
        contingency.counts_program.cache_clear()

    monkeypatch.setattr(pk, "pallas_supported", lambda: True)
    for name in ("assign_nearest", "category_counts", "grouped_moments",
                 "knn_topk_indices", "lloyd_partial_sums",
                 "segment_reduce_sum"):
        orig = getattr(pk, name)
        monkeypatch.setattr(
            pk, name,
            lambda *a, _orig=orig, **kw: _orig(*a, **{**kw,
                                                      "interpret": True}))
    clear()
    yield
    clear()
