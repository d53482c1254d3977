"""utils/compile_cache.py: one guarded setter, a path that never moves."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, os, sys
import jax
from flink_ml_tpu.utils import compile_cache
before = jax.config.jax_compilation_cache_dir
used = compile_cache.configure()
print(json.dumps({"before": before, "used": used,
                  "option": jax.config.jax_compilation_cache_dir}))
"""

_AOT = """
import json, sys
import jax, jax.numpy as jnp
from jax import monitoring
from flink_ml_tpu.observability.compilestats import instrumented_jit
from flink_ml_tpu.utils import compile_cache
hits = []
monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
used = compile_cache.configure()
f = instrumented_jit(lambda x: jnp.tanh(x @ x.T).sum(), name="cache.probe")
f(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"used": used, "hits": len(hits),
                  "entries": compile_cache.entry_count(used)}))
"""


def _run(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
    proc = subprocess.run([sys.executable, "-c", code], env=full, cwd="/",
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_var_set_means_no_write(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper touches nothing —
    JAX read the variable itself."""
    doc = _run(_PROBE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert doc["used"] == str(tmp_path)
    assert doc["before"] == doc["option"] == str(tmp_path)


def test_unset_means_the_fixed_in_checkout_path():
    """Unset, the cache goes to <checkout>/.jax_cache — identical across
    two processes started from another directory (the path is part of
    nothing's key but a directory that moves never hits)."""
    first, second = _run(_PROBE), _run(_PROBE)
    want = os.path.join(REPO, ".jax_cache")
    assert first["before"] is None
    assert first["used"] == second["used"] == want
    assert first["option"] == second["option"] == want


def test_instrumented_jit_aot_path_hits_the_cache(tmp_path):
    """instrumented_jit compiles through ``.lower().compile()``; a second
    process must find that executable in the same persistent cache."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    cold = _run(_AOT, **env)
    warm = _run(_AOT, **env)
    assert cold["hits"] == 0 and cold["entries"] > 0
    assert warm["hits"] > 0
    assert warm["entries"] == cold["entries"]


def test_one_setter_in_the_tree():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "chiprun_out" and d != "__pycache__"]
        for name in files:
            if name.endswith(".py") and name != "test_compile_cache.py":
                with open(os.path.join(root, name)) as f:
                    if "compilation_cache_dir\"" in f.read():
                        hits.append(os.path.relpath(
                            os.path.join(root, name), REPO))
    assert hits == [os.path.join("flink_ml_tpu", "utils",
                                 "compile_cache.py")]
