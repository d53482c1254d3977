"""scripts/tpu_kernel_check.py exercised end-to-end in interpreter mode.

The script's real job is proving Mosaic lowerings on a chip, but a chip
window must never be burned by a plain Python bug in the harness itself —
so CI runs the WHOLE script (small-shape phase + the benchmark-scale
phase at shrunk sizes) with the kernels patched to interpret mode and
asserts it reports full parity (rc 0)."""

import importlib.util
import os
import sys

import numpy as np
import pytest


def _load_script():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "tpu_kernel_check.py")
    spec = importlib.util.spec_from_file_location("tpu_kernel_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_check_main_passes_in_interpret_mode(monkeypatch,
                                                    interpreted_kernels):
    import jax

    mod = _load_script()
    # the script refuses the cpu backend; CI is exactly where we want it
    # to run anyway (interpret-mode kernels are backend-agnostic)
    monkeypatch.setattr(jax, "default_backend", lambda: "interpret-ci")
    # shrink the scale phase ~64x so interpreter mode finishes in seconds
    assert mod.main(shrink=64) == 0


def test_kernel_check_detects_wrong_results(monkeypatch,
                                            interpreted_kernels):
    """A kernel that returns wrong numbers must drive rc 2, not rc 0 —
    the fail-closed contract chip_smoke.py's kernels phase trusts."""
    import jax

    from flink_ml_tpu.ops import pallas_kernels as pk

    mod = _load_script()
    monkeypatch.setattr(jax, "default_backend", lambda: "interpret-ci")
    # assign_nearest lies: everything lands in cluster 0
    monkeypatch.setattr(
        pk, "assign_nearest",
        lambda x, c, interpret=False: np.zeros(len(x), np.int32))
    assert mod.main(small_only=True) == 2
