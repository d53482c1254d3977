"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``. Four virtual CPU devices stand in for the four-chip
mesh; sizes are cut through ``run_cell.run``'s internal ``overrides``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
