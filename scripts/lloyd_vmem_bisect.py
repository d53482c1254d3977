"""What Mosaic counts for the Lloyd and assign kernels at a shape: the least
``vmem_limit_bytes`` at which each compiles for a v5e, found by bisection.
``LLOYD_VMEM_BUDGET_BYTES`` and ``_lloyd_working_bytes``' constants in
``flink_ml_tpu/ops/pallas_kernels.py`` were set against these numbers
(PR 30: the count reads 2-24 % over Mosaic's at every shape tried); run it
again when the kernels or the compiler change. Ahead of time, with the TPU
compiler the installation brings: no chip is needed.

    python scripts/lloyd_vmem_bisect.py 10,100,4096 10,100,8192 1000,64,256

Each argument is ``k,d,tile``; the tile is forced, whatever ``lloyd_tile``
would pick. One line a shape: the count's MiB and Mosaic's for each kernel
(``None``: it does not compile at 64 MiB either, and the reason is printed).
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from flink_ml_tpu.ops import pallas_kernels as pk  # noqa: E402

MIB = 1 << 20
#: bisection stops at this width
STEP_MIB = 0.25


def compiles(which, k, d, tile, limit, one):
    """True when kernel ``which`` compiles at ``limit`` bytes of VMEM."""
    def of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    real_call, real_tile = pl.pallas_call, pk.lloyd_tile

    def limited(*args, **kw):
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=limit)
        return real_call(*args, **kw)

    pk.pl.pallas_call, pk.lloyd_tile = limited, lambda k, d: tile
    rows = 3 * tile + 77
    try:
        jax.clear_caches()
        if which == "lloyd":
            pk._lloyd_tiles.lower(of((rows, d)), of((), jnp.int32),
                                  of((k, d))).compile()
        else:
            pk._assign_tiles.lower(of((rows, d)), of((k, d))).compile()
        return True
    except Exception as e:  # noqa: BLE001 — Mosaic's refusal is the answer
        if "vmem" not in str(e).lower():
            print(f"  {which} {(k, d, tile)}: {str(e)[:300]}",
                  file=sys.stderr)
        return False
    finally:
        pk.pl.pallas_call, pk.lloyd_tile = real_call, real_tile


def least_mib(which, k, d, tile, one):
    lo, hi = 0.25, 64.0
    if not compiles(which, k, d, tile, int(hi * MIB), one):
        return None
    while hi - lo > STEP_MIB:
        mid = (lo + hi) / 2
        if compiles(which, k, d, tile, int(mid * MIB), one):
            hi = mid
        else:
            lo = mid
    return hi


def main(argv) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    for arg in argv:
        k, d, tile = (int(v) for v in arg.split(","))
        counted = pk._lloyd_working_bytes(k, d, tile) / MIB
        print(f"k {k} d {d} tile {tile}: counted {counted:.2f} MiB"
              f" (budget {pk.LLOYD_VMEM_BUDGET_BYTES / MIB:.0f}),"
              f" lloyd {least_mib('lloyd', k, d, tile, one)} MiB,"
              f" assign {least_mib('assign', k, d, tile, one)} MiB",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
