"""Compile a KMeans cell's fit program for a described v5e, without the
chip, and print ``memory_analysis()``: how many bytes the program keeps
beside its arguments (PERF.md section 4: the temporaries must not grow
with the table).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_memory_lloyd.py [--workload kmeans_fit_ref10]

As ``aot_memory.py`` does for the SGD cells: the program picks its kernel
by ``jax.default_backend()``, which is the CPU here, so this script hands
the program's own builder the described devices and the kernel choice the
chip would make. Nothing runs.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="kmeans_fit_ref10")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness import spec
    from flink_ml_tpu.models.clustering import kmeans
    from flink_ml_tpu.ops import pallas_kernels
    from flink_ml_tpu.parallel.mesh import create_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = create_mesh(devices=topo.devices[:cell.chips])
    params, data = cell.stage_params(), cell.config["inputData"]["paramMap"]
    n, d = int(data["numValues"]), int(data["vectorDim"])
    k, rounds = int(params["k"]), int(params["maxIter"])

    def shape(dims, dtype, pspec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    init = kmeans._build_init_rows_program(mesh, k).lower(
        shape((n, d), jnp.float32, P("data", None)),
        shape((k,), jnp.int32, P())).compile().memory_analysis()
    print(f"{cell.name}: the k initial rows (jit_lloyd_init_rows), per "
          f"device: temporaries {init.temp_size_in_bytes / 1e9:.3f} GB",
          flush=True)
    for use_kernel in (True, False):
        if use_kernel and not pallas_kernels.lloyd_kernel_fits(k, d):
            continue
        prog = kmeans._build_lloyd_program(
            mesh, "euclidean", rounds,
            unroll=rounds <= kmeans._UNROLL_MAX_ROUNDS,
            use_kernel=use_kernel)
        compiled = prog.lower(
            shape((n, d), jnp.float32, P("data", None)),
            shape((), jnp.int32, P()), shape((k, d), jnp.float32, P()),
            shape((k,), jnp.float32, P())).compile()
        m = compiled.memory_analysis()
        print(f"{cell.name}: path "
              f"{'pallas-lloyd' if use_kernel else 'xla-lloyd'}, rows {n}, "
              f"per device: arguments {m.argument_size_in_bytes / 1e9:.3f} "
              f"GB, temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {m.output_size_in_bytes / 1e9:.6f} GB, kernel in "
              f"program: {'tpu_custom_call' in compiled.as_text()}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
