"""Compile a NaiveBayes cell's two programs — the look and the counts —
for a described v5e, without the chip, and print ``memory_analysis()``:
how many bytes each keeps beside its arguments (PERF.md section 4: no
``(n, d)`` intermediate, so the temporaries must not grow with the table).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_memory_counts.py [--workload nb_fit_ref]

As ``aot_memory_lloyd.py`` does for the KMeans cell: the program picks its
kernel by ``jax.default_backend()``, which is the CPU here, so this script
hands the program's own builders the described devices and the kernel
choice the chip would make. Nothing runs.
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
    parser.add_argument("--workload", default="nb_fit_ref")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness import spec
    from flink_ml_tpu.models.classification import naivebayes
    from flink_ml_tpu.ops import contingency, pallas_kernels
    from flink_ml_tpu.parallel.mesh import create_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = create_mesh(devices=topo.devices[:cell.chips])
    data = cell.config["inputData"]["paramMap"]
    n, d = int(data["numValues"]), int(data["vectorDim"])
    labels, values = int(data["labelArity"]), int(data["featureArity"])

    def shape(dims, dtype, pspec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    table = (shape((n, d), jnp.float32, P("data", None)),
             shape((n,), jnp.float32, P("data")))

    def report(what, compiled):
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"{cell.name}: {what}, rows {n}, per device: arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.6f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.6f} GB, kernel in program: "
              f"{'tpu_custom_call' in text}, scatter in program: "
              f"{'scatter' in text}", flush=True)

    for rows in (naivebayes._LOOK_ROWS, None):
        report(f"the look (jit_nb_look) at {rows or 'all'} rows a shard",
               contingency.look_program(mesh, rows).lower(*table).compile())
    for use_kernel in (True, False):
        if use_kernel and not pallas_kernels.counts_kernel_fits(
                d, labels, values):
            continue
        report(f"the counts (jit_nb_counts), "
               f"{'pallas' if use_kernel else 'xla'}",
               contingency.counts_program(
                   mesh, labels, values, use_kernel).lower(
                       *table, shape((), jnp.int32, P())).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
