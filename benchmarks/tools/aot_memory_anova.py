"""Compile an ANOVA cell's two programs — the look and the grouped moments
— for a described v5e, without the chip, and print ``memory_analysis()``:
how many bytes each keeps beside its arguments (PERF.md section 4: no
``(n, L)`` one-hot operand, no ``(n, d)`` intermediate, no copy of the label
column, so the temporaries must not grow with the table).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_memory_anova.py [--workload anova_fit_ref] [--chips 1|4]

As ``aot_memory_counts.py`` does for the NaiveBayes cell: the program picks
its kernel by ``jax.default_backend()``, which is the CPU here, so this
script hands the program's own builders the described devices and the
kernel choice the chip would make. Nothing runs.
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
    parser.add_argument("--workload", default="anova_fit_ref")
    parser.add_argument("--chips", type=int, default=None,
                        help="the mesh to compile for (default: the cell's)")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness import spec
    from flink_ml_tpu.ops import pallas_kernels, stats
    from flink_ml_tpu.parallel.mesh import create_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    chips = args.chips or cell.chips
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = create_mesh(devices=topo.devices[:chips])
    data = cell.config["inputData"]["paramMap"]
    n, d = int(data["numValues"]), int(data["vectorDim"])
    labels = int(data["labelArity"])

    def shape(dims, dtype, pspec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    table = (shape((n, d), jnp.float32, P("data", None)),
             shape((n,), jnp.float32, P("data")))
    rows = shape((), jnp.int32, P())
    column = shape((d,), jnp.float32, P())

    def report(what, compiled):
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"{cell.name} on {chips}: {what}, rows {n}, per device: "
              f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.6f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.6f} GB, kernel in program: "
              f"{'tpu_custom_call' in text}", flush=True)

    report("the look (jit_anova_look)",
           stats.moments_look_program(mesh).lower(*table, rows).compile())
    for use_kernel in (True, False):
        if use_kernel and not pallas_kernels.moments_kernel_fits(d, labels):
            continue
        report(f"the moments (jit_anova_moments), "
               f"{'pallas' if use_kernel else 'xla'}",
               stats.moments_program(mesh, labels, use_kernel).lower(
                   *table, rows, column, column).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
