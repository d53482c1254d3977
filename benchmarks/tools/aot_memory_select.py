"""Compile the RobustScaler cell's selection programs for a described v5e,
without the chip, and print ``memory_analysis()``: how many bytes each keeps
beside its arguments (PERF.md section 4: no ``(n, d)`` key image, no sorted
copy and no row-major copy of the table, so the temporaries must not grow
with it; the 32-round program these replaced kept 4.992 GB of them at 12M x
100, and the same passes inside a ``while_loop`` 4.992-6.146 GB).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_memory_select.py [--workload robustscaler_fit_ref] [--head-passes 1,2,4]

As ``aot_memory_counts.py`` does for the NaiveBayes cell: the program's own
builder is handed the described devices. ``--head-passes`` compiles the head
with other numbers of straight-line passes than the one the program ships
with (``ops/quantile.HEAD_PASSES``), to rule a shape in or out before any
chip time is spent on it. Nothing runs.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="robustscaler_fit_ref")
    parser.add_argument("--text", help="write each compiled program's text "
                        "to this file, the program's name appended")
    parser.add_argument("--head-passes", default=None,
                        type=lambda v: [int(k) for k in v.split(",")])
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness import spec
    from flink_ml_tpu.ops import quantile
    from flink_ml_tpu.parallel.mesh import create_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = create_mesh(devices=topo.devices[:cell.chips])
    data = cell.config["inputData"]["paramMap"]
    n, d = int(data["numValues"]), int(data["vectorDim"])
    m = 3                                   # lower, the median, upper

    def shape(dims, dtype, pspec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    def compile_all(head_passes):
        quantile.HEAD_PASSES = head_passes
        quantile.select_programs.cache_clear()
        head, step, step_ends = quantile.select_programs(mesh, m)
        table = shape((n, d), jnp.float32, P("data", None))
        spec = shape((m + 1,), jnp.int32, P())
        state = shape((5 + quantile.PIVOTS, m, d), jnp.uint32, P())
        for name, program, operands in (
                (f"select_head[{head_passes} passes]", head, (table, spec)),
                ("select_step", step, (table, spec, state)),
                ("select_step_ends", step_ends, (table, spec, state))):
            compiled = program.lower(*operands).compile()
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            whole = re.findall(rf"[a-z0-9]+\[(?:{n // cell.chips},{d}|"
                               rf"{d},{n // cell.chips})\][^ ]*", text)
            print(f"{cell.name}: jit_{name}, rows {n}, per device: "
                  f"arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
                  f"temporaries {mem.temp_size_in_bytes / 1e9:.6f} GB, "
                  f"outputs {mem.output_size_in_bytes / 1e9:.6f} GB; arrays "
                  f"of the table's shape in the program: "
                  f"{sorted(set(whole))}", flush=True)
            if args.text:
                Path(f"{args.text}.{name}").write_text(text)

    shipped = quantile.HEAD_PASSES
    try:
        for head_passes in args.head_passes or [shipped]:
            compile_all(head_passes)
    finally:
        quantile.HEAD_PASSES = shipped
        quantile.select_programs.cache_clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
