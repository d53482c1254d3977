"""Compile each cell's fit program for a described v5e, without the chip,
and print ``memory_analysis()``: how many bytes the program keeps beside
its arguments. Used once, to size ``numValues`` (PERF.md section 4).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_memory.py

The LR cells' one program, ``_build_sgd_segment_program`` as a plain fit
with no weight column builds it, handed the described devices. Nothing
runs.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness import spec

    names = [w["name"] for w in json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]
        if spec.load_cell(w["name"]).config["counts"] == "sgd_dense"]
    from flink_ml_tpu.ops import optimizer
    from flink_ml_tpu.ops.losses import BinaryLogisticLoss
    from flink_ml_tpu.parallel.mesh import create_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    def shapes(mesh, *specs):
        return [jax.ShapeDtypeStruct(shape, dtype,
                                     sharding=NamedSharding(mesh, pspec))
                for shape, dtype, pspec in specs]

    for name in names:
        cell = spec.load_cell(name)
        mesh = create_mesh(devices=topo.devices[:cell.chips])
        params, data = cell.stage_params(), cell.config["inputData"]["paramMap"]
        n, d = data["numValues"], data["vectorDim"]
        f32 = jnp.float32
        prm = optimizer.SGDParams(
            learning_rate=params["learningRate"],
            global_batch_size=params["globalBatchSize"],
            max_iter=params["maxIter"], tol=params["tol"])
        # what a plain fit with no weight column runs: the program makes
        # its own start and takes the coefficients as its one host operand
        prog = optimizer._build_sgd_segment_program(
            BinaryLogisticLoss, mesh, prm, fused=True, weighted=False,
            fresh=True)
        args = shapes(mesh, ((n, d), f32, P("data", None)),
                      ((n,), f32, P("data"))) + [None] + shapes(
            mesh, ((d,), f32, P()))
        path = "xla-while"
        compiled = prog.lower(*args).compile()
        m = compiled.memory_analysis()
        print(f"{name}: path {path}, rows {n}, per device: arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f} GB, kernel in program: "
              f"{'tpu_custom_call' in compiled.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
