"""flink_ml_tpu — a TPU-native ML framework with the capabilities of Apache Flink ML.

A from-scratch JAX/XLA/Pallas re-design of the Flink ML feature set
(reference: flink-ml 2.4-SNAPSHOT). The reference is a library on top of a
JVM dataflow engine; this framework replaces that engine with:

- SPMD ``pjit`` programs over a ``jax.sharding.Mesh`` (data parallelism,
  broadcast, collectives over ICI/DCN) instead of Flink network shuffles,
- a compiled round function driven by a host loop (or fully on-device
  ``lax.while_loop``) instead of the Flink iteration runtime,
- a host-side columnar ``Table`` instead of the Flink Table API,
- Orbax-style pytree checkpointing of the round carry instead of
  checkpoint barriers circulating through a dataflow cycle.

Layers (bottom-up, see SURVEY.md §7):
  params    — typed hyperparameter system (ref: flink-ml-servable-core param/)
  linalg    — vectors/matrices + BLAS-equivalent ops (ref: linalg/)
  parallel  — mesh + collectives (ref: AllReduceImpl, BroadcastUtils)
  iteration — bounded/unbounded iteration runtime (ref: flink-ml-iteration)
  api       — Stage/Estimator/Transformer/Model, Pipeline, Graph (ref: flink-ml-core)
  ops       — losses, SGD/FTRL optimizers, shared numeric kernels
  models    — the algorithm library (ref: flink-ml-lib)
  servable  — engine-free online inference (ref: flink-ml-servable-*)
  benchmark — JSON-config benchmark harness (ref: flink-ml-benchmark)
  analysis  — jaxlint static analyzer for JAX/TPU hazards (docs/jaxlint.md;
              no reference equivalent: the JVM had a type system where we
              have tracing)
"""

__version__ = "0.1.0"

# a process's cold start, accounted from inside (docs/observability.md
# "Cold spans"): the package's import and its two heavy third parties,
# stamped before a tracer can exist and adopted by it when it is built
from flink_ml_tpu._cold import importing as _importing

with _importing("flink_ml_tpu"):
    with _importing("numpy"):
        import numpy  # noqa: F401
    with _importing("jax"):
        import jax  # noqa: F401

    from flink_ml_tpu.api import (  # noqa: F401
        AlgoOperator,
        Estimator,
        Model,
        Stage,
        Transformer,
    )
    from flink_ml_tpu.common.table import Table  # noqa: F401
    from flink_ml_tpu.common.functions import (  # noqa: F401
        array_to_vector,
        vector_to_array,
    )
