"""The forms of the grouped moments under the ANOVA F-test, timed on the
chip at the selector cell's shape (12M x 100 float32, a 10-class label): one
exact read of the table in each. PERF.md section 6 (PR 40) holds what it
read; this file is the record of what was timed.

    python scripts/anova_forms.py [--rows 12000000] [--dim 100] [--labels 10] [--repeats 20]

One process that holds the table (a chip belongs to one process at a time):

- ``look``: ``jit_anova_look`` alone (the label column's range over every
  row, every column's ends and mean over the first 4,096 rows);
- ``xla[digits]``: the XLA form (``ops/stats.grouped_moments_xla``: a loop
  of one-hot products over blocks of 8,192 rows) at the kept digits;
- ``kernel[tile][digits]``: the Pallas kernel
  (``pallas_kernels.grouped_moments``) at every ``--tiles`` rows a tile and
  every ``--digits`` (digits of the scaled value, digits of its square);
  each form's integers are held to the XLA form's at the same digits, bit
  for bit, on a table of continuous values and on one of zeros and ones;
- ``parent``: what ``ops/stats.anova_f_test`` ran until PR 40 (a host
  ``np.unique`` of the label column, ``one_hot.T @ x`` at the default
  precision, a gather of the class means), where it fits;
- ``pallas_import``: seconds ``import jax.experimental.pallas`` and
  ``.tpu`` took in this process (the floor of a first fit that keeps the
  kernel).

Needs a TPU: off the chip it exits 2 (``--allow-cpu`` for a rehearsal at a
small ``--rows``, where the kernel is left out).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def emit(**fields):
    print(json.dumps(fields), flush=True)


def timed(call, repeats: int):
    """Median milliseconds of ``call`` to its result ready on the device,
    after one run that compiles."""
    import jax

    t = time.perf_counter()
    first = jax.block_until_ready(call())
    first_s = time.perf_counter() - t
    walls = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(call())
        walls.append((time.perf_counter() - t) * 1e3)
    return first, round(statistics.median(walls), 3), round(first_s, 3)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=12_000_000)
    parser.add_argument("--dim", type=int, default=100)
    parser.add_argument("--labels", type=int, default=10)
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--tiles", default="1024,2048,4096")
    parser.add_argument("--digits", default="4-4,3-4,3-3")
    parser.add_argument("--seed", type=int, default=40)
    parser.add_argument("--no-parent", action="store_true")
    parser.add_argument("--allow-cpu", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.allow_cpu:
        print("needs a TPU", file=sys.stderr)
        return 2
    from flink_ml_tpu.ops import fixedpoint, stats
    from flink_ml_tpu.parallel.mesh import create_mesh, set_default_mesh

    mesh = create_mesh(devices=jax.devices()[:1])
    set_default_mesh(mesh)
    n, d, labels = args.rows, args.dim, args.labels
    key = jax.random.key(args.seed)

    @jax.jit
    def make(key):
        k = [jax.random.fold_in(key, i) for i in range(2)]
        u = jax.random.uniform(k[0], (n, d), jnp.float32)
        return u, jnp.floor(jax.random.uniform(k[1], (n,), jnp.float32)
                            * labels)

    u, y = jax.block_until_ready(make(key))
    emit(form="table", rows=n, d=d, labels=labels,
         device=jax.devices()[0].device_kind)

    seen, look_ms, look_first = timed(
        lambda: stats.moments_look_program(mesh)(u, y, np.int32(n)),
        args.repeats)
    seen = np.asarray(seen, np.float64)
    emit(form="look", ms=look_ms, first_s=look_first,
         labels_seen=seen[:3].tolist())
    pivot, scale = stats.pivot_and_scale(
        seen[3:3 + d], seen[3 + d:3 + 2 * d], seen[3 + 2 * d:],
        min(stats._LOOK_ROWS, n))
    inv = (1.0 / scale).astype(np.float32)
    emit(form="scales", pivot=sorted(set(pivot.tolist()))[:4],
         scale=sorted(set(scale.tolist()))[:4])

    def with_digits(pair):
        fixedpoint.MOMENTS_DIGITS = pair
        if on_tpu:
            from flink_ml_tpu.ops import pallas_kernels
            pallas_kernels.MOMENTS_DIGITS = pair
        jax.clear_caches()

    pallas_s = None
    if on_tpu:
        t = time.perf_counter()
        from flink_ml_tpu.ops import pallas_kernels
        pallas_s = round(time.perf_counter() - t, 3)
        emit(form="pallas_import", seconds=pallas_s)
    binary = jax.block_until_ready(jnp.floor(u * 2.0))
    half = np.full((d,), 0.5, np.float32)
    for pair in [tuple(int(v) for v in p.split("-"))
                 for p in args.digits.split(",")]:
        with_digits(pair)
        name = f"{pair[0]}-{pair[1]}"
        xla = jax.jit(lambda x, y, p, i: stats.grouped_moments_xla(
            x, y, jnp.int32(n), p, i, labels,
            jnp.bfloat16 if on_tpu else jnp.float32))
        ref, ms, first_s = timed(lambda: xla(u, y, pivot, inv), args.repeats)
        ref = [np.asarray(a) for a in ref]
        ref01 = [np.asarray(a) for a in xla(binary, y, half, half * 2)]
        emit(form=f"xla[{name}]", ms=ms, first_s=first_s,
             top=float(ref[3].max()), counted=int(ref[2].sum()))
        if not on_tpu:
            continue
        for tile in [int(t) for t in args.tiles.split(",")]:
            pallas_kernels.MOMENTS_TILES_N = (tile,)
            pallas_kernels.MOMENTS_VMEM_BUDGET_BYTES = 1 << 30
            jax.clear_caches()
            kernel = jax.jit(lambda x, y, p, i: pallas_kernels.
                             grouped_moments(x, y, jnp.int32(n), p, i,
                                             labels))
            try:
                got, ms, first_s = timed(lambda: kernel(u, y, pivot, inv),
                                         args.repeats)
                got01 = kernel(binary, y, half, half * 2)
            except Exception as exc:  # noqa: BLE001 — a form that fails is a finding
                emit(form=f"kernel[{tile}][{name}]", failed=repr(exc)[:300])
                continue
            emit(form=f"kernel[{tile}][{name}]", ms=ms, first_s=first_s,
                 equal_uniform=[bool(np.array_equal(np.asarray(a), b))
                                for a, b in zip(got, ref)],
                 equal_binary=[bool(np.array_equal(np.asarray(a), b))
                               for a, b in zip(got01, ref01)])
    if not args.no_parent:
        def parent():
            classes, y_idx = np.unique(np.asarray(y), return_inverse=True)
            c = len(classes)
            y32 = jnp.asarray(y_idx.astype(np.int32))
            oh = jax.nn.one_hot(y32, c, dtype=u.dtype)
            packed = np.asarray(jnp.concatenate(
                [oh.sum(axis=0)[:, None], oh.T @ u], axis=1), np.float64)
            means = (packed[:, 1:] / packed[:, :1]).astype(np.float32)
            centered = u - jnp.asarray(means)[y32]
            return jnp.sum(centered * centered, axis=0)

        try:
            _, ms, first_s = timed(parent, 3)
            emit(form="parent", ms=ms, first_s=first_s)
        except Exception as exc:  # noqa: BLE001
            emit(form="parent", failed=repr(exc)[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main())
