"""What a fit's results cost to bring to the host, by the form of the read:
the record behind ``iteration.read_boundary`` starting every leaf's copy
before it waits on any (PR 34). On the chip, after one warm ``sgd_segment``
at the LR cells' shapes (12M x 100 float32 a device, the published 20 rounds
of 100,000 rows), the host time from the program's end to the boundary
bundle, the coefficients and the loss on the host, for

    serial   three reads, each waited on before the next starts (the parent)
    started  ``copy_to_host_async`` on every leaf, then ``np.asarray`` in order
    get      ``jax.device_get`` of the tuple: the same, JAX's own spelling,
             and what ``read_boundary`` calls
    packed   one vector ``[coeffs, mean_loss, epoch, stop]`` made on the device
             by a second small program, read once
    ready    ``jax.block_until_ready`` on the three, then ``get``
    pinned   a second small program whose outputs live in pinned host memory
             (``memory_kind="pinned_host"``), then ``serial``: the device
             writes the host's copy itself (left out, and said, where the
             runtime refuses the memory kind)

and the same from the launch (the carry placed, the program called, no wait
before the read: what a fit does; ``packed`` and ``pinned`` pay their second
dispatch there). ``ready`` and ``pinned`` are not what the program does:
they are here for what is left, the millisecond between the program's end
and the first read's return (PERF.md section 7).

    python scripts/fetch_forms.py [--rows 12000000] [--fits 300] [--rehearse]

The mesh is ``data=<every device>``: one chip gives the one-chip cell's
shapes, four the four-chip cell's. One JSON line a form, medians in ms;
it exits 2 off the chip (a CPU's copies are no device's) unless
``--rehearse`` says the run is there to find faults, not numbers.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from flink_ml_tpu.ops.losses import BinaryLogisticLoss  # noqa: E402
from flink_ml_tpu.ops.optimizer import (  # noqa: E402
    SGDParams, _build_sgd_segment_program)
from flink_ml_tpu.parallel.mesh import create_mesh, data_pspec  # noqa: E402

D = 100


def serial(boundary, coeffs, mean_loss):
    vals = np.asarray(boundary)
    return vals, np.asarray(coeffs, np.float64), float(mean_loss)


def started(boundary, coeffs, mean_loss):
    for leaf in (boundary, coeffs, mean_loss):
        leaf.copy_to_host_async()
    return serial(boundary, coeffs, mean_loss)


def get(boundary, coeffs, mean_loss):
    vals, coeffs, mean_loss = jax.device_get((boundary, coeffs, mean_loss))
    return vals, np.asarray(coeffs, np.float64), float(mean_loss)


def ready(boundary, coeffs, mean_loss):
    return get(*jax.block_until_ready((boundary, coeffs, mean_loss)))


def pack(boundary, coeffs, mean_loss):
    return jnp.concatenate([coeffs, mean_loss[None],
                            boundary.astype(coeffs.dtype)])


def unpack(packed):
    packed = np.asarray(packed)
    return (packed[D + 1:].astype(np.int32),
            packed[:D].astype(np.float64), float(packed[D]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=12_000_000,
                    help="rows a device")
    ap.add_argument("--fits", type=int, default=300)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip too: the times mean nothing")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu" and not args.rehearse:
        print("fetch_forms: needs the chip", file=sys.stderr)
        return 2

    mesh = create_mesh(devices=jax.devices())
    p = len(jax.devices())
    n = args.rows * p
    rows = NamedSharding(mesh, P(data_pspec(mesh)))
    repl = NamedSharding(mesh, P())
    xs = jax.jit(lambda key: jax.random.uniform(key, (n, D), jnp.float32),
                 out_shardings=NamedSharding(
                     mesh, P(data_pspec(mesh), None)))(jax.random.key(34))
    ys = jax.jit(lambda x: (x[:, 0] > 0.5).astype(jnp.float32),
                 out_shardings=rows)(xs)
    prm = SGDParams(learning_rate=0.1, global_batch_size=100_000,
                    max_iter=20, tol=1e-6)
    seg = _build_sgd_segment_program(BinaryLogisticLoss, mesh, prm,
                                     fused=True, weighted=False)
    pack_prog = jax.jit(pack, out_shardings=repl)

    def launch():
        coeffs, offsets = jax.device_put(
            (np.zeros(D, np.float32), np.zeros((p,), np.int32)),
            (repl, rows))
        coeffs, _, _, mean_loss, boundary = seg(
            xs, ys, None, coeffs, offsets, (), np.int32(0),
            np.int32(prm.max_iter))
        return boundary, coeffs, mean_loss

    def launch_packed():
        return (pack_prog(*launch()),)

    forms = {"serial": (launch, serial), "started": (launch, started),
             "get": (launch, get), "packed": (launch_packed, unpack),
             "ready": (launch, ready)}
    want = serial(*launch())
    try:
        to_host = jax.jit(lambda *leaves: leaves, out_shardings=NamedSharding(
            mesh, P(), memory_kind="pinned_host"))
        serial(*to_host(*launch()))
        forms["pinned"] = (lambda: to_host(*launch()), serial)
    except Exception as e:  # noqa: BLE001 — the runtime's refusal is the answer
        print(json.dumps({"form": "pinned", "not_run": str(e)[:300]}),
              flush=True)
    for name, (start, read) in forms.items():   # warm, and the same answer
        got = read(*start())
        assert all(np.array_equal(g, w) for g, w in zip(got, want)), name

    for name, (start, read) in forms.items():
        from_end, from_launch = [], []
        for _ in range(args.fits):
            leaves = jax.block_until_ready(start())
            t0 = time.perf_counter()
            read(*leaves)
            from_end.append((time.perf_counter() - t0) * 1e3)
        for _ in range(args.fits):
            t0 = time.perf_counter()
            read(*start())
            from_launch.append((time.perf_counter() - t0) * 1e3)
        q = statistics.quantiles(from_end, n=4)
        print(json.dumps({
            "form": name, "devices": p, "rows": n, "fits": args.fits,
            "device_kind": jax.devices()[0].device_kind,
            "from_program_end_ms": statistics.median(from_end),
            "from_program_end_quartiles_ms": [q[0], q[2]],
            "from_program_end_p95_ms": sorted(from_end)[
                int(0.95 * len(from_end))],
            "launch_to_host_ms": statistics.median(from_launch)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
