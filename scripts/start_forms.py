"""What a fit's start costs the host, by the form it is given: the record
behind a plain SGD fit making its zero carry and its epoch bounds inside
the program (PR 39). On the chip, at the LR cells' shapes (12M x 100 float32
a device, the published 20 rounds of 100,000 rows), first the pieces alone,
each the host time until the call returns (the chip idles under all of it)
and until what it made is ready:

    tree_put      ``jax.device_put`` of the three host leaves a plain sgd
                  carry has (coefficients ``(d,)``, offsets ``(p,)`` over
                  the rows, the ``inf`` loss), each with its sharding: what
                  ``sgd.init_carry`` did until PR 39
    packed_put    one ``device_put`` of one replicated ``(d + p + 1,)`` vector
    call_resident a small mapped program over operands already on the mesh:
                  what a dispatch alone costs
    call_vector   the same call with the ``(d,)`` as the host array it is:
                  jit's own argument path places it
    call_scalars  the same call with its two ``int32`` bounds from the host
                  (``np.int32``): what ``sgd.launch`` did until PR 39

then the whole start with the fit's own ``sgd_segment`` program, from the
first host instruction to the call's return (``launch``) and to the fitted
state on the host (``to_host``: what a fit's wall holds of it):

    carry         the tree put, then the call with carry and bounds
    fresh         the call with the coefficients as its one host operand,
                  carry and bounds made inside

    python scripts/start_forms.py [--rows 12000000] [--fits 300] [--rehearse]

The mesh is ``data=<every device>``: one chip gives the one-chip cell's
shapes, four the four-chip cell's. One JSON line a form, medians in ms; the
two whole starts must answer bit for bit or it exits 1. It exits 2 off the
chip (a CPU's transfers are no device's) unless ``--rehearse`` says the run
is there to find faults, not numbers.
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

from flink_ml_tpu.iteration.iteration import read_boundary  # noqa: E402
from flink_ml_tpu.ops.losses import BinaryLogisticLoss  # noqa: E402
from flink_ml_tpu.ops.optimizer import (  # noqa: E402
    SGDParams, _build_sgd_segment_program)
from flink_ml_tpu.parallel import mapreduce as mr  # noqa: E402
from flink_ml_tpu.parallel.mesh import create_mesh, data_pspec  # noqa: E402

D = 100


def timed(fn, repeats, finish=jax.block_until_ready,
          names=("returned", "ready")):
    """Medians, quartiles and p95 in ms over ``repeats`` calls of ``fn``:
    until it returns, and until ``finish`` has what it returned."""
    first, second = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        t1 = time.perf_counter()
        finish(out)
        t2 = time.perf_counter()
        first.append((t1 - t0) * 1e3)
        second.append((t2 - t0) * 1e3)
    found = {}
    for name, times in zip(names, (first, second)):
        q = statistics.quantiles(times, n=4)
        found.update({f"{name}_ms": statistics.median(times),
                      f"{name}_quartiles_ms": [q[0], q[2]],
                      f"{name}_p95_ms": sorted(times)[int(0.95 * repeats)]})
    return found


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=12_000_000,
                    help="rows a device")
    ap.add_argument("--fits", type=int, default=300)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip too: the times mean nothing")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu" and not args.rehearse:
        print("start_forms: needs the chip", file=sys.stderr)
        return 2

    mesh = create_mesh(devices=jax.devices())
    p = len(jax.devices())
    n = args.rows * p
    spec0 = data_pspec(mesh)
    rows = NamedSharding(mesh, P(spec0))
    repl = NamedSharding(mesh, P())
    about = {"devices": p, "rows": n, "fits": args.fits,
             "device_kind": jax.devices()[0].device_kind}

    def say(form, found):
        print(json.dumps({"form": form, **about, **found}), flush=True)

    # -- the pieces alone -------------------------------------------------
    w_host = np.zeros(D, np.float32)
    leaves = (w_host, np.zeros((p,), np.int32), np.asarray(np.inf, np.float32))
    packed = np.zeros(D + p + 1, np.float32)
    small = mr.map_shards(
        lambda w, lo, hi: w + (hi - lo).astype(w.dtype), mesh,
        in_specs=(P(), P(), P()), out_specs=P())
    w_dev, lo_dev, hi_dev = jax.device_put(
        (w_host, np.int32(0), np.int32(20)), (repl, repl, repl))
    pieces = {
        "tree_put": lambda: jax.device_put(leaves, (repl, rows, repl)),
        "packed_put": lambda: jax.device_put(packed, repl),
        "call_resident": lambda: small(w_dev, lo_dev, hi_dev),
        "call_vector": lambda: small(w_host, lo_dev, hi_dev),
        "call_scalars": lambda: small(w_dev, np.int32(0), np.int32(20)),
    }
    for piece in pieces.values():  # warm: every signature compiled
        jax.block_until_ready(piece())
    for name, piece in pieces.items():
        say(name, timed(piece, args.fits))

    # -- the whole start, with the fit's own program ----------------------
    xs = jax.jit(lambda key: jax.random.uniform(key, (n, D), jnp.float32),
                 out_shardings=NamedSharding(mesh, P(spec0, None)))(
                     jax.random.key(39))
    ys = jax.jit(lambda x: (x[:, 0] > 0.5).astype(jnp.float32),
                 out_shardings=rows)(xs)
    prm = SGDParams(learning_rate=0.1, global_batch_size=100_000,
                    max_iter=20, tol=1e-6)
    seg = {fresh: _build_sgd_segment_program(
        BinaryLogisticLoss, mesh, prm, fused=True, weighted=False,
        fresh=fresh) for fresh in (False, True)}

    def carry():
        coeffs, offsets, _ = jax.device_put(leaves, (repl, rows, repl))
        coeffs, _, _, mean_loss, boundary = seg[False](
            xs, ys, None, coeffs, offsets, (), np.int32(0),
            np.int32(prm.max_iter))
        return boundary, coeffs, mean_loss

    def fresh():
        coeffs, _, _, mean_loss, boundary = seg[True](xs, ys, None, w_host)
        return boundary, coeffs, mean_loss

    starts = {"carry": carry, "fresh": fresh}
    answers = {name: [np.asarray(v).tolist() for v in read_boundary(start())]
               for name, start in starts.items()}
    for name, start in starts.items():
        say(name, timed(start, args.fits, finish=read_boundary,
                        names=("launch", "to_host")))
    same = answers["carry"] == answers["fresh"]
    print(json.dumps({"fresh_answers_as_carry": same,
                      "coefficients_head": answers["fresh"][1][:3]}),
          flush=True)
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
