"""What an SGD round's device time is, by the form its batch is read in: the
record behind a round reading its minibatch from HBM once (PR 41). Two
forms of the same products, ``_sgd_update_math``'s margins and gradient:

    hbm      the window sliced ``(rows, d)`` out of the table and read by
             each product where it lies: two HBM reads of the batch a round
             (the form past ``ONCHIP_BATCH_BYTES``, and every round's until
             PR 41)
    onchip   the window sliced ``(d, rows)`` out of the column-major table
             behind ``optimization_barrier``: made once, in on-chip memory,
             and both products read it there

On the chip, at the LR cells' shapes (12M x 100 float32 on one device; a
batch of 100,000 rows, the one-chip cell's, and of 25,000, what a task of
the four-chip cell takes), each form's plain-fit program (``jit_sgd_segment``,
fresh, no weight column) runs at 20 and at 220 rounds, the calls of the
four interleaved; a round's device time is the slope, (t220 - t20) / 200,
so the launch and the read of the answer drop out. The two forms' 20-round
coefficients are compared (the same float32 products, summed in another
order).

    python scripts/round_forms.py [--fits 60] [--rehearse]
    python scripts/round_forms.py --gate [--d 100]

``--gate`` needs no chip: it bisects, by the TPU compiler ahead of time for
a v5e, the batch rows at which XLA stops placing the made window in on-chip
memory (``S(1)`` in its layout) at width ``d``: the reading behind
``ONCHIP_BATCH_BYTES``. One JSON line a form or a probe. The timing exits 2
off the chip unless ``--rehearse`` says the run is there to find faults.
"""

import argparse
import contextlib
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from flink_ml_tpu.ops import optimizer  # noqa: E402
from flink_ml_tpu.ops.losses import BinaryLogisticLoss  # noqa: E402
from flink_ml_tpu.parallel.mesh import create_mesh  # noqa: E402

D = 100
ROUNDS = (20, 220)
BUDGET = optimizer.ONCHIP_BATCH_BYTES
#: the gate's budget a form is built and traced under
FORMS = {"hbm": 0, "onchip": BUDGET, "every-window-on-chip": 1 << 40}


@contextlib.contextmanager
def built(mesh, batch, rounds, form):
    """The plain fit's program with every round's window in ``form``: a
    program is traced at its first call, so call it inside."""
    optimizer.ONCHIP_BATCH_BYTES = FORMS[form]
    optimizer._build_sgd_segment_program.cache_clear()
    try:
        yield optimizer._build_sgd_segment_program(
            BinaryLogisticLoss, mesh,
            optimizer.SGDParams(learning_rate=0.1, global_batch_size=batch,
                                max_iter=rounds, tol=0.0),
            fused=True, weighted=False, fresh=True)
    finally:
        optimizer.ONCHIP_BATCH_BYTES = BUDGET
        optimizer._build_sgd_segment_program.cache_clear()


def times(args):
    if jax.default_backend() != "tpu" and not args.rehearse:
        print("round_forms: needs the chip", file=sys.stderr)
        return 2
    mesh = create_mesh(devices=jax.devices()[:1])
    rows = NamedSharding(mesh, P("data"))
    xs = jax.jit(lambda key: jax.random.uniform(key, (args.rows, D)),
                 out_shardings=rows)(jax.random.key(41))
    ys = jax.jit(lambda x: (x[:, 0] > 0.5).astype(jnp.float32),
                 out_shardings=rows)(xs)
    w0 = np.zeros(D, np.float32)
    about = {"rows": args.rows, "d": D, "fits": args.fits,
             "device_kind": jax.devices()[0].device_kind}
    same = True
    for batch in args.batches:
        progs = {}
        for form in ("hbm", "onchip"):
            for rounds in ROUNDS:
                with built(mesh, batch, rounds, form) as prog:
                    jax.block_until_ready(prog(xs, ys, None, w0))
                progs[form, rounds] = prog
        walls = {key: [] for key in progs}
        for _ in range(args.fits):
            for key, prog in progs.items():
                t = time.perf_counter()
                jax.block_until_ready(prog(xs, ys, None, w0))
                walls[key].append((time.perf_counter() - t) * 1e3)
        answers = {form: np.asarray(progs[form, ROUNDS[0]](
            xs, ys, None, w0)[0], np.float64) for form in ("hbm", "onchip")}
        gap = float(np.abs(answers["hbm"] - answers["onchip"]).max()
                    / np.abs(answers["hbm"]).max())
        same = same and gap < 1e-5
        for form in ("hbm", "onchip"):
            med = {r: statistics.median(walls[form, r]) for r in ROUNDS}
            round_us = (med[ROUNDS[1]] - med[ROUNDS[0]]) / (
                ROUNDS[1] - ROUNDS[0]) * 1e3
            window = batch * (-(-D // 8) * 8) * 4
            print(json.dumps({
                "form": form, "batch": batch, **about,
                "fit_ms": {str(r): med[r] for r in ROUNDS},
                "quartiles_ms": {str(r): statistics.quantiles(
                    walls[form, r], n=4)[::2] for r in ROUNDS},
                "round_us": round_us,
                "window_bytes": window,
                "window_gb_per_s": window / round_us / 1e3,
                "coef_gap_vs_hbm": gap}), flush=True)
    return 0 if same else 1


def probe(mesh, rows, d):
    """``(layout of the made window, temporaries)`` at ``rows`` x ``d``."""
    n = max(12_000_000, rows)
    with built(mesh, rows, 20, "every-window-on-chip") as prog:
        compiled = prog.lower(
            jax.ShapeDtypeStruct((n, d), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data"))),
            jax.ShapeDtypeStruct((n,), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data"))),
            None, jax.ShapeDtypeStruct((d,), jnp.float32,
                                       sharding=NamedSharding(mesh, P())),
        ).compile()
    found = re.search(rf"= f32\[{d},{rows}\]\{{([^}}]*)\}}",
                      compiled.as_text())
    return (found.group(1) if found else None,
            compiled.memory_analysis().temp_size_in_bytes)


def gate(args):
    from jax.experimental import topologies

    chip = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    mesh = create_mesh(devices=[chip])
    pad = -(-args.d // 8) * 8
    lo, hi = 1024, (1 << 28) // (pad * 4)  # 256 MiB: twice a v5e's VMEM
    while hi - lo > 128:
        mid = (lo + hi) // 2 // 128 * 128
        layout, temp = probe(mesh, mid, args.d)
        onchip = layout is not None and "S(1)" in layout
        print(json.dumps({"probe_rows": mid, "d": args.d, "layout": layout,
                          "temp_bytes": temp, "onchip": onchip}), flush=True)
        lo, hi = (mid, hi) if onchip else (lo, mid)
    print(json.dumps({"d": args.d, "onchip_rows": lo,
                      "onchip_bytes": lo * pad * 4, "hbm_rows": hi,
                      "hbm_bytes": hi * pad * 4,
                      "budget_bytes": BUDGET,
                      "budget_margin": lo * pad * 4 / BUDGET}), flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=12_000_000)
    ap.add_argument("--batches", type=lambda s: [int(v) for v in
                                                 s.split(",")],
                    default=[100_000, 25_000])
    ap.add_argument("--fits", type=int, default=60)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip too: the times mean nothing")
    ap.add_argument("--gate", action="store_true",
                    help="bisect the on-chip window ahead of time, no chip")
    ap.add_argument("--d", type=int, default=D)
    args = ap.parse_args(argv)
    return gate(args) if args.gate else times(args)


if __name__ == "__main__":
    sys.exit(main())
