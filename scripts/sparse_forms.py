"""What a sparse SGD round's two products cost on the device, by form: the
record behind the form ``ops/sparse_window.py`` keeps.

At the click-through cell's shapes
(``benchmarks/configs/criteo-hashed-lr.json``: the benchmark's own
``CriteoHashedGenerator`` table, 23M rows of 39 hashed entries in 2^18
buckets, on one device) the first round's window, 100,000 rows as the
column lies, ``(39, rows)`` ids and values, and multipliers in
(-1, 1) are made once; then each form runs alone, ``--calls`` calls
enqueued back to back and waited on once, three times; a form's time is
the median of the three over the calls. Forms of the gradient (a
scatter-add of 3.9M terms into 262,144 float32 buckets):

    scatter          XLA ``.at[ids].add`` over every entry of the window
    segment-sum      ``jax.ops.segment_sum`` over the flattened window
    sorted-scatter   the entries sorted by id, then ``.at[ids].add`` told
                     the ids are sorted
    split-scatter    the program's (``sparse_window.products``): the
                     entries whose id is one bucket on every row (the
                     column's ``hot`` index) summed as columns, the rest
                     through ``.at[ids].add``
    split-sorted     the same with the rest sorted first
    pallas-vmem      a kernel that keeps the 1 MiB gradient in VMEM and
                     adds one entry at a time (ids and terms in SMEM)

and of the margins (a gather of the coefficients at 3.9M ids and a sum a
row): ``gather`` over every entry, ``split-gather`` the split form. The
``dictionary`` form, the program's (gradient and margins): the narrow
positions the column's index names (at most ``sparse_window.NARROW_MAX``
buckets over the table) by their dictionaries' compare, select and sum, the
hot ones as columns, the rest gathered and scattered; its two halves alone
(``dict-part``: the narrow positions' compares, nothing else); and for each
width of ``--dict-widths``, one position's ids against a dictionary of that
many slots (compare, select and sum over the window's rows, both ways): a
slot's cost a row, which with the serial cost of an entry (``split-scatter``
over its entries) sets ``NARROW_MAX``. Before all of them the ``index``
forms, what a column's dictionaries cost in set-up: the column's index
program alone (``proposal``: the ids' range, the hot positions, each
position's dictionary proposed from its sorted sample), the check a fit
makes of the rows it reads (``check``: the published 20 rounds of
``--batch`` rows against the proposed dictionaries, the program
``DeviceSparseColumn.narrow_through`` runs), and the check of every row of
the table an index made before that (``whole-table``: each proposed
dictionary of at most 1,024 and of at most 6,144 buckets, 128 slots a
step); ``--index-only`` stops after them. Then,
for each width of ``--hot-sets``, each cold position's most frequent ids
in the window taken by compare, select and sum and the rest scattered and
gathered with those entries sent out of bounds (``gradient``,
``margins``), and the dropped scatter and the filled gather alone. Every
gradient and margins form is held to float64 NumPy on the same window
(``gap``: max abs difference over max abs), and a form that does not add
every entry reads about 1. The program's round in place: the plain-fit
program at 20 and 40 rounds with each gradient form it can build
(``scatter``: no index; ``split-scatter``: the hot index alone;
``split-dict-scatter``: the column's), a round the slope
(``--no-in-program`` leaves it out). The first line also times the column's
index program (``index_first_s`` with its compile, ``index_warm_s``).

    python scripts/sparse_forms.py [--calls 20] [--hot-sets 64,256,1024]
                                   [--dict-widths 256,1024,2048,4096]
                                   [--index-only]
    python scripts/sparse_forms.py --rehearse --rows 200000 --batch 10000

One JSON line a form; the lines also go to ``chiprun_out/sparse_forms.json``.
Off the chip it exits 2 unless ``--rehearse`` says the run is there to find
faults (its times mean nothing).
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from flink_ml_tpu.linalg import sparse  # noqa: E402
from flink_ml_tpu.linalg.sparse import device_sparse_column  # noqa: E402
from flink_ml_tpu.ops import optimizer, sparse_window  # noqa: E402
from flink_ml_tpu.ops.losses import BinaryLogisticLoss  # noqa: E402
from flink_ml_tpu.parallel.mesh import create_mesh  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmarks", "configs", "criteo-hashed-lr.json")
ROUNDS = (20, 40)
#: the caps of the whole-table check timed: the one it ran at, and 6,144
WHOLE_TABLE_CAPS = (1024, 6144)


def _sorted(ids, terms):
    ids, terms = jax.lax.sort((ids.ravel(), terms.ravel()), num_keys=1)
    return ids, terms


def _split(hot, k):
    at = [j for j, _ in hot]
    return (np.asarray([j for j in range(k) if j not in at]),
            np.asarray(at, np.int32), np.asarray([b for _, b in hot]))


def gradient_forms(size, hot, k):
    cold, at, buckets = _split(hot, k)

    def hot_part(grad, vals, mult):
        return grad.at[buckets].add(jnp.sum(vals[at] * mult[None, :], axis=1))

    def scatter(ids, vals, mult):
        return sparse_window.scatter_add(ids, vals * mult[None, :], size)

    def segment_sum(ids, vals, mult):
        return jax.ops.segment_sum((vals * mult[None, :]).ravel(),
                                   ids.ravel(), size)

    def sorted_scatter(ids, vals, mult):
        ids, terms = _sorted(ids, vals * mult[None, :])
        return jnp.zeros((size,), terms.dtype).at[ids].add(
            terms, indices_are_sorted=True, mode="promise_in_bounds",
            wrap_negative_indices=False)

    def split_scatter(ids, vals, mult):
        return sparse_window.products(ids, vals, size, hot)[1](mult)

    def split_sorted(ids, vals, mult):
        grad = sorted_scatter(ids[cold], vals[cold], mult)
        return hot_part(grad, vals, mult)

    def pallas_vmem(ids, vals, mult):
        return _pallas_scatter(ids.ravel(), (vals * mult[None, :]).ravel(),
                               size)

    return {"scatter": scatter, "segment-sum": segment_sum,
            "sorted-scatter": sorted_scatter, "split-scatter": split_scatter,
            "split-sorted": split_sorted, "pallas-vmem": pallas_vmem}


def hot_sets(ids, cold, size, width):
    """``(len(cold), width)`` int32: each cold entry position's ``width``
    most frequent ids in the window ``ids`` (host), padded with ``size``,
    a bucket no id holds."""
    out = np.full((len(cold), width), size, np.int32)
    for i, j in enumerate(cold):
        found, counts = np.unique(ids[j], return_counts=True)
        top = found[np.argsort(-counts, kind="stable")[:width]]
        out[i, :len(top)] = top
    return out


def hot_set_forms(size, hot, k, sets):
    """The gradient and the margins with each cold entry position's hot set
    (``sets``) taken by compare, select and sum, the rest scattered and
    gathered with the set's entries sent out of bounds (dropped, filled);
    and the dropped scatter and the filled gather alone."""
    cold, at, buckets = _split(hot, k)
    sets = jnp.asarray(sets)

    def part(ids, vals):
        ic, vc = ids[cold], vals[cold]
        eq = ic[:, :, None] == sets[:, None, :]
        return ic, vc, eq, jnp.where(jnp.any(eq, axis=2), size, ic)

    def gradient(ids, vals, mult):
        ic, vc, eq, rest = part(ids, vals)
        terms = vc * mult[None, :]
        sums = jnp.sum(jnp.where(eq, terms[:, :, None], 0.0), axis=1)
        grad = jnp.zeros((size,), terms.dtype).at[rest].add(terms,
                                                             mode="drop")
        grad = grad.at[sets].add(sums, mode="drop")
        return grad.at[buckets].add(jnp.sum(vals[at] * mult[None, :], axis=1))

    def dropped_scatter(ids, vals, mult):
        ic, vc, eq, rest = part(ids, vals)
        return jnp.zeros((size,), vc.dtype).at[rest].add(
            vc * mult[None, :], mode="drop")

    def margins(ids, vals, w):
        ic, vc, eq, rest = part(ids, vals)
        on = jnp.sum(jnp.where(eq, w.at[sets].get(
            mode="fill", fill_value=0.0)[:, None, :], 0.0), axis=2)
        off = w.at[rest].get(mode="fill", fill_value=0.0)
        return (jnp.sum((on + off) * vc, axis=0)
                + jnp.sum(w[buckets][:, None] * vals[at], axis=0))

    def filled_gather(ids, vals, w):
        ic, vc, eq, rest = part(ids, vals)
        return jnp.sum(w.at[rest].get(mode="fill", fill_value=0.0) * vc,
                       axis=0)

    return gradient, dropped_scatter, margins, filled_gather


def margin_forms(size, hot):
    def gather(ids, vals, w):
        return jnp.sum(sparse_window.gather(w, ids) * vals, axis=0)

    def split_gather(ids, vals, w):
        return sparse_window.products(ids, vals, size, hot)[0](w)

    return {"gather": gather, "split-gather": split_gather}


def dictionary_forms(column):
    """``{name: (product, operand)}``: the program's two products with the
    column's whole index (``dictionary``), and the narrow positions' part
    of each alone (``dict-part``: an index with no hot entry and every
    other position's ids and values left out)."""
    size, hot, narrow = column.size, column.hot, column.narrow
    at = np.asarray([j for j, _ in narrow])
    only = tuple((i, slots) for i, (_, slots) in enumerate(narrow))

    def full(which):
        return lambda ids, vals, dicts, arg: sparse_window.products(
            ids, vals, size, hot, narrow, dicts)[which](arg)

    def part(which):
        return lambda ids, vals, dicts, arg: sparse_window.products(
            ids[at], vals[at], size, (), only, dicts[at])[which](arg)

    return {("gradient", "dictionary"): full(1),
            ("margins", "dictionary"): full(0),
            ("gradient", "dict-part"): part(1),
            ("margins", "dict-part"): part(0)}


def width_forms(size, width):
    """One position's window ids against a dictionary of ``width`` slots,
    both ways, as ``sparse_window.products`` takes a narrow position."""
    layout = ((0, width),)

    def gradient(ids, vals, dicts, mult):
        return sparse_window.products(ids, vals, size, (), layout,
                                      dicts)[1](mult)

    def margins(ids, vals, dicts, w):
        return sparse_window.products(ids, vals, size, (), layout,
                                      dicts)[0](w)

    return gradient, margins


def whole_table_check(cap, chunk=128):
    """``check(ids, dicts, buckets, same) -> (k,)`` bool: the check an index
    made of every row of the table before a fit checked the rows it reads.
    Each position whose sample holds at most ``cap`` buckets and that is not
    hot is narrow only if every row's id is one of its dictionary's, counted
    by comparing the ids with ``chunk`` slots of one dictionary a step, read
    from the ids' transpose."""
    def check(ids, dicts, buckets, same):
        n, k = ids.shape
        candidate = jnp.logical_and(buckets <= cap, jnp.logical_not(same))
        trips = jnp.where(candidate, -(-buckets // chunk), 0)
        ends = jnp.cumsum(trips)
        lanes = ids.T

        def step(t, seen):
            j = jnp.sum(ends <= t)
            c = t - (ends[j] - trips[j])
            row = jax.lax.dynamic_index_in_dim(lanes, j, keepdims=False)
            d = jax.lax.dynamic_slice(dicts, (j, c * chunk), (1, chunk))
            hits = jnp.sum(row[None, :] == d[0][:, None], dtype=jnp.int32)
            return seen + jnp.where(jnp.arange(k) == j, hits, 0)

        matched = jax.lax.fori_loop(0, ends[-1], step,
                                    jnp.zeros((k,), jnp.int32))
        return jnp.logical_and(candidate, matched == n)

    return jax.jit(check)


def index_forms(column, mesh, rows, calls, out):
    """The index program alone, the check of a fit's ``rows`` against the
    proposed dictionaries, and the whole-table check at each cap of
    :data:`WHOLE_TABLE_CAPS`: one line each, with the slots compared."""
    index = sparse._index_program()
    ms, sets = timed(index, (column.ids,), calls)
    out({"index": "proposal", "ms": ms, "sets_ms": sets,
         "rows": len(column)})
    _, _, same, _, buckets, dicts = index(column.ids)
    narrow = column.narrow
    check = sparse._check_program(mesh, narrow, rows)
    args = (column.ids, column.dicts, np.int32(0), np.int32(len(column)))
    ms, sets = timed(check, args, calls)
    slots = sum(s for _, s in narrow)
    out({"index": "check", "ms": ms, "sets_ms": sets, "rows": rows,
         "positions": len(narrow), "slots": slots,
         "ps_per_slot_row": ms * 1e9 / (slots * rows),
         "misses": np.asarray(check(*args)).tolist()})
    host_buckets, host_same = np.asarray(buckets), np.asarray(same)
    for cap in WHOLE_TABLE_CAPS:
        taken = np.logical_and(host_buckets <= cap, ~host_same)
        slots = int(sum(sparse.dict_slots(int(b))
                        for b in host_buckets[taken]))
        fn = whole_table_check(cap)
        ms, sets = timed(fn, (column.ids, dicts, buckets, same),
                         max(1, calls // 5))
        out({"index": "whole-table", "cap": cap, "ms": ms, "sets_ms": sets,
             "rows": len(column), "positions": int(taken.sum()),
             "slots": slots,
             "ps_per_slot_row": ms * 1e9 / (max(slots, 1) * len(column))})


def _pallas_scatter(ids, terms, size, block=2048):
    """The gradient as one ``(size / 128, 128)`` VMEM block over the whole
    grid; each step brings ``block`` ids and terms into SMEM and adds them
    one at a time into their row of 128 lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e = ids.shape[0]
    pad = (-e) % block
    ids = jnp.pad(ids, (0, pad)).reshape(1, -1)
    terms = jnp.pad(terms, (0, pad)).reshape(1, -1)

    def kernel(ids_ref, terms_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

        def add(i, carry):
            b = ids_ref[0, i]
            row = pl.ds(b // 128, 1)
            out_ref[row, :] = out_ref[row, :] + jnp.where(
                lane == b % 128, terms_ref[0, i], 0.0)
            return carry

        jax.lax.fori_loop(0, block, add, 0)

    smem = functools.partial(pl.BlockSpec, (1, block), lambda g: (0, g),
                             memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel, grid=((e + pad) // block,),
        in_specs=[smem(), smem()],
        out_specs=pl.BlockSpec((size // 128, 128), lambda g: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((size // 128, 128), jnp.float32),
    )(ids, terms)
    return out.reshape(size)


def timed(fn, args, calls):
    """Median over three sets of the ms a call takes, ``calls`` enqueued
    back to back and waited on once (the first call compiles, untimed)."""
    jax.block_until_ready(fn(*args))
    sets = []
    for _ in range(3):
        t = time.perf_counter()
        out = [fn(*args) for _ in range(calls)]
        jax.block_until_ready(out)
        sets.append((time.perf_counter() - t) * 1e3 / calls)
    return statistics.median(sets), sets


def table(rows, mesh):
    from benchmarks.harness import generators, system

    with open(CONFIG) as f:
        data = dict(json.load(f)["inputData"]["paramMap"], numValues=rows)
    t = time.perf_counter()
    cols = generators.make_columns("CriteoHashedGenerator", data, 20260415,
                                   system.row_sharding(mesh))
    made = time.perf_counter() - t
    feats = cols["features"]
    t = time.perf_counter()
    column = device_sparse_column(feats["ids"], feats["values"],
                                  int(feats["size"]))
    index_s = time.perf_counter() - t
    t = time.perf_counter()
    device_sparse_column(feats["ids"], feats["values"], int(feats["size"]))
    return column, cols["label"], {"datagen_s": made, "index_first_s": index_s,
                                   "index_warm_s": time.perf_counter() - t}


def in_place(column, label, mesh, batch, calls, out):
    """The plain-fit program with each form it builds, a round the slope
    between ``ROUNDS``."""
    w0 = np.zeros(column.size, np.float32)
    fits = {}
    layouts = {"scatter": sparse_window.Layout(column.size),
               "split-scatter": sparse_window.Layout(column.size, column.hot),
               "split-dict-scatter": sparse_window.Layout(
                   column.size, column.hot, column.narrow)}
    for name, layout in layouts.items():
        xs = (column.ids, column.values) + ((column.dicts,) if layout.narrow
                                            else ())
        for rounds in ROUNDS:
            prog = optimizer._build_sgd_segment_program(
                BinaryLogisticLoss, mesh, optimizer.SGDParams(
                    learning_rate=0.1, global_batch_size=batch,
                    max_iter=rounds, tol=0.0),
                fused=True, weighted=False, fresh=True, sparse=layout)
            fits[name, rounds] = (timed(
                lambda: prog(xs, label, None, w0), (), max(2, calls // 4)),
                np.asarray(prog(xs, label, None, w0)[0]))
    for name in layouts:
        (lo, _), (hi, _) = (fits[name, r][0] for r in ROUNDS)
        gap = np.abs(fits[name, ROUNDS[0]][1] - fits["scatter", ROUNDS[0]][1])
        out({"in_program": name, "fit_ms": {str(r): fits[name, r][0][0]
                                            for r in ROUNDS},
             "round_ms": (hi - lo) / (ROUNDS[1] - ROUNDS[0]),
             "coef_gap_vs_scatter": float(
                 gap.max() / np.abs(fits["scatter", ROUNDS[0]][1]).max())})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=23_000_000)
    ap.add_argument("--batch", type=int, default=100_000)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--hot-sets", type=lambda v: [int(x) for x in
                                                  v.split(",") if x],
                    default=[256, 1024],
                    help="widths of the per-position hot sets to time")
    ap.add_argument("--dict-widths", type=lambda v: [int(x) for x in
                                                     v.split(",") if x],
                    default=[256, 1024, 2048, 4096],
                    help="dictionary widths to time on one position")
    ap.add_argument("--no-in-program", dest="in_program",
                    action="store_false",
                    help="leave out the fit programs' round times")
    ap.add_argument("--index-only", action="store_true",
                    help="time the index forms alone")
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip too: the times mean nothing")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu" and not args.rehearse:
        print("sparse_forms: needs the chip", file=sys.stderr)
        return 2
    lines = []

    def out(line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    mesh = create_mesh(devices=jax.devices()[:1])
    column, label, made = table(args.rows, mesh)
    k, size = column.entries, column.size
    proposed = [list(p) for p in column.narrow]
    index_forms(column, mesh, min(args.rows, ROUNDS[0] * args.batch),
                args.calls, out)
    # the rows the longest fit below reads, checked as a fit checks them
    t = time.perf_counter()
    _, checked, demoted = column.narrow_through(mesh,
                                                ROUNDS[-1] * args.batch)
    out({"rows": args.rows, "batch": args.batch, "entries": k, "size": size,
         "hot": len(column.hot), "calls": args.calls, "proposed": proposed,
         "narrow": [list(p) for p in column.narrow],
         "checked_rows": checked, "demoted": list(demoted),
         "narrow_through_s": time.perf_counter() - t,
         "device_kind": jax.devices()[0].device_kind, **made})
    if args.index_only:
        return save(lines)
    window = jax.jit(lambda a: jax.lax.optimization_barrier(
        a.T[:, :args.batch]))
    ids, vals = window(column.ids), window(column.values)
    mult = jax.random.uniform(jax.random.key(7), (args.batch,), jnp.float32,
                              -1.0, 1.0)
    w = jax.random.normal(jax.random.key(8), (size,), jnp.float32) * 0.01
    ids_h, vals_h = np.asarray(ids), np.asarray(vals, np.float64)
    want = np.bincount(ids_h.ravel(), (vals_h * np.asarray(
        mult, np.float64)[None, :]).ravel(), minlength=size)
    for name, fn in gradient_forms(size, column.hot, k).items():
        try:
            ms, sets = timed(jax.jit(fn), (ids, vals, mult), args.calls)
            got = np.asarray(jax.jit(fn)(ids, vals, mult), np.float64)
            out({"gradient": name, "ms": ms, "sets_ms": sets,
                 "entries_per_us": ids.size / ms / 1e3,
                 "gap": float(np.abs(got - want).max() / np.abs(want).max())})
        except Exception as exc:  # noqa: BLE001 — a failure is a reading
            out({"gradient": name, "failed": repr(exc)[:300]})
    dots = np.sum(np.asarray(w, np.float64)[ids_h] * vals_h, axis=0)
    for name, fn in margin_forms(size, column.hot).items():
        ms, sets = timed(jax.jit(fn), (ids, vals, w), args.calls)
        got = np.asarray(jax.jit(fn)(ids, vals, w), np.float64)
        out({"margins": name, "ms": ms, "sets_ms": sets,
             "gap": float(np.abs(got - dots).max() / np.abs(dots).max())})
    for (product, name), fn in dictionary_forms(column).items():
        arg = mult if product == "gradient" else w
        try:
            ms, sets = timed(jax.jit(fn), (ids, vals, column.dicts, arg),
                             args.calls)
            got = np.asarray(jax.jit(fn)(ids, vals, column.dicts, arg),
                             np.float64)
            line = {product: name, "ms": ms, "sets_ms": sets}
            if name == "dictionary":
                ref = want if product == "gradient" else dots
                line["gap"] = float(np.abs(got - ref).max()
                                    / np.abs(ref).max())
            out(line)
        except Exception as exc:  # noqa: BLE001 — a failure is a reading
            out({product: name, "failed": repr(exc)[:300]})
    cold = _split(column.hot, k)[0]
    for width in args.dict_widths:
        # the first cold position's ids against ``width`` slots, its
        # distinct ids first (a row matches one slot, as in the program)
        found = np.unique(ids_h[cold[0]])[:width]
        dicts = np.full((1, width), -1, np.int32)
        dicts[0, :len(found)] = found
        dicts = jnp.asarray(dicts)
        one = (ids[cold[0]:cold[0] + 1], vals[cold[0]:cold[0] + 1])
        for product, fn, arg in zip(("gradient", "margins"),
                                    width_forms(size, width), (mult, w)):
            try:
                ms, sets = timed(jax.jit(fn), (*one, dicts, arg),
                                 args.calls)
                out({"dict_width": width, "product": product, "ms": ms,
                     "sets_ms": sets,
                     "ps_per_slot_row": ms * 1e9 / (width * args.batch)})
            except Exception as exc:  # noqa: BLE001 — a failure is a reading
                out({"dict_width": width, "product": product,
                     "failed": repr(exc)[:300]})
    for width in args.hot_sets:
        sets = hot_sets(ids_h, cold, size, width)
        share = float(np.mean(np.isin(ids_h[cold], sets)))
        forms = dict(zip(("gradient", "dropped-scatter", "margins",
                          "filled-gather"),
                         hot_set_forms(size, column.hot, k, sets)))
        for name, fn in forms.items():
            arg = w if name in ("margins", "filled-gather") else mult
            try:
                ms, times = timed(jax.jit(fn), (ids, vals, arg), args.calls)
                got = np.asarray(jax.jit(fn)(ids, vals, arg), np.float64)
                ref = {"gradient": want, "margins": dots}.get(name)
                out({"hot_set": width, "form": name, "in_sets": share,
                     "ms": ms, "sets_ms": times,
                     "gap": None if ref is None else float(
                         np.abs(got - ref).max() / np.abs(ref).max())})
            except Exception as exc:  # noqa: BLE001 — a failure is a reading
                out({"hot_set": width, "form": name,
                     "failed": repr(exc)[:300]})
    if args.in_program:
        in_place(column, label, mesh, args.batch, args.calls, out)
    return save(lines)


def save(lines):
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sparse_forms.json"),
              "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
