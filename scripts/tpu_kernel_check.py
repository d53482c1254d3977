"""On-chip pallas kernel parity check.

The interpret-mode tests prove the kernels' math on CPU; this script
proves the MOSAIC LOWERING on the real chip: every kernel in
``ops/pallas_kernels.py`` is compiled — never interpreted — and run at
small scale against a numpy oracle, then at the shapes the benchmark
fits use against its XLA twin on the same chip. Each check prints its
observed maximum error. Exit 0 = all kernels agree, 2 = a kernel
produced wrong results, 3 = a kernel failed to lower, compile or run.
Either failure is terminal for the run that selected the kernel: no call
site retries on the XLA twin, so the fix is in the kernel or in its shape
gate.

Run on the TPU backend: ``python scripts/tpu_kernel_check.py``
(``python chip_smoke.py`` calls ``main()`` in-process as its kernels
phase). ``--shrink N`` divides the benchmark-scale shapes so CI can run
the whole script in interpreter mode; ``--small-only`` skips that phase.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def main(shrink: int = 1, small_only: bool = False) -> int:
    import jax

    if jax.default_backend() == "cpu":
        print("kernel check needs the TPU backend", file=sys.stderr)
        return 1
    from flink_ml_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(7)
    failures, errors = [], []

    def check(name, fn, oracle, rtol=1e-4, atol=1e-4):
        try:
            got = np.asarray(fn())
        except Exception as e:  # noqa: BLE001 — record, keep checking
            errors.append(f"{name}: {type(e).__name__}: {e}")
            return
        oracle = np.asarray(oracle)
        err = (float(np.max(np.abs(got - oracle)))
               if got.shape == oracle.shape and got.size else float("nan"))
        scale = float(np.max(np.abs(oracle))) if oracle.size else 0.0
        try:
            np.testing.assert_allclose(got, oracle, rtol=rtol, atol=atol)
            print(f"{name}: OK (max abs err {err:.3g}, oracle max "
                  f"{scale:.3g})", flush=True)
        except AssertionError as e:
            failures.append(f"{name}: {e}")

    # index checks are TIE-TOLERANT: the kernel's csq − 2·c·x matmul is
    # float32 but not the oracle's direct differences, so near-equidistant
    # points may pick a different (equally valid) winner — compare the
    # DISTANCE at the chosen index against the oracle's best distance
    # instead of the index itself.
    x = rng.normal(size=(2048, 16)).astype(np.float32)
    c = rng.normal(size=(5, 16)).astype(np.float32) * 4
    d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    check("assign_nearest(dist@chosen)",
          lambda: d2[np.arange(len(x)), np.asarray(pk.assign_nearest(x, c))],
          d2.min(1), rtol=1e-3, atol=1e-2)

    # train set spans MULTIPLE KNN_TILE_T tiles (with a ragged final
    # tile) so the streamed carry/merge lowering is what gets proven,
    # not just the single-tile case
    train = rng.normal(size=(pk.KNN_TILE_T + 517, 16)).astype(np.float32)
    dt = ((x[:, None, :] - train[None, :, :]) ** 2).sum(-1)

    def knn_dists():
        idx = np.asarray(pk.knn_topk_indices(x, train, 3))  # (n, 3)
        return dt[np.arange(len(x))[:, None], idx]

    # full top-k machinery (mask + select passes), not just column 0:
    # distances at the chosen k indices must match the k smallest
    # distances in order. Tolerance from the chip (PR 21): the kernel's
    # x·tᵀ runs at the TPU's default matmul precision, so among
    # near-equidistant rows it may rank a neighbour whose exact distance
    # is up to 0.74 % (0.062 absolute) larger than the oracle's pick.
    check("knn_topk_indices(dists@chosen)", knn_dists,
          np.sort(dt, axis=1)[:, :3], rtol=2e-2, atol=0.1)

    # WELL-SEPARATED clusters so assignment ties are implausible, and
    # generous tolerances: the check hunts wrong lowerings (wrong
    # tiles/accumulation), not TPU matmul rounding
    cw = rng.normal(size=(5, 16)).astype(np.float32) * 10
    xw = (cw[rng.integers(0, 5, 2048)]
          + rng.normal(size=(2048, 16)).astype(np.float32) * 0.1) \
        .astype(np.float32)
    dw = ((xw[:, None, :] - cw[None, :, :]) ** 2).sum(-1)
    n_valid = 1900  # the rows from here on stand for a shard's padding
    v = (np.arange(2048) < n_valid).astype(np.float32)
    one_hot = (dw.argmin(1)[:, None] == np.arange(5)[None, :]) * v[:, None]
    lloyd_want = np.concatenate(
        [one_hot.T @ xw, one_hot.sum(0)[:, None]], axis=1)
    check("lloyd_partial_sums",
          lambda: pk.lloyd_partial_sums(xw, n_valid, cw),
          lloyd_want, rtol=5e-2, atol=0.5)

    # the two KMeans kernels' float32 products are three bfloat16 parts an
    # operand (pk._split3), multiplied on the MXU and added in float32: on
    # the benchmark's own kind of data (uniform, so rows do lie near ties)
    # the chosen centroid is the float64-nearest but for four float32
    # roundings of the terms csq - 2 c.x cancels, predict's assignment is
    # the fit kernel's row for row, and the sums are the float64 sums of
    # those rows. At the benchmark's (k, d), at 3k over the MXU's 128
    # columns, at k not a whole number of sublane tiles, at a narrower tile
    # (a generator of its own: the checks below keep the data they had)
    uniform = np.random.default_rng(30)
    for k, d in ((10, 100), (50, 100), (4, 6), (200, 64)):
        tile = pk.lloyd_tile(k, d)
        rows = 2 * tile + 77                     # a ragged last tile
        counted = rows - 50                      # and a shard's padding
        xs, cs = (uniform.random(shape, np.float32)
                  for shape in ((rows, d), (k, d)))
        x64, c64 = xs.astype(np.float64), cs.astype(np.float64)
        exact = ((x64[:, None, :] - c64[None]) ** 2).sum(-1)
        terms = (c64 ** 2).sum(1).max() + 2 * (x64 @ c64.T).max(1)
        chosen = {}

        def regret(k=k, d=d, xs=xs, cs=cs, exact=exact, terms=terms,
                   chosen=chosen):
            chosen["v"] = np.asarray(pk.assign_nearest(xs, cs))
            return (exact[np.arange(len(xs)), chosen["v"]]
                    - exact.min(1)) / terms

        check(f"assign_nearest[k {k}, d {d}, tile {tile}](regret)", regret,
              np.zeros(rows), rtol=0, atol=4 * 2.0 ** -24)
        if "v" not in chosen:
            continue
        one_hot = (chosen["v"][:counted, None]
                   == np.arange(k)[None]).astype(np.float64)
        packed = {}

        def counts(xs=xs, cs=cs, counted=counted, packed=packed):
            packed["v"] = np.asarray(
                pk.lloyd_partial_sums(xs, counted, cs), np.float64)
            return packed["v"][:, -1]

        check(f"lloyd_partial_sums[k {k}, d {d}](counts = assign's)",
              counts, one_hot.sum(0), rtol=0, atol=0)
        if "v" in packed:
            want = one_hot.T @ x64[:counted]
            check(f"lloyd_partial_sums[k {k}, d {d}](sums, relative)",
                  lambda packed=packed, want=want:
                  (packed["v"][:, :-1] - want) / np.maximum(want, 1.0),
                  np.zeros_like(want), rtol=0, atol=1e-6)

    # segment-reduce: out-of-range ids (negative padding included) must
    # drop like jax.ops.segment_sum; a ragged final tile; one and two
    # value columns
    ns, us = 3 * pk.SEGREDUCE_TILE_N + 77, 37
    sv = rng.normal(size=(ns, 2)).astype(np.float32)
    sid = rng.integers(-2, us + 2, ns).astype(np.int32)
    keep = (sid >= 0) & (sid < us)
    seg_want = np.zeros((us, 2), np.float64)
    np.add.at(seg_want, sid[keep], sv[keep].astype(np.float64))
    check("segment_reduce_sum(2 cols)",
          lambda: pk.segment_reduce_sum(sv, sid, us), seg_want,
          rtol=2e-2, atol=0.2)
    check("segment_reduce_sum(1 col)",
          lambda: pk.segment_reduce_sum(sv[:, 0], sid, us), seg_want[:, 0],
          rtol=2e-2, atol=0.2)

    # contingency counts: exact. A ragged last tile, rows past n_valid,
    # entries that are not whole numbers in range (counted nowhere); an
    # even arity, an odd one, and one past the kernel's unrolled loop
    def counts_oracle(xc, yc, n_valid, labels, values):
        y_ok = (yc == np.floor(yc)) & (yc >= 0) & (yc < labels)
        y_ok &= np.arange(len(yc)) < n_valid
        with np.errstate(invalid="ignore"):
            ok = y_ok[:, None] & (xc == np.floor(xc)) & (xc >= 0) & (
                xc < values)
        rows, cols = np.nonzero(ok)
        want = np.zeros((values, labels, xc.shape[1]), np.int64)
        np.add.at(want, (xc[rows, cols].astype(np.int64),
                         yc[rows].astype(np.int64), cols), 1)
        return want

    for nc, dc, lc, vc in ((3 * pk.COUNTS_TILES_N[0] + 77, 100, 10, 20),
                           (5000, 7, 3, 41)):
        xc = np.floor(rng.random((nc, dc)) * vc).astype(np.float32)
        yc = np.floor(rng.random(nc) * lc).astype(np.float32)
        xc[3, 1], xc[4, 0], xc[5, 1], xc[6, 1] = 0.5, -1.0, vc, np.nan
        yc[7], yc[8] = lc, 0.25
        check(f"category_counts(n {nc}, d {dc}, L {lc}, V {vc})",
              lambda: pk.category_counts(xc, yc, nc - 5, lc, vc),
              counts_oracle(xc, yc, nc - 5, lc, vc), rtol=0, atol=0)

    # grouped moments: exact. The kernel's digit sums against float64
    # sums of the same float32 values, by class: a ragged last tile, rows
    # past n_valid, a label out of range (added nowhere), a column about a
    # far pivot; where every |w| <= 1 the sums of w are exact, the sums of
    # the float32 w * w exact to the digits' last place (2**-33 a row)
    from flink_ml_tpu.ops.fixedpoint import MOMENTS_DIGITS, digits_value

    for nm, dm, lm in ((3 * pk.MOMENTS_TILES_N[0] + 77, 100, 10),
                       (5000, 7, 3)):
        xm = rng.random((nm, dm)).astype(np.float32)
        xm[:, 1] += 1000.0
        ym = np.floor(rng.random(nm) * lm).astype(np.float32)
        ym[7], ym[8] = lm, 0.25
        pivot = np.zeros(dm, np.float32)
        pivot[1] = 1000.0
        inv = np.full(dm, 0.5, np.float32)
        live = (np.arange(nm) < nm - 5)[:, None]
        w = ((xm - pivot) * inv).astype(np.float32)
        hot = (ym[:, None] == np.arange(lm)) & live            # (n, L)
        want_s = hot.T.astype(np.float64) @ w.astype(np.float64)
        want_q = hot.T.astype(np.float64) @ (w * w).astype(np.float64)

        def moments(xm=xm, ym=ym, nm=nm, lm=lm, pivot=pivot, inv=inv):
            lo, hi, counts, top = (np.asarray(a) for a in pk.grouped_moments(
                xm, ym, nm - 5, pivot, inv, lm))
            at = MOMENTS_DIGITS[0]
            return np.concatenate([
                digits_value(lo[:at], hi[:at]).ravel(),
                digits_value(lo[at:], hi[at:]).ravel(),
                counts.astype(np.float64), top.astype(np.float64)])

        check(f"grouped_moments(n {nm}, d {dm}, L {lm})", moments,
              np.concatenate([want_s.ravel(), want_q.ravel(),
                              hot.sum(axis=0).astype(np.float64),
                              np.abs(np.where(live, w, 0)).max(axis=0)]),
              rtol=0, atol=nm * 2.0 ** -33)

    # -- benchmark-scale phase: kernel path vs the XLA path at the shapes
    # the fits use, both ON CHIP. The small-shape phase above proves the
    # lowering against numpy; this phase bounds kernel-vs-XLA drift at
    # scale (Lloyd partials at 1M x 100 k=10, KNN over a multi-tile 200k
    # train set, the FTRL sparse program's two segment-reduces, the
    # contingency counts at 1M x 100).
    if not small_only:
        import jax.numpy as jnp

        # Lloyd partials, north-star KMeans shape (1M x 100, k=10)
        nL, dL, kL = (1 << 20) // shrink, 100, 10
        cw2 = rng.normal(size=(kL, dL)).astype(np.float32) * 10
        xw2 = (cw2[rng.integers(0, kL, nL)]
               + rng.normal(size=(nL, dL)).astype(np.float32) * 0.1) \
            .astype(np.float32)
        @jax.jit
        def lloyd_xla(x, c):
            # matmul distance form (what measure.pairwise lowers to) — the
            # (n, k, d) broadcast form would materialize 4 GB here
            d2 = (jnp.sum(x * x, axis=1, keepdims=True)
                  - 2.0 * (x @ c.T) + jnp.sum(c * c, axis=1)[None, :])
            one_hot = jax.nn.one_hot(jnp.argmin(d2, axis=1), c.shape[0],
                                     dtype=x.dtype)
            return jnp.concatenate(
                [one_hot.T @ x, jnp.sum(one_hot, axis=0)[:, None]], axis=1)

        xd, cd = jnp.asarray(xw2), jnp.asarray(cw2)
        want = np.asarray(lloyd_xla(xd, cd))
        lloyd_got = {}

        def lloyd_run():
            lloyd_got["v"] = np.asarray(pk.lloyd_partial_sums(xd, nL, cd))
            return lloyd_got["v"][:, :-1]

        # relative tolerance on the accumulated sums; the counts column
        # is checked SEPARATELY with atol 0 — on well-separated clusters
        # any count drift means dropped/double-counted rows (the
        # wrong-tiles/accumulation bug class this phase hunts), so it
        # must not hide under a sums-scaled tolerance
        check("lloyd_partial_sums@1Mx100(sums)", lloyd_run, want[:, :-1],
              rtol=1e-3, atol=np.abs(want[:, :-1]).max() * 1e-4)
        if "v" in lloyd_got:
            check("lloyd_partial_sums@1Mx100(counts)",
                  lambda: lloyd_got["v"][:, -1], want[:, -1],
                  rtol=0, atol=0)

        # nearest-centroid assignment at the same shape: the exact
        # distance at the kernel's chosen centroid against the exact
        # distance at the XLA twin's choice (tie-tolerant, as above)
        @jax.jit
        def assign_xla(x, c):
            d2 = (jnp.sum(x * x, axis=1, keepdims=True)
                  - 2.0 * (x @ c.T) + jnp.sum(c * c, axis=1)[None, :])
            return jnp.argmin(d2, axis=1)

        @jax.jit
        def dist_at(x, c, idx):
            return jnp.sum(jnp.square(x - c[idx]), axis=1)

        best = np.asarray(dist_at(xd, cd, assign_xla(xd, cd)))
        check("assign_nearest@1Mx100(dist@chosen)",
              lambda: dist_at(xd, cd, pk.assign_nearest(xd, cd)), best,
              rtol=1e-3, atol=1e-2)

        # KNN streamed top-k over a multi-tile train set vs lax.top_k
        nK, dK, ntK, kK = (max(256, 4096 // shrink), 100,
                           max(pk.KNN_TILE_T + 257, 200_000 // shrink), 5)
        xk = rng.normal(size=(nK, dK)).astype(np.float32)
        tk = rng.normal(size=(ntK, dK)).astype(np.float32)
        xkd, tkd = jnp.asarray(xk), jnp.asarray(tk)

        @jax.jit
        def knn_xla(x, t):
            d2 = (jnp.sum(x * x, axis=1, keepdims=True)
                  - 2.0 * (x @ t.T) + jnp.sum(t * t, axis=1)[None, :])
            return jax.lax.top_k(-d2, kK)[1]

        idx_want = np.asarray(knn_xla(xkd, tkd))
        # index-tolerant at scale: compare the exact distances at the
        # chosen indices (float ties may legally pick different rows)
        dk_want = ((xk[:, None, :] - tk[idx_want][:, :, :]) ** 2).sum(-1)

        def knn_scale_dists():
            idx = np.asarray(pk.knn_topk_indices(xkd, tkd, kK))
            return ((xk[:, None, :] - tk[idx][:, :, :]) ** 2).sum(-1)

        check("knn_topk_indices@4kx200k", knn_scale_dists, dk_want,
              rtol=1e-2, atol=1.0)

        # segment-reduce at the FTRL sparse program's shapes
        # (models/online.py _ftrl_sparse_program): a 100k-row batch over
        # 8 shards pads to rows_s = 16384 > the row gate, so the shapes
        # that DO reach the kernel are a 2048-row shard block with ~16
        # non-zeros per row, a (nnz,) forward sum over rows and a
        # (nnz, 2) grad|weight sum over the d = 100 coordinates
        rowsF, dF = max(64, 2048 // shrink), 100
        nnzF = rowsF * 16
        rowF = np.sort(rng.integers(0, rowsF, nnzF)).astype(np.int32)
        colF = rng.integers(0, dF, nnzF).astype(np.int32)
        valF = rng.normal(size=nnzF).astype(np.float32)
        gwF = np.stack([valF * rng.normal(size=nnzF).astype(np.float32),
                        np.ones(nnzF, np.float32)], axis=1)
        if not (pk.segment_reduce_fits(rowsF, 1)
                and pk.segment_reduce_fits(dF, 2)):
            errors.append("segment_reduce_sum@ftrl: gate refuses the shape")
        else:
            rowFd, colFd = jnp.asarray(rowF), jnp.asarray(colF)
            valFd, gwFd = jnp.asarray(valF), jnp.asarray(gwF)
            seg_xla = jax.jit(jax.ops.segment_sum,
                              static_argnames="num_segments")
            want = np.asarray(seg_xla(valFd, rowFd, num_segments=rowsF))
            check(f"segment_reduce_sum@ftrl(rows {rowsF})",
                  lambda: pk.segment_reduce_sum(valFd, rowFd, rowsF), want,
                  rtol=2e-2, atol=max(0.05, np.abs(want).max() * 1e-2))
            want = np.asarray(seg_xla(gwFd, colFd, num_segments=dF))
            check(f"segment_reduce_sum@ftrl(coords {dF})",
                  lambda: pk.segment_reduce_sum(gwFd, colFd, dF), want,
                  rtol=2e-2, atol=max(0.05, np.abs(want).max() * 1e-2))

        # contingency counts at the NaiveBayes benchmark's shapes (d 100,
        # 20 values, 10 labels) against the XLA loop of one-hot products:
        # both exact, so equal
        from flink_ml_tpu.ops.contingency import category_counts_xla

        nN, dN, lN, vN = (1 << 20) // shrink + 13, 100, 10, 20
        xN = jnp.asarray(np.floor(rng.random((nN, dN)) * vN), jnp.float32)
        yN = jnp.asarray(np.floor(rng.random(nN) * lN), jnp.float32)
        one_hot = (jnp.float32 if jax.devices()[0].platform == "cpu"
                   else jnp.bfloat16)   # XLA's CPU multiplies no bfloat16
        want = np.asarray(jax.jit(
            category_counts_xla, static_argnums=(3, 4, 5))(
                xN, yN, nN - 5, lN, vN, one_hot))
        check(f"category_counts@nb(n {nN})",
              lambda: pk.category_counts(xN, yN, nN - 5, lN, vN), want,
              rtol=0, atol=0)

    for f in failures:
        print("PARITY FAILURE:", f, file=sys.stderr)
    for e in errors:
        print("KERNEL ERROR:", e, file=sys.stderr)
    if failures:
        return 2
    if errors:
        return 3
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser(prog="tpu-kernel-check")
    parser.add_argument("--shrink", type=int, default=1,
                        help="divide the benchmark-scale shapes (a power "
                        "of two; CI runs the script interpreted)")
    parser.add_argument("--small-only", action="store_true",
                        help="skip the benchmark-scale phase")
    cli = parser.parse_args()
    sys.exit(main(shrink=cli.shrink, small_only=cli.small_only))
