"""Native kernel tests: C++ Swing core vs the Python oracle."""

import numpy as np
import pytest

from flink_ml_tpu import native
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.recommendation import Swing


def make_purchases(rng, n_users=40, n_items=25, per_user=8):
    users = np.repeat(np.arange(n_users), per_user)
    items = np.concatenate([rng.choice(n_items, per_user, replace=False)
                            for _ in range(n_users)])
    return Table.from_columns(user=users.astype(np.int64),
                              item=items.astype(np.int64))


import shutil

needs_gcc = pytest.mark.skipif(shutil.which("g++") is None,
                               reason="no g++ toolchain; Python fallback "
                                      "is a supported configuration")


@needs_gcc
def test_native_builds():
    assert native.available(), "g++ build of native kernels failed"


@needs_gcc
def test_native_rebuilds_on_key_mismatch_regardless_of_mtimes(monkeypatch):
    """What loads must have been compiled on THIS machine from THESE
    sources: the library is keyed by a hash of sources, flags and host
    CPU recorded beside it. A library that is newer than every source
    (the old mtime rule would keep it) but whose recorded key differs —
    a copy from another machine, an edited flag — is rebuilt."""
    import os
    import time

    assert native.available()
    assert native._recorded_key() == native._build_key()
    future = time.time() + 3600
    os.utime(native._LIB, (future, future))  # newer than the sources
    with open(native._KEY_FILE, "w") as f:
        f.write("built-somewhere-else")
    built = []
    real_run = native.subprocess.run

    def recording_run(cmd, *a, **kw):
        built.append(cmd[0])
        return real_run(cmd, *a, **kw)

    monkeypatch.setattr(native.subprocess, "run", recording_run)
    assert native._build() is not None
    assert built == ["g++"]
    assert native._recorded_key() == native._build_key()
    assert os.path.getmtime(native._LIB) < future
    # and a matching key is a no-op: nothing compiles
    assert native._build() is not None
    assert built == ["g++"]


@needs_gcc
def test_native_matches_python_oracle(rng):
    table = make_purchases(rng)
    op = Swing(min_user_behavior=2, k=5, alpha1=5, beta=0.5)
    users = np.asarray(table.column("user"), np.int64)
    items = np.asarray(table.column("item"), np.int64)
    user_items = {}
    for u, i in zip(users.tolist(), items.tolist()):
        user_items.setdefault(u, set()).add(i)
    user_items = {u: np.asarray(sorted(s), np.int64)
                  for u, s in user_items.items()
                  if op.min_user_behavior <= len(s) <= op.max_user_behavior}
    item_users = {}
    for u in user_items:
        for i in user_items[u].tolist():
            lst = item_users.setdefault(i, [])
            if len(lst) < op.max_user_num_per_item:
                lst.append(u)
    weights = {u: 1.0 / (op.alpha1 + len(s)) ** op.beta
               for u, s in user_items.items()}

    py = dict(op._score_python(user_items, item_users, weights, op.alpha2))
    cc = dict(op._score_native(user_items, item_users, weights, op.alpha2))
    assert set(py) == set(cc)
    for item in py:
        assert len(py[item]) == len(cc[item])
        for (ji, si), (jj, sj) in zip(py[item], cc[item]):
            assert ji == jj
            assert si == pytest.approx(sj, rel=1e-12)


@needs_gcc
def test_swing_transform_uses_native(rng):
    assert native.available()
    table = make_purchases(rng)
    out = Swing(min_user_behavior=2, k=4).transform(table)[0]
    assert out.num_rows > 0
    # every rec string parses as item,score pairs
    for rec in out["output"]:
        for pair in rec.split(";"):
            item, score = pair.split(",")
            int(item)
            float(score)


def test_csv_kernel_numeric_fast_path(tmp_path):
    from flink_ml_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    out = native.csv_parse_numeric(b"1,2\n3,4\n", 2)
    np.testing.assert_allclose(out, [[1, 2], [3, 4]])
    assert native.csv_parse_numeric(b"1,x\n", 2) is None  # fallback signal
    assert native.csv_parse_numeric(b"1\n", 2) is None    # short row


def test_table_csv_round_trip(tmp_path):
    from flink_ml_tpu.common.table import Table

    t = Table.from_columns(a=np.array([1.0, 2.0, 3.5]),
                           b=np.array([4.0, 5.0, 6.0]))
    p = tmp_path / "t.csv"
    t.to_csv(str(p))
    back = Table.from_csv(str(p))
    assert back.column_names == ["a", "b"]
    np.testing.assert_allclose(back["a"], t["a"])
    np.testing.assert_allclose(back["b"], t["b"])


def test_table_csv_mixed_columns(tmp_path):
    from flink_ml_tpu.common.table import Table

    p = tmp_path / "m.csv"
    p.write_text("x,label\n1.5,cat\n2.5,dog\n")
    t = Table.from_csv(str(p))
    np.testing.assert_allclose(t["x"], [1.5, 2.5])
    assert list(t["label"]) == ["cat", "dog"]

    # no-header variant with generated names
    p2 = tmp_path / "n.csv"
    p2.write_text("1,2\n3,4\n")
    t2 = Table.from_csv(str(p2), header=False)
    assert t2.column_names == ["c0", "c1"]
    np.testing.assert_allclose(t2["c0"], [1, 3])


def test_table_csv_end_to_end_fit(tmp_path, rng):
    """The full user path: csv file → Table → VectorAssembler → fit."""
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.models.feature import VectorAssembler

    x = rng.normal(size=(100, 2))
    y = (x @ [1.0, -1.0] > 0).astype(np.float64)
    Table.from_columns(f1=x[:, 0], f2=x[:, 1], label=y).to_csv(
        str(tmp_path / "train.csv"))

    t = Table.from_csv(str(tmp_path / "train.csv"))
    t = VectorAssembler(input_cols=["f1", "f2"],
                        output_col="features").transform(t)[0]
    model = LogisticRegression(max_iter=10, global_batch_size=50).fit(t)
    out = model.transform(t)[0]
    assert np.mean(out["prediction"] == t["label"]) > 0.9


def test_csv_edge_cases(tmp_path):
    from flink_ml_tpu import native
    from flink_ml_tpu.common.table import Table
    from flink_ml_tpu.linalg import Vectors

    if native.available():
        # whitespace-only line must defer to the general parser, not be
        # silently skipped by the fast path
        assert native.csv_parse_numeric(b" \n5\n", 1) is None

    # vector columns are rejected by to_csv
    col = np.empty(1, dtype=object)
    col[0] = Vectors.dense([1.0, 2.0])
    with pytest.raises(ValueError, match="scalar"):
        Table.from_columns(v=col).to_csv(str(tmp_path / "v.csv"))

    # quoted header cell containing the delimiter
    p = tmp_path / "q.csv"
    p.write_text('"last,first",age\n1,2\n')
    t = Table.from_csv(str(p))
    assert t.column_names == ["last,first", "age"]
    np.testing.assert_allclose(t["age"], [2.0])

    # explicit names with header=True: header skipped, names honored
    p2 = tmp_path / "h.csv"
    p2.write_text("a,b\n1,2\n")
    t2 = Table.from_csv(str(p2), names=["x", "y"])
    assert t2.column_names == ["x", "y"]
    np.testing.assert_allclose(t2["x"], [1.0])


def test_factorize_i64_matches_pandas_oracle():
    """Native factorize must produce EXACTLY pandas' first-appearance
    labels and distinct order (the contract _token_codes relies on),
    across collisions, duplicates, negatives and edge sizes."""
    import pytest

    pd = pytest.importorskip("pandas")

    from flink_ml_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native tier unavailable")
    rng = np.random.default_rng(3)
    cases = [
        rng.integers(-(1 << 62), 1 << 62, 10_000),
        rng.integers(0, 7, 50_000),              # tiny domain, many dups
        np.arange(1000)[::-1].astype(np.int64),  # all distinct, reversed
        np.zeros(17, np.int64),
        np.asarray([], np.int64),
        np.asarray([np.iinfo(np.int64).min, -1, 0, 1,
                    np.iinfo(np.int64).max] * 3, np.int64),
    ]
    for keys in cases:
        res = native.factorize_i64(keys)
        assert res is not None
        uniq, codes = res
        inv, pu = pd.factorize(keys, sort=False)
        np.testing.assert_array_equal(uniq, np.asarray(pu))
        np.testing.assert_array_equal(codes, np.asarray(inv, np.int64))


def test_factorize_i64_cap_falls_back():
    from flink_ml_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native tier unavailable")
    old = native.FACTORIZE_UNIQ_CAP
    native.FACTORIZE_UNIQ_CAP = 4
    try:
        assert native.factorize_i64(np.arange(100, dtype=np.int64)) is None
        uniq, codes = native.factorize_i64(
            np.asarray([5, 5, 9, 9], np.int64))
        np.testing.assert_array_equal(uniq, [5, 9])
        np.testing.assert_array_equal(codes, [0, 0, 1, 1])
    finally:
        native.FACTORIZE_UNIQ_CAP = old


def test_doc_freq_i64_matches_python_engines():
    """Native doc-freq must equal both python engines (bincount-matrix
    and row-sort) across small and large domains, including rows with
    repeated codes and u larger than any code present."""
    from flink_ml_tpu import native
    from flink_ml_tpu.models.feature.text import (
        _doc_freq_small_domain,
        _rowwise_counts,
    )

    if not native.available():
        import pytest
        pytest.skip("native tier unavailable")
    rng = np.random.default_rng(5)
    for n, w, u in [(200, 7, 5), (500, 3, 2000), (1, 1, 1), (50, 4, 4)]:
        mat = rng.integers(0, u, (n, w)).astype(np.int64)
        got = native.doc_freq_i64(mat, u)
        want_small = _doc_freq_small_domain(mat, u)
        _, starts, _ = _rowwise_counts(mat.copy(), with_counts=False)
        want_sort = np.bincount(starts, minlength=u)
        np.testing.assert_array_equal(got, want_small)
        np.testing.assert_array_equal(got, want_sort)
    # empty matrix
    np.testing.assert_array_equal(
        native.doc_freq_i64(np.zeros((0, 3), np.int64), 4), np.zeros(4))


def test_rowwise_counts_matches_python_engines():
    """Native per-row counter must equal all three python engines
    (k-pass, bincount-matrix, row-sort) across dtypes and domains,
    including empty and single-row edges."""
    from flink_ml_tpu import native
    from flink_ml_tpu.models.feature import text as text_mod

    if not native.available():
        pytest.skip("native tier unavailable")
    rng = np.random.default_rng(9)
    cases = [
        (300, 8, 5, np.uint8),      # k-pass domain
        (200, 6, 300, np.uint16),   # bincount domain
        (100, 4, 9000, np.uint32),  # larger domain
        (50, 5, 12, np.int64),
        (1, 1, 1, np.uint8),
    ]
    for n, w, u, dt in cases:
        mat = rng.integers(0, u, (n, w)).astype(dt)
        got = native.rowwise_counts(mat, u)
        assert got is not None, (u, dt)
        # python oracle: force the native path off
        orig = native.rowwise_counts
        try:
            native.rowwise_counts = lambda *a, **k: None
            want = text_mod._rowwise_counts(mat.copy(), domain=u)
        finally:
            native.rowwise_counts = orig
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], np.asarray(want[1], np.int64))
        np.testing.assert_array_equal(got[2], want[2])
    # domain beyond the cap falls back to python (returns None)
    assert native.rowwise_counts(
        np.zeros((2, 2), np.uint8), native.ROWWISE_DOMAIN_CAP + 1) is None


def test_doc_freq_i64_out_of_range_falls_back():
    """ADVICE r5 #1: codes outside [0, u) must NOT be silent heap
    corruption — the kernel bounds-checks and the wrapper returns None so
    callers fall back to the (IndexError-raising) python engines."""
    from flink_ml_tpu import native

    if not native.available():
        pytest.skip("native tier unavailable")
    assert native.doc_freq_i64(np.asarray([[0, 5]], np.int64), 3) is None
    assert native.doc_freq_i64(np.asarray([[-1, 0]], np.int64), 3) is None
    # in-range still works after the guard
    np.testing.assert_array_equal(
        native.doc_freq_i64(np.asarray([[0, 1], [2, 1]], np.int64), 3),
        [1, 2, 1])


def test_doc_freq_i64_domain_cap_falls_back():
    """ADVICE r5 #2: a mostly-distinct corpus (u ~ rows*w) must not
    allocate an 8*u-byte stamp per forked worker — above the shared
    ROWWISE_DOMAIN_CAP the wrapper returns None and the chunked python
    engines bound memory."""
    from flink_ml_tpu import native

    if not native.available():
        pytest.skip("native tier unavailable")
    mat = np.asarray([[0, 1]], np.int64)
    assert native.doc_freq_i64(mat, native.ROWWISE_DOMAIN_CAP + 1) is None
    assert native.doc_freq_i64(mat, 0) is None  # empty domain: fallback
    assert native.doc_freq_i64(mat, native.ROWWISE_DOMAIN_CAP // 2 + 2) \
        is not None


def test_rowwise_counts_out_of_range_falls_back():
    """Same guard for the rowwise counter, across the narrow dtypes."""
    from flink_ml_tpu import native

    if not native.available():
        pytest.skip("native tier unavailable")
    assert native.rowwise_counts(np.asarray([[9]], np.uint8), 4) is None
    assert native.rowwise_counts(np.asarray([[-2]], np.int64), 4) is None
    got = native.rowwise_counts(np.asarray([[3, 3, 1]], np.uint16), 4)
    np.testing.assert_array_equal(got[1], [1, 3])
    np.testing.assert_array_equal(got[2], [1, 2])


def test_cv_fit_survives_corrupt_codes_via_fallback(monkeypatch):
    """End to end: if the native df kernel rejects (simulated by forcing
    None), the CountVectorizer fit still produces the right vocabulary
    through the python engines."""
    from flink_ml_tpu import native
    from flink_ml_tpu.models.feature.text import CountVectorizer

    docs = np.asarray([["a", "b", "a"], ["b", "b", "c"], ["a", "c", "c"]])
    t = Table.from_columns(doc=docs)
    want = CountVectorizer(input_col="doc").fit(t).vocabulary
    monkeypatch.setattr(native, "doc_freq_i64", lambda *a, **k: None)
    got = CountVectorizer(input_col="doc").fit(t).vocabulary
    assert got == want


def test_native_threads_env_validation(monkeypatch):
    """Non-positive / garbage FLINK_ML_TPU_NATIVE_THREADS degrades to 1
    with a warning — never a crash; valid values parse and cap."""
    from flink_ml_tpu import native

    monkeypatch.delenv(native.NATIVE_THREADS_ENV, raising=False)
    assert native.native_threads() == 1
    monkeypatch.setenv(native.NATIVE_THREADS_ENV, "4")
    assert native.native_threads() == 4
    monkeypatch.setenv(native.NATIVE_THREADS_ENV, "100000")
    assert native.native_threads() == native._NATIVE_THREADS_MAX
    for bad in ("0", "-3", "two", "", "2.5"):
        monkeypatch.setenv(native.NATIVE_THREADS_ENV, bad)
        monkeypatch.setattr(native, "_threads_warned", False)
        assert native.native_threads() == 1
    # a factorize under a garbage knob still runs (single-threaded)
    monkeypatch.setenv(native.NATIVE_THREADS_ENV, "garbage")
    if native.available():
        keys = np.asarray([5, 5, 7, 5, 9], np.int64)
        out = native.factorize_i64(keys)
        assert out is not None
        np.testing.assert_array_equal(out[1], [0, 0, 1, 0, 2])


def test_factorize_i64_threaded_byte_identical(rng):
    """The threaded factorizer's chunk-order merge must reproduce the
    sequential first-appearance codes and alphabet EXACTLY, at every
    thread count — including key sets spanning chunk boundaries."""
    from flink_ml_tpu import native

    if not native.available():
        pytest.skip("native tier unavailable")
    # > 2 * 65536 keys so clamp_threads really splits; repeated keys
    # across the whole range force cross-chunk merges
    keys = rng.integers(0, 5000, size=300_000).astype(np.int64)
    uniq1, codes1 = native.factorize_i64(keys, n_threads=1)
    for t in (2, 3, 4):
        uniq_t, codes_t = native.factorize_i64(keys, n_threads=t)
        np.testing.assert_array_equal(uniq_t, uniq1)
        np.testing.assert_array_equal(codes_t, codes1)
    # mostly-distinct tail: the merge path with large local alphabets
    keys2 = np.concatenate([np.arange(200_000, dtype=np.int64),
                            keys[:100_000]])
    u1, c1 = native.factorize_i64(keys2, n_threads=1)
    u4, c4 = native.factorize_i64(keys2, n_threads=4)
    np.testing.assert_array_equal(u4, u1)
    np.testing.assert_array_equal(c4, c1)


def test_doc_freq_i64_threaded_byte_identical(rng):
    """Threaded doc-freq partials must merge to the exact sequential
    counts, and ANY thread's bounds hit must fail the whole call (the
    guard contract is thread-count-invariant)."""
    from flink_ml_tpu import native

    if not native.available():
        pytest.skip("native tier unavailable")
    u = 64
    codes = rng.integers(0, u, size=(30_000, 20)).astype(np.int64)
    df1 = native.doc_freq_i64(codes, u, n_threads=1)
    assert df1 is not None
    for t in (2, 4):
        df_t = native.doc_freq_i64(codes, u, n_threads=t)
        np.testing.assert_array_equal(df_t, df1)
    # out-of-range code in the LAST chunk: threaded call must reject
    bad = codes.copy()
    bad[-1, -1] = u + 5
    assert native.doc_freq_i64(bad, u, n_threads=4) is None
    bad[-1, -1] = -2
    assert native.doc_freq_i64(bad, u, n_threads=4) is None
