"""Native (C++) kernel tier.

The reference's "native layer" is the JVM runtime itself (SURVEY.md: zero
C++/CUDA in the repo); this framework's equivalent split is: XLA/Pallas for
device compute, and C++ for host-side kernels that are neither XLA-friendly
nor fast in Python — currently the Swing pairwise-intersection core.

Kernels compile lazily with g++ into a shared library next to the sources
(keyed by a hash of sources, flags and host CPU — see ``_build_key``) and
bind via ctypes; every caller must handle ``available() == False`` and
fall back to its Python implementation (no hard native dependency).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from flink_ml_tpu.common.locks import make_lock
from flink_ml_tpu.resilience import faults

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = sorted(
    os.path.join(_DIR, f) for f in os.listdir(_DIR) if f.endswith(".cpp"))
_LIB = os.path.join(_DIR, "_native_kernels.so")
#: the build key the library on disk was compiled under (see _build_key)
_KEY_FILE = _LIB + ".key"
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-pthread",
          "-std=c++17")

_lock = make_lock("native.load")
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _cpu_identity() -> str:
    """What ``-march=native`` resolves against: the host CPU's model and
    feature flags. Part of the build key, so a library copied in from
    another machine (the tree is copied as it stands, ignored files
    included) is rebuilt instead of loaded."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        import platform

        return platform.machine() + platform.processor()
    keep = {}
    for line in lines:
        name = line.split(":", 1)[0].strip()
        if name in ("model name", "flags", "Features") and name not in keep:
            keep[name] = line
    return "\n".join(keep.values())


def _build_key() -> str:
    """Hash of everything the binary depends on: source bytes, compiler
    flags, and the CPU ``-march=native`` targets. Modification times play
    no part — a checkout or a copy sets them arbitrarily."""
    h = hashlib.sha256()
    for src in _SOURCES:
        h.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_identity().encode())
    return h.hexdigest()


def _recorded_key() -> Optional[str]:
    try:
        with open(_KEY_FILE) as f:
            return f.read().strip()
    except OSError:
        return None


def _build() -> Optional[ctypes.CDLL]:
    global _build_failed
    if not _SOURCES:  # sources stripped from the install: no native tier
        _build_failed = True
        return None
    key = _build_key()
    try:
        if not os.path.exists(_LIB) or _recorded_key() != key:
            # per-process temp names: concurrent builders never share a
            # file, and os.replace publishes atomically (library first,
            # then the key that vouches for it)
            tmp = f"{_LIB}.{os.getpid()}.tmp"
            tmp_key = f"{_KEY_FILE}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", *_FLAGS, *_SOURCES, "-o", tmp],
                    check=True, capture_output=True)
                with open(tmp_key, "w") as f:
                    f.write(key)
                os.replace(tmp, _LIB)
                os.replace(tmp_key, _KEY_FILE)
            finally:
                for leftover in (tmp, tmp_key):
                    if os.path.exists(leftover):
                        os.remove(leftover)
        lib = ctypes.CDLL(_LIB)
        lib.swing_similarity.restype = ctypes.c_int
        lib.swing_similarity.argtypes = [
            ctypes.POINTER(ctypes.c_int64),  # user_items
            ctypes.POINTER(ctypes.c_int64),  # user_offsets
            ctypes.POINTER(ctypes.c_double),  # user_weights
            ctypes.c_int64,                   # n_users
            ctypes.POINTER(ctypes.c_int64),  # item_users
            ctypes.POINTER(ctypes.c_int64),  # item_offsets
            ctypes.POINTER(ctypes.c_int64),  # item_ids
            ctypes.c_int64,                   # n_items
            ctypes.c_double,                  # alpha2
            ctypes.c_int64,                   # k
            ctypes.POINTER(ctypes.c_int64),  # out_items
            ctypes.POINTER(ctypes.c_double),  # out_scores
            ctypes.POINTER(ctypes.c_int64),  # out_counts
        ]
        lib.csv_parse_numeric.restype = ctypes.c_int64
        lib.csv_parse_numeric.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_double), ctypes.c_int64]
        lib.factorize_i64.restype = ctypes.c_int64
        lib.factorize_i64.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64]
        lib.doc_freq_i64.restype = ctypes.c_int64
        lib.doc_freq_i64.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64]
        for fn_name in ("rowwise_counts_u8", "rowwise_counts_u16",
                        "rowwise_counts_u32", "rowwise_counts_i64"):
            fn = getattr(lib, fn_name)
            fn.restype = ctypes.c_int64
            fn.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        return lib
    except (OSError, subprocess.CalledProcessError):
        # a concurrent builder may have published a valid library even if
        # our own attempt failed — but never load one built from other
        # sources, flags or CPU (a stale kernel is worse than the Python
        # fallback)
        try:
            if os.path.exists(_LIB) and _recorded_key() == key:
                return ctypes.CDLL(_LIB)
        except OSError:
            pass
        _build_failed = True
        return None


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is None and not _build_failed:
        with _lock:
            if _lib is None and not _build_failed:
                _lib = _build()
    return _lib


def available() -> bool:
    return _get_lib() is not None


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def swing_similarity(user_items: np.ndarray, user_offsets: np.ndarray,
                     user_weights: np.ndarray, item_users: np.ndarray,
                     item_offsets: np.ndarray, item_ids: np.ndarray,
                     alpha2: float, k: int):
    """Native Swing scoring. Returns (out_items (n_items, k),
    out_scores (n_items, k), out_counts (n_items,)); raises RuntimeError
    if the native library is unavailable."""
    faults.inject("native-kernel", kernel="swing_similarity")
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native kernels unavailable (g++ build failed)")
    user_items = np.ascontiguousarray(user_items, np.int64)
    user_offsets = np.ascontiguousarray(user_offsets, np.int64)
    user_weights = np.ascontiguousarray(user_weights, np.float64)
    item_users = np.ascontiguousarray(item_users, np.int64)
    item_offsets = np.ascontiguousarray(item_offsets, np.int64)
    item_ids = np.ascontiguousarray(item_ids, np.int64)
    n_items = len(item_ids)
    out_items = np.zeros((n_items, k), np.int64)
    out_scores = np.zeros((n_items, k), np.float64)
    out_counts = np.zeros(n_items, np.int64)
    rc = lib.swing_similarity(
        _ptr(user_items, ctypes.c_int64), _ptr(user_offsets, ctypes.c_int64),
        _ptr(user_weights, ctypes.c_double),
        ctypes.c_int64(len(user_offsets) - 1),
        _ptr(item_users, ctypes.c_int64), _ptr(item_offsets, ctypes.c_int64),
        _ptr(item_ids, ctypes.c_int64), ctypes.c_int64(n_items),
        ctypes.c_double(alpha2), ctypes.c_int64(k),
        _ptr(out_items, ctypes.c_int64), _ptr(out_scores, ctypes.c_double),
        _ptr(out_counts, ctypes.c_int64))
    if rc != 0:
        raise RuntimeError(f"swing_similarity failed with code {rc}")
    return out_items, out_scores, out_counts


def csv_parse_numeric(data: bytes, n_cols: int, delimiter: str = ","):
    """Native all-numeric CSV parse → (n_rows, n_cols) float64 array, or
    None when the buffer isn't purely numeric (caller falls back) or the
    native library is unavailable."""
    faults.inject("native-kernel", kernel="csv_parse_numeric")
    lib = _get_lib()
    if lib is None:
        return None
    max_rows = data.count(b"\n") + 1
    out = np.empty((max_rows, n_cols), np.float64)
    n = lib.csv_parse_numeric(
        data, ctypes.c_int64(len(data)),
        ctypes.c_char(delimiter.encode()), ctypes.c_int64(n_cols),
        _ptr(out, ctypes.c_double), ctypes.c_int64(max_rows))
    if n < 0:
        return None
    return out[:n]


#: distinct-set cap for the native factorizer: past this many distinct
#: keys (mostly-distinct corpora) the hash-table win evaporates and the
#: uniq buffer would get large — callers fall back to their Python engine
FACTORIZE_UNIQ_CAP = 1 << 24

#: env var: worker-thread count for the threadable native kernels
#: (factorize_i64, doc_freq_i64). Default 1 — the host string tier
#: already shards rows over FORKED pool workers, and threads multiply
#: per worker; keep threads × workers within the core count.
NATIVE_THREADS_ENV = "FLINK_ML_TPU_NATIVE_THREADS"

#: sanity ceiling on the parsed thread count (a fat-fingered value must
#: not spawn thousands of threads)
_NATIVE_THREADS_MAX = 256

_threads_warned = False


def native_threads() -> int:
    """The validated FLINK_ML_TPU_NATIVE_THREADS value: a positive int,
    capped at 256. Unset/empty → 1. Non-positive or unparsable values →
    1 with ONE warning per process — a bad knob degrades to the
    single-threaded kernels, never crashes a fit."""
    global _threads_warned
    raw = os.environ.get(NATIVE_THREADS_ENV)
    if not raw:
        return 1
    try:
        value = int(raw)
    except ValueError:
        value = 0
    if value < 1:
        if not _threads_warned:
            _threads_warned = True
            import logging

            logging.getLogger(__name__).warning(
                "%s=%r is not a positive integer; native kernels run "
                "single-threaded", NATIVE_THREADS_ENV, raw)
        return 1
    return min(value, _NATIVE_THREADS_MAX)


def factorize_i64(keys: np.ndarray, n_threads: Optional[int] = None):
    """First-appearance factorization of a 1-D int64 array via the native
    open-addressing kernel: returns (uniq_keys, codes) with uniq in
    appearance order, or None when the native tier is unavailable or the
    distinct count exceeds FACTORIZE_UNIQ_CAP (callers fall back to
    pandas/np.unique). ``n_threads`` (default: the validated
    FLINK_ML_TPU_NATIVE_THREADS) shards the keys across worker threads
    with a deterministic chunk-order merge — output byte-identical to
    the single-threaded pass."""
    faults.inject("native-kernel", kernel="factorize_i64")
    lib = _get_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, np.int64)
    n = len(keys)
    cap = int(min(n, FACTORIZE_UNIQ_CAP)) + 1
    codes = np.empty(n, np.int64)
    uniq = np.empty(cap, np.int64)
    nu = lib.factorize_i64(_ptr(keys, ctypes.c_int64), ctypes.c_int64(n),
                           _ptr(codes, ctypes.c_int64),
                           _ptr(uniq, ctypes.c_int64), ctypes.c_int64(cap),
                           ctypes.c_int64(n_threads if n_threads is not None
                                          else native_threads()))
    if nu < 0:
        return None
    return uniq[:nu].copy(), codes


def doc_freq_i64(codes_mat: np.ndarray, u: int,
                 n_threads: Optional[int] = None):
    """Per-code document frequency of an (n_rows, w) int64 code matrix
    with domain [0, u) — one native pass with a last-seen-row stamp; or
    None when the native tier is unavailable, any code falls outside
    [0, u) (the kernel bounds-checks and returns -1 rather than corrupt
    the heap), or the domain exceeds ROWWISE_DOMAIN_CAP (callers fall
    back to the bincount/row-sort python engines).

    ``n_threads`` (default: the validated FLINK_ML_TPU_NATIVE_THREADS)
    splits the rows across worker threads, each with its own stamp and
    df partial (another 16·u bytes per thread — the domain cap bounds
    it), merged by exact integer sum: byte-identical to single-threaded,
    and ANY thread's bounds hit fails the whole call.

    The cap mirrors the counter siblings: the last-seen stamp is 8*u
    bytes PER FORKED WORKER, and _cv_shard_counts calls this with
    u = shard-distinct tokens, so a mostly-distinct corpus (u up to
    rows*w) would otherwise allocate gigabytes across the host pool on
    exactly the degenerate vocabularies the chunked python engines were
    built to survive."""
    faults.inject("native-kernel", kernel="doc_freq_i64")
    if u <= 0 or u > ROWWISE_DOMAIN_CAP:
        return None
    lib = _get_lib()
    if lib is None:
        return None
    codes_mat = np.ascontiguousarray(codes_mat, np.int64)
    n_rows, w = codes_mat.shape
    df = np.zeros(u, np.int64)
    rc = lib.doc_freq_i64(_ptr(codes_mat, ctypes.c_int64),
                          ctypes.c_int64(n_rows), ctypes.c_int64(w),
                          ctypes.c_int64(u), _ptr(df, ctypes.c_int64),
                          ctypes.c_int64(n_threads if n_threads is not None
                                         else native_threads()))
    if rc < 0:  # out-of-domain code: python engines raise IndexError
        return None
    return df


#: per-domain-entry budget (8 bytes each) shared by the native rowwise
#: counter's cnt array and doc_freq_i64's last-seen stamp — above it the
#: callers' chunked python engines bound memory instead
ROWWISE_DOMAIN_CAP = 1 << 22


def rowwise_counts(codes_mat: np.ndarray, u: int,
                   max_chunk_bytes: int = 256 << 20):
    """CSR-canonical (row_of, values, counts) of an (n_rows, w) code
    matrix with domain [0, u) via the native per-row stamped counter —
    one pass, no large temporaries; or None when the native tier is
    unavailable, the dtype has no kernel variant, or the domain exceeds
    ROWWISE_DOMAIN_CAP (callers keep their python engines). Values come
    back int64; rows ascend, values ascend within each row."""
    faults.inject("native-kernel", kernel="rowwise_counts")
    lib = _get_lib()
    if lib is None or u <= 0 or u > ROWWISE_DOMAIN_CAP:
        return None
    fns = {"uint8": "rowwise_counts_u8", "uint16": "rowwise_counts_u16",
           "uint32": "rowwise_counts_u32", "int64": "rowwise_counts_i64"}
    fn_name = fns.get(codes_mat.dtype.name)
    if fn_name is None:
        return None
    fn = getattr(lib, fn_name)
    n, w = codes_mat.shape
    per_row = int(min(w, u))
    if n == 0 or w == 0:
        z = np.zeros(0, np.int64)
        return z, z.copy(), z.copy()
    chunk = max(1, max_chunk_bytes // max(24 * per_row, 1))
    rows_p, vals_p, cnts_p = [], [], []
    for r0 in range(0, n, chunk):
        sub = np.ascontiguousarray(codes_mat[r0:r0 + chunk])
        m = sub.shape[0]
        cap = m * per_row  # the true per-chunk maximum: -1 unreachable
        row_out = np.empty(cap, np.int64)
        val_out = np.empty(cap, np.int64)
        cnt_out = np.empty(cap, np.int64)
        nnz = fn(sub.ctypes.data, ctypes.c_int64(m), ctypes.c_int64(w),
                 ctypes.c_int64(u), _ptr(row_out, ctypes.c_int64),
                 _ptr(val_out, ctypes.c_int64),
                 _ptr(cnt_out, ctypes.c_int64), ctypes.c_int64(cap))
        if nnz < 0:
            return None
        rows_p.append(row_out[:nnz] + r0)
        vals_p.append(val_out[:nnz].copy())
        cnts_p.append(cnt_out[:nnz].copy())
    return (np.concatenate(rows_p), np.concatenate(vals_p),
            np.concatenate(cnts_p))
