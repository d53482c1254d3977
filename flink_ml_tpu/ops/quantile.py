"""ε-approximate quantiles.

Ref parity: flink-ml-lib/.../common/util/QuantileSummary.java:42 — the
Greenwald-Khanna summary (insert buffer, compress threshold 10000, merge,
query) backing the ``relativeError`` param of RobustScaler, Imputer and
KBinsDiscretizer.

Three tiers:
- :class:`QuantileSummary` — a faithful GK sketch for streaming/merge use
  (online pipelines, bounded memory).
- :func:`approx_quantiles` — the batch path on the host: exact numpy
  quantiles over the materialized column (an exact answer trivially
  satisfies any ε bound; the reference only sketches because its input is an
  unbounded stream).
- :func:`select_on_device` — the batch path on the device: the same exact
  order statistics of a resident column, by counting passes over it where
  it lies (:func:`select_programs`) and one pass that takes the few
  elements left in its narrow brackets out (:func:`finish_program`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.observability.tracing import cold_build, tracer


@dataclasses.dataclass
class _Tuple:
    value: float
    g: int       # rank gap to the previous tuple
    delta: int   # max rank uncertainty


class QuantileSummary:
    """Greenwald-Khanna ε-approximate quantile sketch
    (ref: QuantileSummary.java — defaultCompressThreshold 10000)."""

    COMPRESS_THRESHOLD = 10000

    def __init__(self, relative_error: float = 0.001,
                 compress_threshold: int = COMPRESS_THRESHOLD):
        if not 0 < relative_error <= 1:
            raise ValueError("relative_error must be in (0, 1]")
        self.eps = relative_error
        self.compress_threshold = compress_threshold
        self._sampled: List[_Tuple] = []
        self._buffer: List[float] = []
        self.count = 0

    # -- build ---------------------------------------------------------------
    def insert(self, value: float) -> None:
        self._buffer.append(value)
        if len(self._buffer) >= self.compress_threshold:
            self._flush()

    def insert_all(self, values) -> None:
        for v in np.asarray(values, np.float64).ravel():
            self.insert(float(v))

    def _flush(self) -> None:
        if not self._buffer:
            return
        self._buffer.sort()
        sampled = self._sampled
        merged: List[_Tuple] = []
        threshold = 2 * self.eps * max(self.count + len(self._buffer), 1)
        si, n_new = 0, len(self._buffer)
        for bi, value in enumerate(self._buffer):
            while si < len(sampled) and sampled[si].value <= value:
                merged.append(sampled[si])
                si += 1
            # head/tail inserts get delta 0 so min/max queries stay exact
            # (ref QuantileSummary.java insertion rule)
            is_min = not merged
            is_max = bi == n_new - 1 and si >= len(sampled)
            if is_min or is_max:
                delta = 0
            else:
                delta = max(int(np.floor(threshold)) - 1, 0)
            merged.append(_Tuple(value, 1, delta))
        merged.extend(sampled[si:])
        self.count += n_new
        self._buffer = []
        self._sampled = merged
        self._compress()

    def _compress(self) -> None:
        if len(self._sampled) < 2:
            return
        threshold = 2 * self.eps * self.count
        out = [self._sampled[0]]
        for t in self._sampled[1:-1]:
            last = out[-1]
            if last is not self._sampled[0] and \
                    last.g + t.g + t.delta < threshold:
                out[-1] = _Tuple(t.value, last.g + t.g, t.delta)
            else:
                out.append(t)
        out.append(self._sampled[-1])
        self._sampled = out

    def merge(self, other: "QuantileSummary") -> "QuantileSummary":
        result = QuantileSummary(min(self.eps, other.eps),
                                 self.compress_threshold)
        for s in (self, other):
            s._flush()
        merged = sorted(self._sampled + other._sampled,
                        key=lambda t: t.value)
        result._sampled = merged
        result.count = self.count + other.count
        result._compress()
        return result

    # -- query ---------------------------------------------------------------
    def query(self, prob: float) -> float:
        if not 0 <= prob <= 1:
            raise ValueError("prob must be in [0, 1]")
        self._flush()
        if not self._sampled:
            raise ValueError("query on empty summary")
        rank = prob * (self.count - 1) + 1
        # boundary ranks are exact (head/tail tuples carry delta 0)
        if rank <= 1:
            return self._sampled[0].value
        if rank >= self.count:
            return self._sampled[-1].value
        margin = self.eps * self.count
        min_rank = 0
        for t in self._sampled:
            min_rank += t.g
            max_rank = min_rank + t.delta
            if max_rank - margin <= rank <= min_rank + margin:
                return t.value
        return self._sampled[-1].value

    def query_all(self, probs: Sequence[float]) -> np.ndarray:
        return np.asarray([self.query(p) for p in probs])


def approx_quantiles(x: np.ndarray, probs: Sequence[float],
                     relative_error: float = 0.001) -> np.ndarray:
    """Per-column quantiles of a (n, d) array → (len(probs), d).

    Batch path: numpy's exact linear-interpolation-free 'lower' quantile
    matches the GK sketch's behavior of returning an actual data value.
    """
    x = np.asarray(x, np.float64)
    if x.ndim == 1:
        x = x[:, None]
    return np.quantile(x, np.asarray(probs), axis=0, method="lower")


# ---------------------------------------------------------------------------
# exact order statistics of a device-resident column
# ---------------------------------------------------------------------------
#
# A median is a sort on every other platform; the chip has no fast sort, and
# a sort of an (n, d) column is a second copy of it. The selection below
# never moves the table: every float32 has an order-preserving 32-bit
# integer key (the sign-magnitude flip a radix sort uses), the element of
# 0-based rank r is the smallest key t with count(key <= t) >= r + 1, and a
# count is one fused compare-and-sum pass over the table where it lies, the
# keys made in registers as each tile is read. Every bracket [lo, hi] a
# probability and a column is PROVEN by counts (count(key < lo) < r + 1 <=
# count(key <= hi)); what is left to choose is where to count next:
#
# - a first guess: the order statistics of a small sample of rows (a few
#   contiguous runs spread over every shard) at the sample ranks a safety
#   margin under the wanted one, at it, and the margin over. The first pass
#   over the table counts at those three: it proves the bracket or, on a
#   sorted table, proves which side of it the element lies. Nothing is taken
#   on trust;
# - then three counts a probability a pass, placed by interpolating the
#   wanted rank between the bracket's two proven ends, in VALUES (a float's
#   key is piecewise logarithmic in it), with the same margin either side:
#   on a smooth column the K elements of a bracket become some 2 sqrt(K), so
#   12M become 1e5 (the sample), 600, 50, a dozen;
# - a bracket that TWO passes running did not cut to a quarter of its keys
#   is cut at its quarters by the next, which does: the loop's own fallback
#   (ties, outliers, a first guess proven wrong), three passes a two bits at
#   the worst. One such pass is not enough to give the ranks up: a count
#   that fell outside its margin (one bracket in some 5,000 a pass) leaves
#   the wide outer part of its bracket with the wanted rank at its very
#   end, and interpolated there the next pass leaves fewer elements than a
#   hit would have: the table's pass count does not move. So does a bracket
#   that spans zero, whose values interpolate well and whose keys (all the
#   exponents between) stay wide. Cut at its quarters at once, as this was
#   first written, such a bracket lagged one pass to the end: one uniform
#   table in some fifty read the whole table an eighth time for one bracket
#   of 300, and a normal table nearly always (PERF.md section 6, PR 35);
# - a bracket of a few dozen elements is not counted any further: ONE more
#   read of the table takes its elements out and picks the wanted one among
#   them (the finishing pass: `finish_program`). The rows of a shard fall
#   into thousands of blocks by row index, a block keeps the count, sum,
#   smallest and largest of the bracket's elements that fall in it, which
#   give up to three of them back exactly, and 50 elements thrown into
#   8,192 blocks put four in one less than once in a million brackets; a
#   bracket that does is declined, left as it was, and counted on. Three
#   counting passes take a smooth 12M-row column's bracket to some 50
#   elements in 200 keys; from there the counts cut it at its quarters, two
#   bits a pass, four more reads of the whole table spent on 15,000 of its
#   1.2e9 elements, until PR 37. It is the one pass that loops over the
#   table, slice by slice: a reduction within blocks of rows is not
#   something XLA fuses into a read of the table (`block_sums`);
# - where the finishing pass does not apply (more than `FINISH_HELD`
#   elements, or declined) a bracket of a few dozen elements lying dense in
#   its last keys is cut at its quarters, which then beats its ranks (a
#   factor of 4 a pass for certain, where some 2 sqrt(K) elements with a
#   margin around them are about as many as there were);
# - where a bracket is a run of keys with a handful of elements far apart
#   in it (a column centred on zero, where floats lie dense; a short
#   table), or holds many elements that three spread counts did not part
#   (one value many times over: whole numbers, categories, a clipped or
#   zero-inflated column), a pass of another kind also pulls its ends in to
#   the elements themselves (the smallest key >= lo, the largest <= hi):
#   one value left is then the answer at once, not after a bisection of its
#   mantissa (12M whole numbers 0..999: 6 passes with it, 18 without).
#
# The answers are exact by construction: the loop ends when every bracket is
# one key wide, wherever the counts were taken and whichever elements were
# taken out. How many passes that takes is the TABLE's, not the shape's
# (a uniform or a sorted 12M x 100 table four, a normal one six or seven,
# whole numbers six or seven: PERF.md section 6, PR 37, lists what uniform,
# normal, zero-inflated, whole-number and sorted tables took on the chip),
# and tests/test_order_stats.py holds a bound a distribution.

#: rows of every shard the first guess is taken from, in SAMPLE_RUNS
#: contiguous runs spread evenly over the shard (32 small rounds over them
#: take 2 ms at d 100; the brackets the third pass leaves shrink with the
#: fourth root of it)
SAMPLE_ROWS = 1 << 17
SAMPLE_RUNS = 16
#: standard deviations of the rank between two proven counts that a
#: bracket keeps either side of its guess
SAFETY = 4.0
#: keys that two passes at two bits finish: a bracket of a few dozen
#: elements with no more keys than that for each of them is dense (the
#: rule under ``EVEN_KEYS``)
SNAP_KEYS = 16
#: keys that three passes at two bits finish: a bracket wider than that for
#: each of its elements, where it holds no more of them than one pass's
#: counts tell apart (``PIVOTS + 1``), or wider than that and holding more,
#: none of which its last pass took off, has its ends pulled in to its
#: elements (:func:`_wants_ends`). Not sooner: that pass costs two and a
#: half counting passes as XLA compiles it (22.7 ms against 8.3 at 12M x
#: 100), and it is every bracket's pass once one asks for it. At 16 keys
#: an element 4 uniform tables in 40 took one, for brackets their counts
#: finished in as many passes anyway (81 ms a fit for 68.5); at 64 none in
#: 40 (PERF.md section 6, PR 36)
ENDS_KEYS = 64
#: keys that four passes at two bits finish: a bracket of no more than that,
#: dense in them (at most SNAP_KEYS keys an element) and of so few elements
#: that its ranks cut it no faster than its quarters do (some SAFETY sqrt(K)
#: / 2 left of K against K / 4: K under 4 SAFETY^2), is cut at its quarters
EVEN_KEYS = 255
#: counts a probability a pass (the programs' pivots are written as three:
#: a lower guess, the guess, an upper guess)
PIVOTS = 3
#: counting passes the head program makes, one after another, before it
#: looks at its brackets (and finishes them where they are few-element
#: brackets, or leaves them to the driver): three take a smooth column's
#: bracket to some 50 elements, which is where the finishing pass takes
#: over. Timed on a v5e at 12M x 100 (PERF.md section 6): before there was
#: a finishing pass, with 1 | 2 | 3 | 4 | 5 of them (PR 36), a uniform
#: table's fit 72.8 | 71.5 | 70.0 | 68.7 | 67.2 ms (a host trip between
#: two passes is 1.4 ms), a normal one's 146 | 144 | 142 | 128 | 116, whole
#: numbers' 103 | 101 | 87 | 105 | 126 (the head's passes only count; at
#: five a tied table reads the table nine times); with it (PR 37), at 3 |
#: 4 and a gate of 128 elements: uniform 50.8 | 59.3, sorted 51.0 | 59.0,
#: whole numbers 87.2 | 104.1, normal 98.7 | 84.2, zero-inflated 98.2 |
#: 83.7; at 3 and the gate as it is (``FINISH_HELD``): uniform 51.1,
#: sorted 50.9, whole numbers 86.7, normal 75.1, zero-inflated 50.6
HEAD_PASSES = 3
#: blocks a shard's rows fall into for the finishing pass, by row index
#: modulo this (a sorted table's neighbours fall in different blocks); a
#: block gives up to three of a bracket's elements back. Timed on a v5e at
#: 12M x 100 (PERF.md section 6, PR 37): the pass alone 42 | 38 | 38 | 35 |
#: 34 ms at 1,024 .. 16,384 with one slice a turn, 21.1 | 21.4 | 20.9 with
#: eight at 2,048 | 4,096 | 8,192 (the words of three brackets, 31 MB at
#: 8,192, stay in fast memory)
FINISH_BLOCKS = 8192
#: elements a bracket may hold and keys it may span to be finished: K
#: elements thrown into L blocks put four in one with probability some
#: ``C(K, 4) / L^3`` (4e-7 at 50 and 8,192; 6e-4 at 300, what a bracket
#: holds whose last count fell outside its margin); the more keys it
#: spans, the fewer elements its blocks' packed word has room to count
#: (:func:`_finishable`: 512 in up to 1.4M keys, 50 in up to 11M)
FINISH_HELD = 512
FINISH_KEYS = ((1 << 28) - 1) // 3
#: brackets of a column one read of the table finishes: the words of three
#: are what a turn of the loop keeps in fast memory
FINISH_GROUP = 3
#: slices of ``FINISH_BLOCKS`` rows the finishing pass takes a turn of its
#: loop: the blocks' words are read and written once a turn, not once a
#: slice (38.5 | 24.5 | 21.4 | 23.5 ms at 1 | 4 | 8 | 16)
FINISH_SLICES = 8

_TOP = 0x80000000


def float_keys(x):
    """The order-preserving int32 key of float32: signed order == IEEE
    order, -0 under +0, NaNs at the two ends by their sign. The one copy
    of the mapping: the programs make their keys by it, and the checks
    their oracles."""
    s = jax.lax.bitcast_convert_type(x, jnp.int32)
    return s ^ ((s >> 31) & jnp.int32(0x7FFFFFFF))


def keys_to_float(keys):
    """The float32 of an int32 key (the mapping is its own inverse)."""
    return jax.lax.bitcast_convert_type(
        keys ^ ((keys >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def _signed(keys):
    """uint32 keys (what the brackets are kept in: a width never
    overflows) as the int32 keys of the same order."""
    return jax.lax.bitcast_convert_type(keys ^ jnp.uint32(_TOP), jnp.int32)


def _unsigned(signed):
    return jax.lax.bitcast_convert_type(signed, jnp.uint32) ^ jnp.uint32(
        _TOP)


def _float_to_key(x):
    return _unsigned(float_keys(x))


def _key_to_float(keys):
    return keys_to_float(_signed(keys))


def count_le(x, piv):
    """``(P, d)`` int32: how many rows of ``x (rows, d)`` have a key at or
    under each of ``piv (P, d)`` int32. One sum a pivot over the same keys:
    XLA makes them one fusion with ``P`` results that reads the table once
    where it lies and makes the keys in registers (a broadcast compare
    ``(rows, P, d)`` makes it write the keys out first: 4.99 GB at 12M x
    100)."""
    keys = float_keys(x.T)                           # (d, rows)
    return jnp.stack([jnp.sum(keys <= piv[i][:, None], axis=1,
                              dtype=jnp.int32)
                      for i in range(piv.shape[0])])


def _ends_within(x, lo, hi, rows_valid):
    """``(smallest key >= lo, largest key <= hi)`` over the first
    ``rows_valid`` rows of ``x``, as signed keys, each ``(P, d)`` like
    ``lo`` and ``hi`` (``hi`` and ``lo`` themselves where no row lies
    there). One reduction a bracket over the same keys, as in
    :func:`count_le` and for the same reason."""
    keys = float_keys(x.T)                           # (d, rows)
    valid = jnp.arange(x.shape[0]) < rows_valid
    s_lo, s_hi = _signed(lo)[:, :, None], _signed(hi)[:, :, None]
    a = [jnp.min(jnp.where(valid & (keys >= s_lo[i]), keys, s_hi[i]),
                 axis=1) for i in range(lo.shape[0])]
    b = [jnp.max(jnp.where(valid & (keys <= s_hi[i]), keys, s_lo[i]),
                 axis=1) for i in range(lo.shape[0])]
    return jnp.stack(a), jnp.stack(b)


def _wants_ends(held, width, stuck):
    """Which brackets (``held`` elements in ``width`` keys; ``stuck``: the
    last pass took no element off) the next pass should pull in to their
    elements: a handful of elements in a run of keys far wider than they
    (a column centred on zero, where floats lie dense; a short table), or
    many elements that three counts spread over the bracket did not part
    (one value many times over: whole numbers, categories, a clipped or
    zero-inflated column), while counts alone would still take more than
    three passes over the keys that are left."""
    u32 = jnp.uint32
    return jnp.where(held <= PIVOTS + 1,
                     width // u32(ENDS_KEYS) > held,
                     stuck & (width > u32(ENDS_KEYS)))


def _sum_bits(width):
    """The low bits of a block's packed word that hold the sum of its
    offsets: as many as three offsets of at most ``width`` take (at most
    28)."""
    u32 = jnp.uint32
    return u32(32) - jax.lax.clz(
        u32(3) * jnp.minimum(width, u32(FINISH_KEYS)) | u32(1))


def _finishable(held, width, pad):
    """Which brackets (``held`` elements in ``width`` keys; ``pad`` rows of
    zero padding may lie among them) the finishing pass takes: few enough
    elements that four of them in one block are rare, and no more of them
    than the count over a block's sum (:func:`_block_terms`) has room for:
    four thirds of them fit the bits that three offsets leave."""
    u32 = jnp.uint32
    room = u32(1) << jnp.minimum(u32(32) - _sum_bits(width), u32(12))
    return ((held <= FINISH_HELD) & (width <= u32(FINISH_KEYS))
            & (u32(4) * (held + pad.astype(u32)) < u32(3) * room))


def _block_terms(rows, lo, width):
    """What each of ``rows (r, d)`` adds to its block's three words for
    each bracket ``[lo, lo + width]`` (``(g, d)`` uint32 keys), each ``(g,
    d, r)`` uint32, zero where the element lies outside the bracket: its
    offset from ``lo`` with a one over the sum's bits (added up: the
    block's count over the sum of its offsets, :func:`_sum_bits`), the
    offset, and the offset's complement (of each the largest is kept: the
    block's largest and its smallest).

    Why the packed word cannot lie where :func:`_finishable` holds: three
    offsets of at most ``width`` add up to less than ``2^bits``, so a
    block of ``k <= 3`` elements reads count ``k`` and their sum; ``k >=
    4`` elements (of at most ``held + pad``) carry less than ``k / 3`` into
    the count, which then reads between 4 and ``4 k / 3``, under the
    ``2^(32 - bits)`` it has room for: never as a count of three or
    fewer."""
    u32 = jnp.uint32
    off = _float_to_key(rows.T)[None] - lo[:, :, None]
    inside = off <= width[:, :, None]
    one = (u32(1) << _sum_bits(width))[:, :, None]
    return (jnp.where(inside, off + one, u32(0)),
            jnp.where(inside, off, u32(0)), jnp.where(inside, ~off, u32(0)))


def block_sums(x, lo, width, blocks: int):
    """The three words (:func:`_block_terms`) of every block of ``x (rows,
    d)`` for each bracket, each ``(g, d, blocks)`` uint32; row ``i`` falls
    in block ``i % blocks``. One read of the table, ``FINISH_SLICES`` slices
    of ``blocks`` rows at a time where they lie: the loop is over slices of
    the table, its carry the words alone, and nothing of the table's size
    is made (a reshape of the rows into blocks, in whatever order, makes
    XLA write the keys out first; PERF.md section 6, PR 37)."""
    rows, d = x.shape
    whole = rows // blocks
    turns = whole // FINISH_SLICES

    def add(words, terms):
        return (words[0] + terms[0], jnp.maximum(words[1], terms[1]),
                jnp.maximum(words[2], terms[2]))

    def several(pieces, words):
        """The words with ``pieces`` of ``blocks`` rows or fewer added: their
        terms are put together first, so the words are read and written
        once for all of them."""
        terms = None
        for piece in pieces:
            new = _block_terms(piece, lo, width)
            short = blocks - piece.shape[0]
            if short:
                new = tuple(jnp.pad(t, ((0, 0), (0, 0), (0, short)))
                            for t in new)
            terms = new if terms is None else add(terms, new)
        return words if terms is None else add(words, terms)

    def take(j, words):
        return several([jax.lax.dynamic_slice(
            x, ((j * FINISH_SLICES + k) * blocks, 0), (blocks, d))
            for k in range(FINISH_SLICES)], words)

    words = tuple(jnp.zeros(lo.shape + (blocks,), jnp.uint32)
                  for _ in range(3))
    if turns:            # (a shard shorter than a turn: nothing to trace)
        words = jax.lax.fori_loop(0, turns, take, words)
    done = turns * FINISH_SLICES * blocks
    return several([x[a:a + blocks] for a in range(done, rows, blocks)],
                   words)


def block_elements(words, width):
    """``(3, g, d, blocks)`` uint32: the offsets of up to three elements a
    block of each bracket of ``width (g, d)`` keys, ``0xFFFFFFFF`` where
    there is none, and ``(g, d)`` bool: some block of the bracket holds
    more than three (its words say so, not which)."""
    u32 = jnp.uint32
    packed, largest, complement = words
    bits = _sum_bits(width)[:, :, None]
    count, total = packed >> bits, packed & ((u32(1) << bits) - u32(1))
    smallest = ~complement
    none = u32(0xFFFFFFFF)
    found = jnp.stack([
        jnp.where(count >= 2, smallest, none),
        jnp.where(count == 3, total - smallest - largest, none),
        jnp.where(count >= 1, largest, none)])
    return found, jnp.any(count > 3, axis=-1)


def _pack(lo, hi, c_lo, c_hi, piv, stuck, was_slow, declined):
    """A fit's brackets between two programs, as one ``(5 + PIVOTS, m, d)``
    uint32 array (one operand a launch, not eight): the bracket's two
    proven ends and the two counts that prove them, the marks (of its last
    pass two; one that the finishing pass left it open), the next pass's
    pivots."""
    u32 = jnp.uint32
    marks = (stuck.astype(u32) | (was_slow.astype(u32) << u32(1))
             | (declined.astype(u32) << u32(2)))
    as_bits = functools.partial(jax.lax.bitcast_convert_type,
                                new_dtype=u32)
    return jnp.concatenate([
        jnp.stack([lo, hi, as_bits(c_lo), as_bits(c_hi), marks]), piv])


def _unpack(state):
    as_count = functools.partial(jax.lax.bitcast_convert_type,
                                 new_dtype=jnp.int32)
    marks = state[4]
    return (state[0], state[1], as_count(state[2]), as_count(state[3]),
            state[5:], (marks & jnp.uint32(1)) > 0,
            (marks & jnp.uint32(2)) > 0, (marks & jnp.uint32(4)) > 0)


def _table(xl, spec, axes):
    """What every program knows of the table before it reads it: its rows,
    the counts wanted (1-based ranks, ``(m, 1)``), the rows of zero padding
    at its tail, and how many of this shard's rows are the table's."""
    from flink_ml_tpu.parallel import mapreduce as mr

    local_n = xl.shape[0]
    n_valid = spec[0]
    pad = local_n * mr.shard_count(axes) - n_valid
    local_valid = jnp.clip(n_valid - mr.shard_index(axes) * local_n,
                           0, local_n)
    return n_valid, (spec[1:] + 1)[:, None], pad, local_valid


def _offered(state, pad):
    """``(open brackets, those of them the finishing pass takes)``: the
    ones that hold few elements in not too many keys and were not left
    open by a finishing pass already."""
    lo, hi, c_lo, c_hi, _, _, _, gave_up = state
    is_open = hi > lo
    return is_open, is_open & ~gave_up & _finishable(
        (c_hi - c_lo).astype(jnp.uint32), hi - lo, pad)


def _wants_finish(state, pad):
    """Whether the next read of the table should be the finishing pass:
    some bracket is open, and it takes every open one."""
    is_open, offered = _offered(state, pad)
    return jnp.any(is_open) & jnp.all(offered == is_open)


def _report(state, pad, passes, finished=0, declined=0):
    """What a program tells the driver (:func:`read_report`)."""
    lo, hi, c_lo, c_hi, _, stuck, _, _ = state
    # a run of keys far wider than the elements in it, which are a
    # handful or among which the last pass's counts all fell between
    # two: the next pass looks for the elements
    sparse = _wants_ends((c_hi - c_lo).astype(jnp.uint32), hi - lo, stuck)
    answers = jax.lax.bitcast_convert_type(_key_to_float(hi), jnp.int32)
    return jnp.concatenate([answers.reshape(-1), jnp.stack([
        jnp.any(hi > lo).astype(jnp.int32),
        jnp.any(sparse).astype(jnp.int32),
        _wants_finish(state, pad).astype(jnp.int32),
        jnp.int32(finished), jnp.int32(declined), jnp.int32(passes)])])


@functools.lru_cache(maxsize=32)
@cold_build("select")
def select_programs(mesh, m: int):
    """``(head, step, step_ends)``: the three programs of a selection of
    ``m`` order statistics a column over ``mesh``, each held (a dictionary
    hit a warm fit) and each traced only when first called.

    - ``head(xs, spec) -> (state, report)``: the first guess from the
      sample and the table's first ``HEAD_PASSES`` counting passes, one
      after another with nothing around them, then, where that leaves
      few-element brackets (:func:`_wants_finish`), the finishing pass
      (:func:`finish_program`'s, behind a branch: what the driver would
      launch next, a host trip sooner); one pass where the table is its
      own sample: that pass proves every answer;
    - ``step(xs, spec, state) -> (state, report)``: one more pass;
    - ``step_ends``: one more pass that also pulls every bracket's ends in
      to its outermost elements.

    ``xs`` is the row-sharded table, ``spec`` the replicated int32 vector
    ``[n_valid, rank_0, .., rank_{m-1}]`` (0-based ranks over the rows
    ``[0, n_valid)``: the rows past them are ``ensure_on_mesh``'s zero
    padding, at the table's tail), ``state`` the brackets (:func:`_pack`)
    and ``report`` the int32 vector a driver reads between two programs
    (:func:`read_report`): the bits of the ``(m, d)`` float32 elements at
    the brackets' upper ends (the answers once no bracket is open), whether
    any bracket is still open, whether the next pass should be
    ``step_ends``, whether it should be the finishing pass, the brackets a
    finishing pass of this program closed and left open, and the reads of
    the table this program made. Under ``shard_map``
    each shard reads its own rows and the counts cross by one ``psum`` a
    pass.

    Why three programs and a driver between them, and not one loop: a
    reduction over the rows fuses into ONE read of the table where it lies
    (9.5 ms at 12M x 100 on a v5e) only while no control flow surrounds
    it; inside a ``while_loop`` or behind a ``cond`` XLA first copies the
    table row-major (6.1 GB and 98 ms a fit; PERF.md section 6, PR 35 and
    36). Straight-line passes keep nothing of the table's size. (The
    finishing pass is no reduction over the table: its loop takes slices
    of it, and neither the loop nor the head's branch around it makes the
    compiler copy anything: 0.003 GB of temporaries, PR 37.)"""
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import mapreduce as mr
    from flink_ml_tpu.parallel.mesh import data_axes, data_pspec

    axes = data_axes(mesh)
    u32 = jnp.uint32
    full = u32(0xFFFFFFFF)

    table = functools.partial(_table, axes=axes)

    def count(rows, piv, zeros_in):
        """Rows of the whole table's ``rows`` with a key <= ``piv (..., m,
        d)``, less the ``zeros_in`` padding rows (+0.0) among them."""
        flat = piv.reshape(-1, piv.shape[-1])
        c = mr.reduce_sum(count_le(rows, _signed(flat)), axes)
        c = c - zeros_in * (flat >= u32(_TOP)).astype(jnp.int32)
        return c.reshape(piv.shape)

    def first_guess(xl, spec):
        """The ``(PIVOTS, m, d)`` keys the first pass counts at: the order
        statistics of a sample at the sample ranks a safety margin under
        the wanted one, at it, and the margin over."""
        local_n, d = xl.shape
        n_valid, _, pad, _ = table(xl, spec)
        ranks = spec[1:]
        shards = mr.shard_count(axes)
        if local_n <= SAMPLE_ROWS:
            sample, sample_pad = xl, pad
        else:
            run = SAMPLE_ROWS // SAMPLE_RUNS
            starts = [(local_n - run) * i // (SAMPLE_RUNS - 1)
                      for i in range(SAMPLE_RUNS)]
            sample = jnp.concatenate([xl[a:a + run] for a in starts])
            sample_pad = 0
        s_rows = sample.shape[0] * shards - sample_pad
        q = ranks.astype(jnp.float32) / jnp.maximum(n_valid - 1, 1)
        margin = jnp.where(s_rows >= n_valid, 0.0,
                           SAFETY * jnp.sqrt(s_rows * q * (1 - q)) + 1)
        side = jnp.asarray([-1.0, 0.0, 1.0])[:, None]
        s_ranks = jnp.clip(jnp.round(q * (s_rows - 1) + side * margin),
                           0, s_rows - 1).astype(jnp.int32)       # (3, m)

        def halve(_, bracket):
            lo, hi = bracket
            mid = lo + (hi - lo) // u32(2)
            ok = count(sample, mid, sample_pad) >= s_ranks[:, :, None] + 1
            return jnp.where(ok, lo, mid + u32(1)), jnp.where(ok, mid, hi)

        zeros = jnp.zeros((PIVOTS, m, d), u32)
        _, first = jax.lax.fori_loop(0, 32, halve, (zeros, zeros + full))
        # counted at key - 1, the lower guess proves itself the lower end
        # where the column is tied there
        return first.at[0].set(first[0] - (first[0] > 0).astype(u32))

    def place(lo, hi, c_lo, c_hi, slow, target):
        """The next pass's three pivots ``(3, m, d)`` in ``[lo, hi - 1]``:
        by the wanted rank between the two proven counts, with the safety
        margin either side, or, where ``slow``, at the bracket's
        quarters."""
        width = hi - lo
        last = width - jnp.minimum(width, u32(1))
        quarter = width >> u32(2)
        even = jnp.stack([quarter, width >> u32(1),
                          jnp.maximum(width >> u32(1), last - quarter)])
        k = jnp.maximum(c_hi - c_lo, 1).astype(jnp.float32)
        f = jnp.clip((target - c_lo).astype(jnp.float32) - 0.5,
                     0.5, k - 0.5) / k
        # (a small bracket loses little by a miss: a narrower margin.) The
        # margin is the score interval's, not f +- z sd: a rank near an end
        # of its bracket (where a one-sided bracket's usually lies) has a
        # count as skewed as a Poisson's, and f +- z sd missed 30 times as
        # often as z says (0.4 % of brackets at K 570: two extra passes in
        # every second fit of 300)
        z = jnp.minimum(SAFETY, 1 + jnp.log10(k))
        centre = (f + z * z / (2 * k)) / (1 + z * z / k)
        reach = (z * jnp.sqrt(f * (1 - f) / k + z * z / (4 * k * k))
                 / (1 + z * z / k))
        v_lo, v_hi = _key_to_float(lo), _key_to_float(hi)
        at = jnp.clip(jnp.stack([centre - reach, f, centre + reach]),
                      0.0, 1.0)
        guess = _float_to_key(v_lo * (1 - at) + v_hi * at)
        # in order, inside the bracket and at least a key apart, whatever
        # rounding made of them; an end that is no number (a bracket not
        # yet proven on that side) has nothing to interpolate
        slow = slow | ~(jnp.isfinite(v_lo) & jnp.isfinite(v_hi))
        # and the last EVEN_KEYS keys of a bracket of a few elements lying
        # dense in them are cut faster at its quarters (a factor of 4 a
        # pass, four passes) than by their ranks (some 2 sqrt(K) / margin);
        # a little wider, one more pass by ranks first ends in as many
        held = jnp.maximum(c_hi - c_lo, 1).astype(u32)
        slow = slow | ((held <= int(4 * SAFETY * SAFETY))
                       & (width <= EVEN_KEYS)
                       & (width // u32(SNAP_KEYS) <= held))
        low, high = (jnp.minimum(guess[0], guess[1]),
                     jnp.maximum(guess[0], guess[1]))
        guess = jnp.clip(jnp.stack([
            jnp.minimum(low, guess[2]),
            jnp.maximum(low, jnp.minimum(high, guess[2])),
            jnp.maximum(high, guess[2])]), lo, lo + last)
        guess = jnp.stack([
            guess[1] - jnp.maximum(guess[1] - guess[0],
                                   jnp.minimum(guess[1] - lo, u32(1))),
            guess[1],
            guess[1] + jnp.maximum(
                guess[2] - guess[1],
                jnp.minimum(lo + last - guess[1], u32(1)))])
        return jnp.where(slow, lo + even, guess)

    @functools.partial(jax.jit, static_argnames="with_ends")
    def a_pass(xl, spec, state, with_ends: bool):
        """One read of the table: the counts at the state's pivots prove a
        narrower bracket (with ``with_ends`` its ends are also pulled in
        to its outermost elements: nothing lies between them and the old
        ends, so the two counts stand), and the next pivots are placed."""
        _, target, pad, local_valid = table(xl, spec)
        lo, hi, c_lo, c_hi, piv, _, was_slow, gave_up = state
        c = count(xl, piv, pad)
        ok = c >= target
        new_hi = jnp.minimum(hi, jnp.min(jnp.where(ok, piv, full), axis=0))
        new_c_hi = jnp.minimum(c_hi, jnp.min(
            jnp.where(ok, c, jnp.int32(2**31 - 1)), axis=0))
        new_lo = jnp.maximum(lo, jnp.max(
            jnp.where(ok, u32(0), piv + u32(1)), axis=0))
        new_c_lo = jnp.maximum(c_lo, jnp.max(jnp.where(ok, 0, c), axis=0))
        if with_ends:
            a, b = _ends_within(xl, lo, hi, local_valid)
            # (the smallest over the shards is the complement of the
            # largest complement: no negation to overflow)
            new_lo = jnp.maximum(new_lo, _unsigned(~mr.reduce_max(~a, axes)))
            new_hi = jnp.minimum(new_hi, _unsigned(mr.reduce_max(b, axes)))
        slow = (new_hi - new_lo) > ((hi - lo) >> u32(2))
        stuck = (new_c_hi - new_c_lo) == (c_hi - c_lo)
        # (one slow pass is a count outside its margin as often as a
        # column that has no smooth ranks: the second gives them up)
        return (new_lo, new_hi, new_c_lo, new_c_hi,
                place(new_lo, new_hi, new_c_lo, new_c_hi, slow & was_slow,
                      target), stuck, slow, gave_up)

    def select_head(xl, spec):
        d = xl.shape[1]
        state = (jnp.zeros((m, d), u32), jnp.zeros((m, d), u32) + full,
                 jnp.zeros((m, d), jnp.int32),
                 jnp.zeros((m, d), jnp.int32) + spec[0],
                 first_guess(xl, spec), jnp.zeros((m, d), bool),
                 jnp.zeros((m, d), bool), jnp.zeros((m, d), bool))
        # (a table that is its own sample guessed its answers exactly:
        # the one pass that proves them)
        pad = table(xl, spec)[2]
        if xl.shape[0] <= SAMPLE_ROWS:
            state = a_pass(xl, spec, state, with_ends=False)
            return _pack(*state), _report(state, pad, 1)
        for _ in range(HEAD_PASSES):
            state = a_pass(xl, spec, state, with_ends=False)
        # what the driver would launch next where the report said so, a
        # host trip sooner (1.4 ms): the branch not taken costs nothing,
        # and behind this branch, as inside its loop, the finishing pass
        # takes slices of the table where they lie (no reduction over the
        # table stands behind it)
        state, reads, finished, declined = jax.lax.cond(
            _wants_finish(state, pad),
            lambda state: _finish(xl, spec, state, axes),
            lambda state: (state,) + (jnp.int32(0),) * 3, state)
        return _pack(*state), _report(state, pad, HEAD_PASSES + reads,
                                      finished, declined)

    def select_step(xl, spec, packed):
        state = a_pass(xl, spec, _unpack(packed), with_ends=False)
        return _pack(*state), _report(state, table(xl, spec)[2], 1)

    def select_step_ends(xl, spec, packed):
        state = a_pass(xl, spec, _unpack(packed), with_ends=True)
        return _pack(*state), _report(state, table(xl, spec)[2], 1)

    rows = P(data_pspec(mesh), None)
    return (mr.map_shards(select_head, mesh, in_specs=(rows, P()),
                          out_specs=(P(), P())),
            mr.map_shards(select_step, mesh, in_specs=(rows, P(), P()),
                          out_specs=(P(), P())),
            mr.map_shards(select_step_ends, mesh, in_specs=(rows, P(), P()),
                          out_specs=(P(), P())))


@functools.lru_cache(maxsize=32)
@cold_build("select_finish")
def finish_program(mesh, m: int):
    """``finish(xs, spec, state) -> (state, report)``: the finishing pass
    of a selection of ``m`` order statistics a column over ``mesh``, held
    and traced when first called like :func:`select_programs`' three, whose
    operands and results it shares.

    One read of the table (``FINISH_GROUP`` brackets of a column a read:
    one read at three probabilities) that takes the elements of every open
    bracket OUT instead of counting them once more: the rows of a shard
    fall into ``FINISH_BLOCKS`` blocks by row index, each block keeps for
    each bracket the count, sum, smallest and largest of the offsets of its
    elements in the bracket (:func:`block_sums`), which give up to three
    elements a block back exactly (:func:`block_elements`), and the wanted
    one is then searched among them, a small round a bit of the widest
    bracket over the blocks' words, as ``first_guess`` searches the sample (the counts cross the shards by
    the same ``reduce_sum``). A bracket that is not :func:`_finishable`, or
    of which some block holds more than three elements, is DECLINED: left
    exactly as it was, with a mark that it is not to be offered again, for
    the counting passes to go on with. So an answer is proven as before,
    and what the table decides is still only the number of reads.

    The shards' rows of zero padding are elements like any other to the
    blocks, and are taken off the counts among the candidates as the
    counting passes take them off theirs."""
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import mapreduce as mr
    from flink_ml_tpu.parallel.mesh import data_axes, data_pspec

    axes = data_axes(mesh)

    def select_finish(xl, spec, packed):
        state, reads, finished, declined = _finish(
            xl, spec, _unpack(packed), axes)
        return _pack(*state), _report(state, _table(xl, spec, axes)[2],
                                      reads, finished, declined)

    return mr.map_shards(select_finish, mesh,
                         in_specs=(P(data_pspec(mesh), None), P(), P()),
                         out_specs=(P(), P()))


def _finish(xl, spec, state, axes):
    """The finishing pass over this shard's rows ``xl`` (see
    :func:`finish_program`): ``(the state it leaves, the reads of the table
    it made, the brackets it closed, the open brackets it left)``."""
    from flink_ml_tpu.parallel import mapreduce as mr

    u32 = jnp.uint32
    _, target, pad, _ = _table(xl, spec, axes)
    lo, hi, c_lo, c_hi, piv, stuck, was_slow, gave_up = state
    width = hi - lo
    is_open, offered = _offered(state, pad)

    def read(group):
        return block_elements(
            block_sums(xl, lo[group], width[group], FINISH_BLOCKS),
            width[group]) + (jnp.int32(1),)

    def skip(group):
        shape = lo[group].shape
        return (jnp.full((3,) + shape + (FINISH_BLOCKS,), u32(0xFFFFFFFF)),
                jnp.zeros(shape, bool), jnp.int32(0))

    # (of several groups of brackets, one none of whose brackets is offered
    # is not read for: nine probabilities whose outer ones closed early cost
    # one read, not three. A lone group is read: something in it is open)
    several = lo.shape[0] > FINISH_GROUP
    groups = [jax.lax.cond(jnp.any(offered[group]), lambda: read(group),
                           lambda: skip(group)) if several else read(group)
              for group in (slice(g, g + FINISH_GROUP)
                            for g in range(0, lo.shape[0], FINISH_GROUP))]
    found = jnp.concatenate([g[0] for g in groups], axis=1)
    crowded = mr.reduce_max(jnp.concatenate(
        [g[1] for g in groups]).astype(jnp.int32), axes) > 0
    need = target - c_lo              # 1-based, among the bracket's own
    zero = u32(_TOP)
    zeros_in = pad * ((lo <= zero) & (zero <= hi)).astype(jnp.int32)

    def upto(at):
        """The bracket's elements at or under offset ``at (m, d)``."""
        c = mr.reduce_sum(jnp.sum(found <= at[None, :, :, None],
                                  axis=(0, 3), dtype=jnp.int32), axes)
        return c - zeros_in * (zero - lo <= at).astype(jnp.int32)

    def halve(bracket):
        a, z = bracket
        mid = a + (z - jnp.minimum(a, z)) // u32(2)
        ok = upto(mid) >= need
        return (jnp.where((a < z) & ~ok, mid + u32(1), a),
                jnp.where((a < z) & ok, mid, z))

    # (as many rounds as the widest offered bracket has bits: eight at the
    # some 200 keys a smooth column's brackets span)
    _, at = jax.lax.while_loop(
        lambda bracket: jnp.any(bracket[0] < bracket[1]), halve,
        (jnp.zeros_like(lo), jnp.where(offered, width, u32(0))))
    done = offered & ~crowded
    under = jnp.where(at > 0, upto(at - jnp.minimum(at, u32(1))), 0)
    key = jnp.where(done, lo + at, hi)
    state = (jnp.where(done, key, lo), key,
             jnp.where(done, c_lo + under, c_lo),
             jnp.where(done, c_lo + upto(at), c_hi),
             jnp.where(done, key, piv), stuck, was_slow,
             gave_up | (is_open & ~done))
    return (state, sum(g[2] for g in groups), jnp.sum(done, dtype=jnp.int32),
            jnp.sum(is_open & ~done, dtype=jnp.int32))


class Report(NamedTuple):
    """A program's report on the host (:func:`read_report`)."""
    #: ``(m, d)`` float32: the elements at the brackets' upper ends (the
    #: answers once no bracket is open)
    found: np.ndarray
    #: a bracket is still open
    more: bool
    #: the next pass should pull the brackets' ends in (``step_ends``)
    ends: bool
    #: the open brackets are few-element brackets: the next pass should
    #: finish them (:func:`finish_program`)
    finish: bool
    #: brackets this program's finishing pass closed, and left open
    finished: int
    declined: int
    #: whole reads of the table the program made
    passes: int


def read_report(report: np.ndarray, m: int) -> Report:
    """A program's report (:func:`_report`'s int32 vector) on the host."""
    return Report(report[:-6].view(np.float32).reshape(m, -1),
                  *map(bool, report[-6:-3]), *map(int, report[-3:]))


def select_ranks(probs: Sequence[float], n: int) -> np.ndarray:
    """0-based rank ``floor(q (n - 1))`` of each probability: numpy's
    ``method='lower'`` (docs/deviations.md)."""
    return np.floor(np.asarray(probs, np.float64) * (n - 1)).astype(np.int32)


@functools.lru_cache(maxsize=32)
def _spec_on_mesh(mesh, n: int, probs: tuple):
    """The replicated ``[n, ranks...]`` operand of the programs, placed
    once a mesh, row count and probabilities: a warm fit places nothing."""
    from flink_ml_tpu.parallel.collective import replicate

    return replicate(mesh, np.concatenate(
        [[n], select_ranks(probs, n)]).astype(np.int32))


def select_on_device(x, probs: Sequence[float]):
    """Per-column order statistics of a DEVICE ``(n, d)`` float32 column →
    ``((m, d) float32 on the host, passes)``: the element of 0-based rank
    ``floor(q (n - 1))`` of every column, EXACT for every float32 input
    (ties, negatives, infinities, denormals; NaN bit patterns sort outside
    the finite band, negative payloads below -inf, positive above +inf, as
    a sort puts them at the ends), with no sort and no second copy of the
    table, and the whole reads of the table it took.

    The one driver of :func:`select_programs` and :func:`finish_program`:
    the head, then one program a launch while a bracket is open (the
    finishing pass where the report says the open brackets are few-element
    brackets, else one more counting pass, with or without the brackets'
    ends), each program's report read through ``read_boundary`` before the
    next is chosen. Spans ``select.place_inputs``,
    ``select.build_program``, then a ``select.launch`` (enqueue only) and a
    ``select.fetch`` (the blocking read; ``passes``: the reads of the table
    it waited for; after a finishing pass also ``finished`` and
    ``declined``: the brackets it closed and left open) a program; counters
    ``ml.select passes``, ``finished`` and ``declined``."""
    from flink_ml_tpu.common.metrics import ML_GROUP, metrics
    from flink_ml_tpu.iteration.iteration import read_boundary
    from flink_ml_tpu.parallel.collective import ensure_on_mesh
    from flink_ml_tpu.parallel.mesh import data_axes, default_mesh

    mesh = default_mesh()
    d, m = x.shape[1], len(probs)
    with tracer.span("select.place_inputs"):
        xs, n = ensure_on_mesh(mesh, x, data_axes(mesh), np.float32)
        spec = _spec_on_mesh(mesh, n, tuple(float(q) for q in probs))
    with tracer.span("select.build_program"):
        head, step, step_ends = select_programs(mesh, m)
        finish = finish_program(mesh, m)
    with tracer.span("select.launch", path="select-device", rows=n, d=d,
                     probs=list(probs)):
        state, report = head(xs, spec)
    passes = finished = declined = 0
    while True:
        with tracer.span("select.fetch") as sp:
            seen = read_report(read_boundary((report,))[0], m)
            sp.set_attribute("passes", seen.passes)
            # (a finishing pass closes or leaves every open bracket)
            if seen.finished or seen.declined:
                sp.set_attribute("finished", seen.finished)
                sp.set_attribute("declined", seen.declined)
        passes += seen.passes
        finished += seen.finished
        declined += seen.declined
        if not seen.more:
            break
        with tracer.span("select.launch", ends=seen.ends,
                         finish=seen.finish):
            state, report = (finish if seen.finish else
                             step_ends if seen.ends else step)(
                xs, spec, state)
    group = metrics.group(ML_GROUP, "select")
    group.counter("passes", passes)
    group.counter("finished", finished)
    group.counter("declined", declined)
    return seen.found, passes
