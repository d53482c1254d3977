"""Exact per-column order statistics of a device-resident column
(``ops/quantile.select_on_device`` over ``select_programs``, the programs
under ``RobustScaler.fit``, path ``select-device``) against ``np.sort`` and
``np.quantile(method="lower")`` on seeded columns: ragged row counts, widths
of 1, 7, 13 and 100, one, three and nine probabilities with 0 and 1 among
them, heavy ties, negatives, infinities, NaN, denormals and signed zeros,
columns of one value, of two, of NaN alone, a sorted and a reverse-sorted
table (the first guess comes from a few runs of rows: it must fall back, not
err), one device and four (equal bit for bit), every number of passes in the
head; the finishing pass (``finish_program``: the few elements left in a
narrow bracket taken out, not counted once more) against numpy, bracket by
bracket: what it closes, what it declines and leaves exactly as it was; a
bound on the passes a distribution; the traced programs hold no loop or
branch around a reduction over the table and nothing larger than it; a warm fit
builds nothing; a process's first fit stays inside its budget of programs
and lowered text and imports no Pallas; the spans and counters of a fit; and
the host path gives the same model.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.feature.scalers import RobustScaler
from flink_ml_tpu.observability import tracing
from flink_ml_tpu.observability.tracing import tracer
from flink_ml_tpu.ops import quantile
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh
from test_kmeans_lowering import eqns
from test_kmeans_warm_fit import BUILDS
from test_optimizer_warm_fit import Watch

#: the sample the program ships with (the fixture below shrinks it)
SHIPPED = (quantile.SAMPLE_ROWS, quantile.SAMPLE_RUNS)
ROOT = os.path.join(os.path.dirname(__file__), "..")


def drop_programs():
    quantile.select_programs.cache_clear()
    quantile.finish_program.cache_clear()
    quantile._spec_on_mesh.cache_clear()


@pytest.fixture(autouse=True)
def restore_state(monkeypatch):
    """A sample of 4 runs of 64 rows, so that tables of a few thousand rows
    are not their own sample; programs built under it are dropped."""
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    monkeypatch.setattr(quantile, "SAMPLE_ROWS", 256)
    monkeypatch.setattr(quantile, "SAMPLE_RUNS", 4)
    drop_programs()
    tracer.recent.clear()
    yield
    set_default_mesh(None)
    drop_programs()
    tracer.recent.clear()


def on_mesh(devices):
    mesh = create_mesh(devices=jax.devices()[:devices])
    set_default_mesh(mesh)
    return mesh


def column(name, rng):
    if name == "ragged":
        return rng.random((1001, 7), dtype=np.float32)
    if name == "one-column":
        return rng.standard_normal((2403, 1)).astype(np.float32)
    if name == "thirteen-columns":
        return (rng.standard_normal((2500, 13)) * 100).astype(np.float32)
    if name == "long":
        return rng.random((40_000, 5), dtype=np.float32)
    if name == "hundred-columns":
        return rng.random((1203, 100), dtype=np.float32)
    if name == "arity-2":
        return np.floor(rng.random((3000, 3)) * 2).astype(np.float32)
    if name == "arity-20":
        return np.floor(rng.random((3000, 3)) * 20).astype(np.float32) - 7
    if name == "zero-inflated":
        u = rng.random((3001, 3))
        return np.where(u < 0.6, 0.0, -np.log1p(-(u - 0.6) / 0.4)).astype(
            np.float32)
    if name == "hostile":
        x = rng.standard_cauchy((2001, 3)).astype(np.float32)
        x[::7], x[::11], x[::13] = np.inf, -np.inf, 1e-42
        x[5], x[6], x[100:103] = -0.0, 0.0, np.nan
        return x
    if name == "one-value":
        return np.full((700, 2), 7.5, np.float32)
    if name == "all-nan":
        return np.full((600, 2), np.nan, np.float32)
    if name == "sorted":
        return np.sort(rng.random((3000, 3), dtype=np.float32), axis=0)
    if name == "reverse-sorted":
        return np.sort(rng.standard_normal((3000, 3)).astype(np.float32),
                       axis=0)[::-1].copy()
    raise KeyError(name)


#: column -> the most passes it may take at these sizes over one, three and
#: nine probabilities on one device and four (what it takes today: the
#: count is the table's, so a rule of the program that stopped working
#: shows here; a bisection takes 32 on every one of them, and without the
#: rule that pulls a bracket of one value in to it the tied columns take a
#: dozen and more). A table longer than its sample takes the head's three
#: at the least, and a tied one a pass or two more than it would pass by
#: pass: the head's passes only count, and the pass that pulls a bracket
#: in to its elements costs two and a half of them, so it is asked for
#: only where counts alone would take more than three (``ENDS_KEYS``;
#: PERF.md section 6, PR 36). Since PR 37 brackets of a few dozen elements
#: are finished in one read (nine probabilities: three), which took two to
#: six passes off ten of these twelve
COLUMNS = {"ragged": 7, "one-column": 7, "thirteen-columns": 7,
           "hundred-columns": 7, "arity-2": 4, "arity-20": 6,
           "zero-inflated": 6, "hostile": 9, "one-value": 3, "all-nan": 3,
           "sorted": 8, "reverse-sorted": 13}
PROBS = {1: [0.5], 3: [0.25, 0.5, 0.75],
         9: [0.0, 0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0]}


def sorted_at(x, probs):
    """``np.sort`` puts NaN last; the keys put a NaN of negative sign
    first. The hostile column's NaNs are positive: the two agree."""
    ranks = np.floor(np.asarray(probs) * (len(x) - 1)).astype(int)
    return np.sort(x, axis=0)[ranks]


def select(x, probs, devices):
    on_mesh(devices)
    return quantile.select_on_device(jnp.asarray(x), probs)


def bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


# -- the programs against a sort -----------------------------------------------

@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("m", [1, 3, 9])
@pytest.mark.parametrize("name", list(COLUMNS))
def test_the_program_selects_what_a_sort_does(name, m, devices):
    x = column(name, np.random.default_rng(len(name) + m))
    got, passes = select(x, PROBS[m], devices)
    assert got.dtype == np.float32 and got.shape == (m, x.shape[1])
    np.testing.assert_array_equal(bits(got), bits(sorted_at(x, PROBS[m])))
    if np.isfinite(x).all():
        np.testing.assert_array_equal(got, np.quantile(
            x, PROBS[m], axis=0, method="lower"))
    assert 1 <= passes <= COLUMNS[name]


@pytest.mark.parametrize("head", [1, 2, 3, 5, 7])
@pytest.mark.parametrize("name", ["ragged", "arity-20", "hostile",
                                  "reverse-sorted"])
def test_every_number_of_passes_in_the_head_selects_the_same(name, head,
                                                             monkeypatch):
    """The head's passes are the step's, one after another: how many of
    them a program holds changes who launches a pass, not what it proves
    (nor, where no bracket wanted its ends pulled in meanwhile, how many
    there are)."""
    monkeypatch.setattr(quantile, "HEAD_PASSES", head)
    x = column(name, np.random.default_rng(12))
    got, passes = select(x, PROBS[9], 1)
    np.testing.assert_array_equal(bits(got), bits(sorted_at(x, PROBS[9])))
    assert head <= passes <= head + COLUMNS[name] + 2


def test_one_device_and_four_agree_bit_for_bit():
    x = column("hostile", np.random.default_rng(9))
    one, _ = select(x, PROBS[9], 1)
    four, _ = select(x, PROBS[9], 4)
    np.testing.assert_array_equal(bits(one), bits(four))


def test_a_smooth_column_takes_a_handful_of_passes():
    """300k uniform rows, a sample of 256: the first guess, then brackets
    of some 2 sqrt(K) elements; a bisection would take 32."""
    x = np.random.default_rng(0).random((300_000, 2), dtype=np.float32)
    got, passes = select(x, PROBS[3], 1)
    np.testing.assert_array_equal(bits(got), bits(sorted_at(x, PROBS[3])))
    assert passes <= 12


def select_forms():
    path = os.path.join(ROOT, "scripts", "select_forms.py")
    spec = importlib.util.spec_from_file_location("select_forms", path)
    forms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(forms)
    return forms


#: distribution (``scripts/select_forms.table_makers``: the tables the chip
#: timed at 12M x 100, PERF.md section 6) -> the most passes 1M x 3 rows of
#: seed 0 may take under the sample the program ships with, on one device
#: and four; what they take today and one more (until PR 37, without the
#: finishing pass: 8, 8, 9, 7, 6)
TABLES = {"uniform": 5, "normal": 6, "zero_inflated": 5, "integer_coded": 7,
          "sorted": 5}


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", list(TABLES))
def test_the_passes_of_a_table_are_its_distributions(name, devices,
                                                     monkeypatch):
    monkeypatch.setattr(quantile, "SAMPLE_ROWS", SHIPPED[0])
    monkeypatch.setattr(quantile, "SAMPLE_RUNS", SHIPPED[1])
    drop_programs()
    n = 1_000_000
    x = np.asarray(select_forms().table_makers(n, 3)[name](
        jax.random.key(0)))
    got, passes = select(x, PROBS[3], devices)
    np.testing.assert_array_equal(bits(got), bits(sorted_at(x, PROBS[3])))
    assert passes <= TABLES[name]


def test_a_table_that_is_its_own_sample_takes_one_pass(monkeypatch):
    monkeypatch.setattr(quantile, "SAMPLE_ROWS", 1 << 16)
    monkeypatch.setattr(quantile, "SAMPLE_RUNS", 16)
    x = column("ragged", np.random.default_rng(1))
    for devices in (1, 4):
        got, passes = select(x, PROBS[3], devices)
        np.testing.assert_array_equal(bits(got),
                                      bits(sorted_at(x, PROBS[3])))
        assert passes == 1


def test_a_tied_column_has_its_brackets_pulled_in_to_their_elements(
        monkeypatch):
    """Whole numbers: a bracket that three spread counts did not part is
    one value many times over, and the pass that looks for its elements
    (``select_step_ends``) ends it at once."""
    monkeypatch.setattr(tracer, "keep_recent", True)
    # (a thousand rows a value: too many for the finishing pass to take)
    x = np.floor(np.random.default_rng(3).random((20_000, 3)) * 20).astype(
        np.float32) - 7
    got, passes = select(x, PROBS[3], 1)
    np.testing.assert_array_equal(bits(got), bits(sorted_at(x, PROBS[3])))
    launches = [r["attrs"] for r in tracer.recent
                if r["name"] == "select.launch"]
    assert launches[0]["path"] == "select-device"
    assert [a["ends"] for a in launches[1:]].count(True) >= 1
    assert not any(a["finish"] for a in launches[1:])    # never so few
    assert passes == quantile.HEAD_PASSES + len(launches) - 1


# -- the pieces ------------------------------------------------------------------

def test_the_keys_order_floats_as_ieee_does():
    values = np.array([-np.inf, -1e30, -1.0, -1e-42, -0.0, 0.0, 1e-42, 1.0,
                       1e30, np.inf], np.float32)
    keys = np.asarray(quantile.float_keys(jnp.asarray(values)))
    assert (np.diff(keys.astype(np.int64)) > 0).all()
    np.testing.assert_array_equal(
        bits(quantile.keys_to_float(jnp.asarray(keys))), bits(values))
    nan = np.asarray(quantile.float_keys(jnp.asarray(
        [np.nan, -np.nan], np.float32)))
    assert nan[0] > keys[-1] and nan[1] < keys[0]


@pytest.mark.parametrize("n,d,valid", [(1000, 11, 900), (513, 1, 513),
                                       (2048, 100, 2047), (77, 8, 0)])
def test_a_pass_counts_and_finds_ends_as_numpy_does(n, d, valid):
    rng = np.random.default_rng(n)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x[3], x[5, 0] = np.inf, -0.0
    keys = np.asarray(quantile.float_keys(jnp.asarray(x)))
    piv = np.sort(rng.choice(keys.ravel(), (5, d)), axis=0)
    np.testing.assert_array_equal(
        quantile.count_le(jnp.asarray(x[:valid]), jnp.asarray(piv)),
        np.stack([(keys[:valid] <= p).sum(0) for p in piv]))
    lo, hi = piv[:2], piv[3:]
    a, b = quantile._ends_within(
        jnp.asarray(x), quantile._unsigned(jnp.asarray(lo)),
        quantile._unsigned(jnp.asarray(hi)), valid)
    # (where no row lies in the bracket: its other end)
    np.testing.assert_array_equal(a, np.stack([np.where(
        keys[:valid] >= t, keys[:valid], u).min(0, initial=2**31 - 1)
        .clip(max=u) for t, u in zip(lo, hi)]))
    np.testing.assert_array_equal(b, np.stack([np.where(
        keys[:valid] <= u, keys[:valid], t).max(0, initial=-2**31)
        .clip(min=t) for t, u in zip(lo, hi)]))


# -- the finishing pass ----------------------------------------------------------

def ukeys(x):
    return np.asarray(quantile._float_to_key(jnp.asarray(x, jnp.float32)))


def brackets_around(x, ranks, below, above, wider=(0, 0)):
    """Proven brackets ``(lo, hi, c_lo, c_hi)``, each ``(m, d)``, around the
    elements of 0-based ``ranks`` of every column: from the element
    ``below`` ranks under the wanted one to the one ``above`` over it, made
    ``wider`` by so many keys either side, with the two counts that prove
    them (keys under ``lo``, keys at or under ``hi``)."""
    keys, n = ukeys(x), len(x)
    by_rank = np.sort(keys, axis=0)
    lo = np.stack([by_rank[max(r - below, 0)] for r in ranks]) - np.uint32(
        wider[0])
    hi = np.stack([by_rank[min(r + above, n - 1)] for r in ranks]) + np.uint32(
        wider[1])
    c_lo = (keys[None] < lo[:, None]).sum(1).astype(np.int32)
    c_hi = (keys[None] <= hi[:, None]).sum(1).astype(np.int32)
    return lo, hi, c_lo, c_hi


def crowded(x, lo, hi, devices, blocks):
    """Which brackets have a block that holds more than three of their
    elements: the rows, zero-padded to a multiple of ``devices``, split
    evenly over the shards, a shard's row ``i`` in its block ``i %
    blocks``."""
    keys = ukeys(x)
    pad = (-len(keys)) % devices
    keys = np.concatenate([keys, np.full((pad, keys.shape[1]),
                                         quantile._TOP, np.uint32)])
    local = len(keys) // devices
    row = np.arange(len(keys))
    block = row % local % blocks + blocks * (row // local)
    out = np.zeros(lo.shape, bool)
    for i, c in np.ndindex(*lo.shape):
        inside = (keys[:, c] >= lo[i, c]) & (keys[:, c] <= hi[i, c])
        out[i, c] = inside.any() and np.bincount(block[inside]).max() > 3
    return out


def takes(held, width, pad):
    """The gate of the finishing pass, as its docstring states it: at most
    ``FINISH_HELD`` elements in at most ``FINISH_KEYS`` keys, and four
    thirds of the elements (and the rows of padding) under what the count
    of a block's packed word has room for beside three offsets."""
    bits = np.vectorize(lambda w: max(int(3 * w).bit_length(), 1))(
        np.minimum(width.astype(np.int64), quantile.FINISH_KEYS))
    return ((held <= quantile.FINISH_HELD) & (width <= quantile.FINISH_KEYS)
            & (4 * (held.astype(np.int64) + pad) < 3 * 2.0 ** (32 - bits)))


def finish(x, ranks, brackets, devices, gave_up=None):
    """The finishing program on ``brackets`` of ``x`` → ``(the state it
    leaves, its report)``."""
    from flink_ml_tpu.parallel.collective import ensure_on_mesh, replicate
    from flink_ml_tpu.parallel.mesh import data_axes

    mesh = on_mesh(devices)
    xs, n = ensure_on_mesh(mesh, jnp.asarray(x, jnp.float32),
                           data_axes(mesh), np.float32)
    spec = replicate(mesh, np.asarray([n, *ranks], np.int32))
    lo, hi, c_lo, c_hi = map(jnp.asarray, brackets)
    no = jnp.zeros(lo.shape, bool)
    state = quantile._pack(lo, hi, c_lo, c_hi, jnp.stack([lo, lo, hi]), no,
                           no, no if gave_up is None else jnp.asarray(gave_up))
    packed, report = quantile.finish_program(mesh, len(ranks))(
        xs, spec, state)
    return ([np.asarray(v) for v in quantile._unpack(packed)],
            quantile.read_report(np.asarray(report), len(ranks)))


def check_finish(x, ranks, brackets, devices):
    """The finishing pass against numpy: a bracket it takes is closed on
    the element a sort puts at the rank, with the counts that prove it; one
    it does not take (too many elements, too many keys, four of them in one
    block) is left exactly as it was, and marked. Returns which it
    closed."""
    state, seen = finish(x, ranks, brackets, devices)
    lo, hi, c_lo, c_hi = brackets
    keys = ukeys(x)
    want = np.sort(keys, axis=0)[np.asarray(ranks)]
    is_open = hi > lo
    offered = is_open & takes(c_hi - c_lo, hi - lo, (-len(x)) % devices)
    done = offered & ~crowded(x, lo, hi, devices, quantile.FINISH_BLOCKS)
    np.testing.assert_array_equal(state[0], np.where(done, want, lo))
    np.testing.assert_array_equal(state[1], np.where(done, want, hi))
    np.testing.assert_array_equal(state[2], np.where(
        done, (keys[None] < want[:, None]).sum(1), c_lo))
    np.testing.assert_array_equal(state[3], np.where(
        done, (keys[None] <= want[:, None]).sum(1), c_hi))
    np.testing.assert_array_equal(state[7], is_open & ~done)
    assert (seen.finished, seen.declined) == (done.sum(),
                                              (is_open & ~done).sum())
    # a read of the table a group of brackets that holds an offered one
    # (a lone group is read whatever it holds)
    group = quantile.FINISH_GROUP
    assert seen.passes == sum(
        offered[g:g + group].any() or len(ranks) <= group
        for g in range(0, len(ranks), group))
    assert seen.more == (is_open & ~done).any() and not seen.finish
    np.testing.assert_array_equal(
        bits(seen.found), bits(np.asarray(quantile._key_to_float(
            jnp.asarray(state[1])))))
    return done


def middle_of_three(rng, middle, spread=0.1):
    """A column of 2,000 rows whose ``middle`` central elements lie in
    ``[1, 1 + spread)``, the others far outside."""
    x = np.concatenate([np.full(1000 - middle // 2, -1e30),
                        1 + rng.random(middle) * spread,
                        np.full(1000 - (middle + 1) // 2, 1e30)])
    return rng.permutation(x).astype(np.float32)[:, None]


def finishing_case(name, rng):
    """``(x, ranks, brackets, blocks, closes)``: a table, the ranks
    wanted, proven brackets around them, the blocks a shard, and whether
    the pass must close every bracket on one device (None: the oracle
    says)."""
    if name in ("smooth-1", "smooth-3", "smooth-9"):
        x = rng.standard_normal((3001, 5)).astype(np.float32)
        ranks = quantile.select_ranks(PROBS[int(name[-1])], len(x))
        return x, ranks, brackets_around(x, ranks, 3, 4), 64, None
    if name == "ties":
        x = np.floor(rng.random((3000, 4)) * 400).astype(np.float32) + 1000
        ranks = quantile.select_ranks(PROBS[3], len(x))
        return x, ranks, brackets_around(x, ranks, 8, 8), 512, True
    if name in ("rank-first", "rank-last"):
        x = rng.random((2500, 3), dtype=np.float32)
        ranks = quantile.select_ranks(PROBS[3], len(x))
        around = (0, 30) if name == "rank-first" else (30, 0)
        return x, ranks, brackets_around(x, ranks, *around), 4096, True
    if name in ("held-at-the-gate", "held-one-over"):
        x = rng.random((40_000, 2), dtype=np.float32)
        ranks = quantile.select_ranks(PROBS[3], len(x))
        above = quantile.FINISH_HELD - 200 - (name == "held-at-the-gate")
        found = brackets_around(x, ranks, 200, above)
        assert (found[3] - found[2] == quantile.FINISH_HELD
                + (name == "held-one-over")).all()
        return x, ranks, found, 32768, name == "held-at-the-gate"
    if name in ("keys-at-the-bound", "keys-one-over", "room-at-the-edge",
                "room-one-over"):
        # the most keys a bracket may span (eight elements in them: the
        # count beside a sum of 28 bits has four), and the most elements
        # 2,000,000 keys leave room for (a sum of 23 bits, a count of
        # nine: four thirds of 383 are under 512)
        keys, middle, spread = {
            "keys-at-the-bound": (quantile.FINISH_KEYS, 8, 500.0),
            "keys-one-over": (quantile.FINISH_KEYS + 1, 8, 500.0),
            "room-at-the-edge": (2_000_000, 383, 0.23),
            "room-one-over": (2_000_000, 384, 0.23)}[name]
        x = middle_of_three(rng, middle, spread)
        ranks = np.asarray([1000 - middle // 4, 999, 1000 + middle // 4],
                           np.int32)
        lo = np.full((3, 1), ukeys(np.float32(1.0)), np.uint32)
        hi = lo + np.uint32(keys)
        at = ukeys(x)
        found = (lo, hi, (at[None] < lo[:, None]).sum(1).astype(np.int32),
                 (at[None] <= hi[:, None]).sum(1).astype(np.int32))
        assert (found[3] - found[2] == middle).all()
        return x, ranks, found, 32768, name in ("keys-at-the-bound",
                                                "room-at-the-edge")
    if name == "one-block":
        # the elements of every bracket 64 rows apart: all in one block
        x = middle_of_three(rng, 0)
        x[5:5 + 64 * 5:64, 0] = 1 + np.arange(5, dtype=np.float32) / 8
        ranks = np.asarray([np.sort(x[:, 0]).tolist().index(1.25)], np.int32)
        return x, ranks, brackets_around(x, ranks, 2, 2), 64, False
    tiny = np.arange(1, 1001, dtype=np.uint32)       # denormals: keys apart
    if name == "signed-zeros":
        x = np.concatenate([tiny | np.uint32(quantile._TOP),
                            [quantile._TOP] * 6, [0] * 5, tiny])
        x = rng.permutation(x.astype(np.uint32)).view(np.float32)[:, None]
        ranks = np.asarray([999, 1005, 1006, 1010, 1011], np.int32)
        return x, ranks, brackets_around(x, ranks, 4, 4), 4096, True
    if name == "nan-payloads":
        # positive payloads sort over +inf, negative ones under -inf
        x = np.concatenate([
            rng.standard_normal(900).astype(np.float32).view(np.uint32),
            np.float32([np.inf, -np.inf]).view(np.uint32),
            0x7FC00000 + np.asarray([0, 1, 2, 5, 9, 9, 17, 40]),
            0xFFC00000 + np.asarray([0, 3, 3, 4, 11, 30])])
        x = rng.permutation(x.astype(np.uint32)).view(np.float32)[:, None]
        ranks = np.asarray([1, 2, 4, 910, 912, 914], np.int32)
        return x, ranks, brackets_around(x, ranks, 1, 1), 4096, True
    if name == "padding":
        # 1,001 rows over four shards: three rows of zero padding, and
        # brackets that hold +0.0
        x = np.concatenate([tiny[:498] | np.uint32(quantile._TOP), [0] * 4,
                            tiny[:499]])
        x = rng.permutation(x.astype(np.uint32)).view(np.float32)[:, None]
        ranks = np.asarray([497, 500, 502], np.int32)
        return x, ranks, brackets_around(x, ranks, 3, 3), 4096, True
    raise KeyError(name)


FINISHING = ["smooth-1", "smooth-3", "smooth-9", "ties", "rank-first",
             "rank-last", "held-at-the-gate", "held-one-over",
             "keys-at-the-bound", "keys-one-over", "room-at-the-edge",
             "room-one-over", "one-block",
             "signed-zeros", "nan-payloads", "padding"]


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", FINISHING)
def test_the_finishing_pass_takes_out_what_a_sort_finds(name, devices,
                                                        monkeypatch):
    x, ranks, brackets, blocks, closes = finishing_case(
        name, np.random.default_rng(len(name)))
    monkeypatch.setattr(quantile, "FINISH_BLOCKS", blocks)
    monkeypatch.setattr(quantile, "FINISH_SLICES", 3)
    drop_programs()
    done = check_finish(x, ranks, brackets, devices)
    if closes is not None and (devices == 1 or name != "one-block"):
        assert done.all() if closes else not done.any()


def test_one_device_and_four_finish_the_same_brackets_bit_for_bit(
        monkeypatch):
    x, ranks, brackets, _, _ = finishing_case(
        "signed-zeros", np.random.default_rng(2))
    monkeypatch.setattr(quantile, "FINISH_SLICES", 2)
    drop_programs()
    one, four = (finish(x, ranks, brackets, devices)
                 for devices in (1, 4))
    for a, b in zip(one[0], four[0]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(bits(one[1].found), bits(four[1].found))
    assert one[1][1:] == four[1][1:]


def test_a_bracket_the_finishing_pass_left_is_not_offered_again():
    """A mark in the state, so that the driver's loop ends: the report of
    a program that holds a declined bracket does not ask for the pass."""
    x, ranks, brackets, _, _ = finishing_case(
        "rank-first", np.random.default_rng(1))
    gave_up = np.zeros(brackets[0].shape, bool)
    gave_up[1] = True
    state, seen = finish(x, ranks, brackets, 1, gave_up)
    np.testing.assert_array_equal(state[0][1], brackets[0][1])
    np.testing.assert_array_equal(state[1][1], brackets[1][1])
    assert state[7][1].all() and not state[7][[0, 2]].any()
    assert seen.more and not seen.finish
    assert seen.declined == gave_up.sum()


def test_a_table_whose_neighbours_share_a_block_is_declined_and_still_exact(
        monkeypatch):
    """Neighbouring ranks ``FINISH_BLOCKS`` rows apart: every narrow
    bracket has its elements in one block, the finishing pass declines it,
    and the counting passes go on from where they stood, to what a sort
    finds."""
    monkeypatch.setattr(quantile, "FINISH_BLOCKS", 64)
    monkeypatch.setattr(tracer, "keep_recent", True)
    drop_programs()
    rng = np.random.default_rng(8)
    by_rank = np.sort(rng.random((64 * 200, 2), dtype=np.float32), axis=0)
    x = by_rank.reshape(64, 200, 2).transpose(1, 0, 2).reshape(-1, 2)
    before = metrics.group(ML_GROUP, "select").snapshot()["counters"]
    got, passes = select(x, PROBS[3], 1)
    np.testing.assert_array_equal(bits(got), bits(sorted_at(x, PROBS[3])))
    after = metrics.group(ML_GROUP, "select").snapshot()["counters"]
    fetches = [r["attrs"] for r in tracer.recent
               if r["name"] == "select.fetch"]
    launches = [r["attrs"] for r in tracer.recent
                if r["name"] == "select.launch"]
    # made once (by the head, behind its branch), never offered again
    made = [a for a in fetches if "declined" in a]
    assert len(made) == 1 and made[0] is fetches[0]
    assert not any(a.get("finish") for a in launches)
    asked = made[0]
    assert asked["declined"] >= 1
    assert asked["passes"] == quantile.HEAD_PASSES + 1
    assert after["declined"] - before.get("declined", 0) == asked["declined"]
    assert after["finished"] - before.get("finished", 0) == asked["finished"]
    # the counting passes went on after it
    assert len(launches) > 1
    assert passes == sum(a["passes"] for a in fetches)


def test_the_state_and_the_report_cross_whole():
    rng = np.random.default_rng(5)
    m, d = 3, 7
    u = lambda: jnp.asarray(rng.integers(0, 2**32, (m, d), dtype=np.uint32))
    c = lambda: jnp.asarray(rng.integers(-5, 2**31 - 1, (m, d)), jnp.int32)
    marks = [jnp.asarray(rng.random((m, d)) < 0.5) for _ in range(3)]
    state = (u(), u(), c(), c(), jnp.stack([u(), u(), u()]), *marks)
    packed = quantile._pack(*state)
    assert packed.shape == (5 + quantile.PIVOTS, m, d)
    assert packed.dtype == jnp.uint32
    for got, want in zip(quantile._unpack(packed), state):
        np.testing.assert_array_equal(got, want)
    answers = rng.standard_normal((m, d)).astype(np.float32)
    answers[0, 0], answers[1, 1] = np.nan, -0.0
    report = np.concatenate([answers.view(np.int32).ravel(),
                             [1, 0, 1, 17, 2, 4]])
    seen = quantile.read_report(report.astype(np.int32), m)
    np.testing.assert_array_equal(bits(seen.found), bits(answers))
    assert seen[1:] == (True, False, True, 17, 2, 4)
    assert (seen.more, seen.ends, seen.finish) == (True, False, True)
    assert (seen.finished, seen.declined, seen.passes) == (17, 2, 4)


def test_ranks_are_numpy_s_lower():
    for n in (1, 2, 7, 1000, 12_000_000):
        ranks = quantile.select_ranks(PROBS[9], n)
        x = np.arange(n, dtype=np.float64) if n <= 1000 else None
        if x is not None:
            np.testing.assert_array_equal(
                ranks, np.quantile(x, PROBS[9], method="lower"))
    np.testing.assert_array_equal(
        quantile.select_ranks(PROBS[3], 12_000_000),
        [2_999_999, 5_999_999, 8_999_999])


# -- the programs, as traced -----------------------------------------------------

LOOPS = ("while", "cond", "scan")


def inside(eqn):
    """Every equation under ``eqn`` (its own jaxprs, and theirs)."""
    for value in eqn.params.values():
        for inner in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield from eqns(inner)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("program", ["head", "step", "step_ends", "finish"])
def test_no_loop_or_branch_surrounds_a_read_of_the_table(program, devices):
    """What makes XLA copy the table before it reduces over its rows is
    control flow around the reduction (6.1 GB at 12M x 100: PERF.md section
    6), so every array as long as a shard lies outside every loop and
    branch (the sample's 32 small rounds are the one loop, over the
    sample); there is no sort, and nothing in a program is larger than the
    table itself (a ``(rows, pivots, d)`` compare would be). The finishing
    pass (its own program, and the head's last step behind a branch) is
    the one loop over the table, and it is over SLICES of it, which the
    compiler takes where they lie (``test_lloyd_gate_compiles`` holds the
    temporaries): the table enters a loop or a branch as the operand of
    ``dynamic_slice`` alone, no slice is longer than ``FINISH_BLOCKS``
    rows, nothing of a shard's length is made inside, and no reduction
    over an array as long as a shard stands inside one."""
    n, d, m = 400_000 * devices, 100, 3
    mesh = create_mesh(devices=jax.devices()[:devices])
    assert n // devices > quantile.SAMPLE_ROWS     # the head's full form
    built = dict(zip(("head", "step", "step_ends"),
                     quantile.select_programs(mesh, m)),
                 finish=quantile.finish_program(mesh, m))[program]
    operands = [jax.ShapeDtypeStruct((n, d), jnp.float32),
                jax.ShapeDtypeStruct((m + 1,), jnp.int32)]
    if program != "head":
        operands.append(jax.ShapeDtypeStruct(
            (5 + quantile.PIVOTS, m, d), jnp.uint32))
    traced = jax.make_jaxpr(built)(*operands)
    local = n // devices
    reads = slices = 0
    for eqn in eqns(traced.jaxpr):
        assert eqn.primitive.name != "sort"
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert int(np.prod(shape, dtype=np.int64)) <= n * d, (
                eqn.primitive, shape)
        reads += (eqn.primitive.name == "reduce_sum"
                  and local in eqn.invars[0].aval.shape)
        if eqn.primitive.name not in LOOPS:
            continue
        for held in inside(eqn):
            # (a loop or a branch inside hands the table on: what it holds
            # is looked at in its turn)
            taken = held.primitive.name in ("dynamic_slice", "slice")
            handed = len(held.invars) if held.primitive.name in LOOPS else (
                int(taken))
            slices += (held.primitive.name == "dynamic_slice"
                       and eqn.primitive.name != "cond"
                       and local in held.invars[0].aval.shape)
            for var in list(held.invars[handed:]) + list(held.outvars):
                shape = getattr(var.aval, "shape", ())
                assert local not in shape, (eqn.primitive, held.primitive,
                                            var.aval)
                assert not taken or max(shape, default=0) <= max(
                    quantile.FINISH_BLOCKS, d), (held.primitive, var.aval)
            assert not (held.primitive.name.startswith("reduce")
                        and local in held.invars[0].aval.shape)
    # the finishing pass, in its own program and behind the head's branch:
    # its slices a group of brackets, and no reduction over the table
    finishes = program in ("head", "finish")
    assert slices == finishes * quantile.FINISH_SLICES * -(
        -m // quantile.FINISH_GROUP)
    passes = {"head": quantile.HEAD_PASSES, "finish": 0}.get(program, 1)
    assert reads == passes * quantile.PIVOTS * m    # one sum a pivot


# -- the fit ---------------------------------------------------------------------

def device_table(x):
    return Table.from_columns(input=jnp.asarray(x, jnp.float32))


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", ["ragged", "hostile", "arity-20"])
def test_host_path_and_device_path_give_the_same_model(name, devices):
    x = column(name, np.random.default_rng(4))
    if name == "hostile":
        x = x[np.isfinite(x).all(axis=1)]      # np.quantile and NaN
    on_mesh(devices)
    on_device, on_host = RobustScaler(), RobustScaler()
    got = on_device.fit(device_table(x))
    want = on_host.fit(Table.from_columns(input=x.astype(np.float64)))
    assert on_device.last_execution_path == "select-device"
    assert on_host.last_execution_path == "host-quantiles"
    np.testing.assert_array_equal(got.medians, want.medians)
    np.testing.assert_array_equal(got.ranges, want.ranges)
    assert got.medians.dtype == np.float64 and got.medians.shape == (
        x.shape[1],)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", ["ragged", "arity-20", "long"])
def test_a_warm_fit_builds_nothing(name, devices, monkeypatch):
    on_mesh(devices)
    watch = Watch(monkeypatch, module=quantile, events=BUILDS)
    table = device_table(column(name, np.random.default_rng(5)))
    closed = lambda: metrics.group(ML_GROUP, "select").snapshot()[
        "counters"].get("finished", 0)
    first = RobustScaler().fit(table)
    before = closed()
    with watch():
        again = RobustScaler().fit(table)
    watch.armed = False
    # (the fit that was watched ran the finishing program too)
    assert name != "long" or closed() > before
    assert watch.jits == [] and watch.requests == 0
    # nothing is placed but the input (a ragged table is padded by a cached
    # program in place of a put): the ranks and the row count were placed
    # by the first fit and are held
    assert {span for span, _ in watch.puts} <= {"select.place_inputs"}
    assert len(watch.puts) <= 1
    np.testing.assert_array_equal(again.medians, first.medians)
    np.testing.assert_array_equal(again.ranges, first.ranges)


#: what a process's first device-path fit may build (today five ``jit``s:
#: the pass, the head, the step, the step with the ends, the finishing
#: program; head, step and finishing program lower to 173,000 + 47,000 +
#: 82,000 characters of text at d 7 (the head: three counting passes and
#: the finishing pass behind its branch; 61,000 with one pass and no
#: finishing pass, 153,000 with four); PR 35's one program was 166,000 and
#: its kernel's lowering took 1.6 s of every process's first fit)
FIRST_FIT_PROGRAMS = 5
FIRST_FIT_LOWERED_CHARS = 330_000

FIRST_FIT = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
made = []
real_jit = jax.jit
def jit(fn, *a, **k):
    out = real_jit(fn, *a, **k)
    made.append(out)
    return out
jax.jit = jit
import flink_ml_tpu
from flink_ml_tpu.common.table import Table
from flink_ml_tpu.models.feature.scalers import RobustScaler
from flink_ml_tpu.ops import quantile
from flink_ml_tpu.parallel import create_mesh
from flink_ml_tpu.parallel.mesh import set_default_mesh
mesh = create_mesh(devices=jax.devices()[:1])
set_default_mesh(mesh)
n, d = 4 * quantile.SAMPLE_ROWS + 3, 7
x = jnp.asarray(np.random.default_rng(0).random((n, d), dtype=np.float32))
before = len(made)
est = RobustScaler()
model = est.fit(Table.from_columns(input=x))
fit_made = made[before:]
head, step, _ = quantile.select_programs(mesh, 3)
finish = quantile.finish_program(mesh, 3)
spec = quantile._spec_on_mesh(mesh, n, (0.25, 0.5, 0.75))
state, _ = head(x, spec)
chars = (len(head.lower(x, spec).as_text())
         + len(step.lower(x, spec, state).as_text())
         + len(finish.lower(x, spec, state).as_text()))
from flink_ml_tpu.common.metrics import ML_GROUP, metrics
print(json.dumps({
    "finished": metrics.group(ML_GROUP, "select").snapshot()["counters"][
        "finished"],
    "path": est.last_execution_path,
    "pallas": "jax.experimental.pallas" in sys.modules,
    "jits": len(fit_made), "chars": chars,
    "exact": bool((model.medians == np.quantile(
        np.asarray(x), 0.5, axis=0, method="lower")).all())}))
"""


def test_a_process_s_first_fit_stays_inside_its_budget():
    """No clock: in a fresh process, one device-path fit of a table longer
    than its sample imports no Pallas, makes at most ``FIRST_FIT_PROGRAMS``
    ``jax.jit`` programs, and the programs it runs lower to at most
    ``FIRST_FIT_LOWERED_CHARS`` characters: a later change that doubles
    the build of a first fit is told here. The seconds are
    ``scripts/select_forms.py``'s, on the chip (PERF.md section 6)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", FIRST_FIT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    seen = json.loads(done.stdout.strip().splitlines()[-1])
    assert seen["path"] == "select-device" and seen["exact"]
    assert seen["finished"] > 0         # the finishing program ran in it
    assert seen["pallas"] is False
    assert 1 <= seen["jits"] <= FIRST_FIT_PROGRAMS
    assert 50_000 < seen["chars"] <= FIRST_FIT_LOWERED_CHARS


#: span -> how often under the root, on the device path (a launch and a
#: fetch a program: counted apart) and on the host's
TREE = {"select-device": {"select.place_inputs": 1, "select.build_program": 1,
                          "fit.model": 1},
        "host-quantiles": {"select.fetch": 1, "fit.model": 1}}


@pytest.mark.parametrize("devices,path", [(1, "select-device"),
                                          (4, "select-device"),
                                          (1, "host-quantiles")])
def test_the_spans_of_a_fit_are_one_tree_under_its_root(devices, path,
                                                        monkeypatch):
    on_mesh(devices)
    x = column("ragged", np.random.default_rng(6))
    table = (device_table(x) if path == "select-device"
             else Table.from_columns(input=x.astype(np.float64)))
    est = RobustScaler(lower=0.1, upper=0.9)
    est.fit(table)                          # warm, and nobody looking:
    assert len(tracer.recent) == 0          # nothing recorded
    groups = ("iteration", "select")
    before = {g: metrics.group(ML_GROUP, g).snapshot()["counters"]
              for g in groups}
    monkeypatch.setattr(tracer, "keep_recent", True)
    est.fit(table)
    assert est.last_execution_path == path
    records = list(tracer.recent)
    assert len({r["trace"] for r in records}) == 1
    root, = [r for r in records if r["parent"] is None]
    assert root["name"] == "RobustScaler.fit"
    assert root["attrs"]["kind"] == "fit"
    children = [r for r in records if r["parent"] == root["id"]]
    names = [r["name"] for r in children]
    assert sum(r["dur_us"] for r in children) <= root["dur_us"]
    fetches = [r for r in children if r["name"] == "select.fetch"]
    if path == "host-quantiles":
        assert {n: names.count(n) for n in set(names)} == TREE[path]
        assert fetches[0]["attrs"] == {
            "path": "host-quantiles", "rows": 1001, "d": 7,
            "probs": [0.1, 0.5, 0.9], "passes": 1}
        return
    launches = [r for r in children if r["name"] == "select.launch"]
    assert {n: names.count(n) for n in set(names)} == dict(
        TREE[path], **{"select.launch": len(launches),
                       "select.fetch": len(launches)})
    # a launch, then the read of its report, in turn
    assert [n for n in names if n.startswith("select.")][2:] == [
        "select.launch", "select.fetch"] * len(launches)
    assert launches[0]["attrs"] == {"path": "select-device", "rows": 1001,
                                    "d": 7, "probs": [0.1, 0.5, 0.9]}
    assert all(set(r["attrs"]) == {"ends", "finish"} for r in launches[1:])
    # every read says the passes it waited for; the read of a program
    # that made a finishing pass (the head may, behind its branch) also
    # what that closed and what it left open
    finishing = [bool(r["attrs"].get("finished", 0)
                      + r["attrs"].get("declined", 0)) for r in fetches]
    assert [set(r["attrs"]) for r in fetches] == [
        {"passes", "finished", "declined"} if f else {"passes"}
        for f in finishing]
    assert finishing[1:] == [r["attrs"]["finish"] for r in launches[1:]]
    made = [r["attrs"]["passes"] for r in fetches]
    # (over four devices a shard of these 1,001 rows is its own sample)
    assert made == [quantile.HEAD_PASSES + finishing[0] if devices == 1
                    else 1] + [1] * (len(fetches) - 1)
    after = {g: metrics.group(ML_GROUP, g).snapshot()["counters"]
             for g in groups}
    moved = {g: {k: v - before[g].get(k, 0) for k, v in after[g].items()}
             for g in groups}
    # one report a program, each a lone leaf under its own wait
    assert [moved["iteration"][name] for name in
            ("boundaryFetches", "boundaryWaits")] == [len(fetches)] * 2
    assert moved["select"]["passes"] == sum(made) <= 10
    for count in ("finished", "declined"):
        assert moved["select"][count] == sum(
            r["attrs"].get(count, 0) for r in fetches)
    assert moved["select"]["finished"] <= 3 * 7
    state = metrics.group(ML_GROUP, "update").snapshot()["gauges"]
    assert any("RobustScaler" in key and value == 3 * 7 * 4
               for key, value in state.items()), state
