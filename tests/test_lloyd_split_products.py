"""What the Lloyd and assign kernels' products add up to, computed: a
float32 operand goes to the MXU as three bfloat16 parts (``_split3``), the
distance takes the six largest part-products and the sums the three that
are not zero, and the answer is the float32 product — nearer a float64
product than the CPU's own float32 ``c @ x`` is. On the CPU a bfloat16
product accumulated in float32 is exact, so the parts' arithmetic is really
run here (interpret mode), and a form that drops a part shows: with the low
or the middle part zeroed the same checks fail ten times over.

The data is made to cancel, rows and centroids at ``1e3 (1 + 1e-2 noise)``:
``csq - 2 c.x`` takes differences of a few thousand from terms of 1e8.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.ops import pallas_kernels as pk
from flink_ml_tpu.parallel import create_mesh

SHAPES = [(10, 100), (4, 6), (40, 128), (64, 32)]
#: rows: 1,025 a device on four, and not whole tiles on one
N = 4100
EPS32 = 2.0 ** -24
#: the six-term product against a float64 one, relative (it reads
#: 3e-8..5e-8 here; the CPU's float32 product 1e-7..8e-7)
PRODUCT_BOUND = 1.5e-7
#: a chosen centroid may be farther than the nearest by four float32
#: roundings of the terms that cancel, and no more
REGRET_BOUND = 4 * EPS32
#: the sums against float64 sums of the same rows, relative
SUMS_BOUND = 1e-6

shapes = pytest.mark.parametrize("k,d", SHAPES, ids=lambda v: str(v))

_split3 = pk._split3


def cancelling(k, d, n=N, seed=1, one_sign=False):
    """``(x, c)`` float32 at ``1e3 (1 + 1e-2 noise)``. With ``one_sign``
    every middle and low part is made positive, so that what a dropped
    part leaves out adds up over a row and is not averaged away."""
    rng = np.random.default_rng(seed)
    x, c = ((1e3 * (1 + 1e-2 * rng.standard_normal(shape))).astype(
        np.float32) for shape in ((n, d), (k, d)))
    if one_sign:
        x, c = (np.asarray((hi + abs(mid)) + abs(lo), np.float32)
                for hi, mid, lo in (parts_f32(x), parts_f32(c)))
    return x, c


def parts_f32(v):
    return [np.asarray(p, np.float32) for p in _split3(jnp.asarray(v))]


def zeroing(part):
    """``_split3`` with its low, or its middle, part zeroed."""
    def split(v):
        parts = list(_split3(v))
        parts[part] = jnp.zeros_like(parts[part])
        return tuple(parts)
    return split


@pytest.fixture
def split_as(monkeypatch):
    """Swap ``_split3`` for the test; the kernels' jitted wrappers are
    traced anew under it and again after it."""
    def clear():
        pk._lloyd_tiles.clear_cache()
        pk._assign_tiles.clear_cache()

    def swap(split):
        monkeypatch.setattr(pk, "_split3", split)
        clear()
    yield swap
    monkeypatch.undo()
    clear()


def product_error(x, c):
    """``_cx`` against the float64 product, the largest relative gap."""
    want = c.astype(np.float64) @ x.astype(np.float64).T
    got = np.asarray(pk._cx(pk._split3(jnp.asarray(x.T)), jnp.asarray(c)),
                     np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def regret(x, c, chosen):
    """How much farther each row's chosen centroid is than its nearest, in
    float64, over the size of the terms ``csq - 2 c.x`` cancels."""
    x64, c64 = x.astype(np.float64), c.astype(np.float64)
    d2 = ((x64[:, None, :] - c64[None]) ** 2).sum(-1)
    scale = (c64 ** 2).sum(1).max() + 2 * (abs(x64) @ abs(c64).T).max(1)
    return (d2[np.arange(len(x)), chosen] - d2.min(1)) / scale


def sums_error(x, chosen, k, sums):
    """``sums`` against the float64 sums of the rows ``chosen`` names."""
    one_hot = (chosen[:, None] == np.arange(k)[None]).astype(np.float64)
    want = one_hot.T @ x.astype(np.float64)
    size = np.maximum(one_hot.T @ abs(x.astype(np.float64)), 1.0)
    return float(np.max(abs(sums - want) / size))


def kernel_round(x, c):
    """One device, the kernels called directly -> (chosen, sums, counts)."""
    chosen = np.asarray(pk.assign_nearest(x, c, interpret=True))
    packed = np.asarray(pk.lloyd_partial_sums(x, len(x), c, interpret=True),
                        np.float64)
    return chosen, packed[:, :-1], packed[:, -1]


# -- the split ---------------------------------------------------------------

def split_cases():
    rng = np.random.default_rng(0)
    wide = rng.standard_normal(4000) * 10.0 ** rng.integers(-30, 31, 4000)
    return {
        "random": rng.standard_normal(4000),
        "negative": -abs(rng.standard_normal(4000)) - 1e-3,
        "zero": np.array([0.0, -0.0, 1.0, -1.0, 0.5, 3.0]),
        "from-1e-30-to-1e30": wide,
        "the-table": rng.random(4000),
        "cancelling": 1e3 * (1 + 1e-2 * rng.standard_normal(4000)),
    }


@pytest.mark.parametrize("kind", list(split_cases()))
def test_split3_is_lossless_bit_for_bit(kind):
    v = split_cases()[kind].astype(np.float32)
    parts = _split3(jnp.asarray(v))
    assert [p.dtype for p in parts] == [jnp.bfloat16] * 3
    hi, mid, lo = (np.asarray(p, np.float32) for p in parts)
    np.testing.assert_array_equal((hi + mid) + lo, v)
    # each part is the nearest bfloat16 of what the parts before it left
    for part, left in ((hi, v), (mid, v - hi)):
        nearest = np.asarray(jnp.asarray(left).astype(jnp.bfloat16),
                             np.float32)
        assert (abs(left - part) <= abs(left - nearest)).all()


#: where ``_split3``'s own product ``65537 v`` overflows
SPLIT_RANGE = 2.0 ** 128 / 65537


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_split3_is_lossless_up_to_its_stated_range_and_not_past_it(sign):
    edge = np.float32(SPLIT_RANGE)
    under = sign * np.array([edge / 2, 0.999 * edge,
                             np.nextafter(edge, np.float32(0))], np.float32)
    hi, mid, lo = parts_f32(under)
    np.testing.assert_array_equal((hi + mid) + lo, under)
    # past it the parts are not numbers: the docstring's range is the range
    past = sign * np.array([1.001 * edge, 1e35], np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        assert not np.isfinite(parts_f32(past)[0]).any()


# -- the product -------------------------------------------------------------

@shapes
def test_the_six_part_products_are_the_float32_product(k, d):
    x, c = cancelling(k, d, n=3000, one_sign=True)
    assert product_error(x, c) <= PRODUCT_BOUND


@pytest.mark.parametrize("part", [2, 1], ids=["low", "middle"])
@shapes
def test_a_product_without_a_part_is_not(k, d, part, monkeypatch):
    x, c = cancelling(k, d, n=3000, one_sign=True)
    monkeypatch.setattr(pk, "_split3", zeroing(part))
    assert product_error(x, c) >= 10 * PRODUCT_BOUND


# -- one Lloyd round of the programs, one device and four --------------------

def programs(devices):
    mesh = create_mesh(devices=jax.devices()[:devices])
    rows = NamedSharding(mesh, P("data", None))
    whole = NamedSharding(mesh, P())
    return (rows, whole,
            km._build_assign_program(mesh, "euclidean", True),
            km._build_lloyd_program(mesh, "euclidean", 1, unroll=True,
                                    use_kernel=True))


@pytest.mark.parametrize("devices", [1, 4])
@shapes
def test_a_round_of_the_fit_against_float64(k, d, devices,
                                            interpreted_kernels):
    x, c = cancelling(k, d)
    rows, whole, assign, fit = programs(devices)
    xs = jax.device_put(x, rows)
    chosen = np.asarray(assign(xs, jax.device_put(c, whole)))
    # the nearest centroid, but where two tie to float32 rounding
    assert regret(x, c, chosen).max() <= REGRET_BOUND
    centroids, counts = fit(xs, *jax.device_put(
        (np.int32(N), c, np.zeros((k,), np.float32)), whole))
    # predict's assignment is the fit kernel's, row for row
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(chosen, minlength=k))
    counts = np.asarray(counts, np.float64)
    assert counts.sum() == N
    # a cluster that got no row keeps its centroid: it adds 0 here
    sums = np.asarray(centroids, np.float64) * counts[:, None]
    assert sums_error(x, chosen, k, sums) <= SUMS_BOUND


@pytest.mark.parametrize("part", [2, 1], ids=["low", "middle"])
@shapes
def test_a_round_without_a_part_fails_ten_times_over(k, d, part, split_as):
    x, c = cancelling(k, d, one_sign=True)
    chosen, sums, counts = kernel_round(x, c)
    whole = sums_error(x, chosen, k, sums)
    assert regret(x, c, chosen).max() <= REGRET_BOUND
    assert whole <= SUMS_BOUND
    np.testing.assert_array_equal(counts, np.bincount(chosen, minlength=k))
    split_as(zeroing(part))
    chosen, sums, counts = kernel_round(x, c)
    np.testing.assert_array_equal(counts, np.bincount(chosen, minlength=k))
    without = sums_error(x, chosen, k, sums)
    if part == 1:
        assert regret(x, c, chosen).max() >= 10 * REGRET_BOUND
        assert without >= 10 * SUMS_BOUND
    else:
        # a low part is at most 2^-17 of its value: over the sums' bound,
        # and ten times what the whole form leaves; the distances' share
        # of it is test_a_product_without_a_part_is_not's
        assert without > SUMS_BOUND and without >= 10 * whole


@shapes
def test_duplicate_centroids_give_the_first_index(k, d):
    x, c = cancelling(k, d, n=600)
    c[k - 1] = c[0]
    c[k // 2] = c[1]
    chosen, _, counts = kernel_round(x, c)
    assert counts[k - 1] == 0 and (chosen != k - 1).all()
    if k // 2 > 1:
        assert counts[k // 2] == 0
    assert counts.sum() == len(x)
    # and all equal: every row to centroid 0
    same = np.repeat(c[:1], k, axis=0)
    chosen, _, counts = kernel_round(x, same)
    assert (chosen == 0).all() and counts[0] == len(x)


# -- the gate ----------------------------------------------------------------

@pytest.mark.parametrize("k,d", SHAPES + [(2, 2), (200, 64), (500, 100),
                                          (1000, 64), (10, 1000)],
                         ids=lambda v: str(v))
def test_lloyd_tile_is_the_widest_counted_under_the_budget(k, d):
    tile = pk.lloyd_tile(k, d)
    assert tile in pk.TILES_N
    assert pk._lloyd_working_bytes(k, d, tile) <= pk.LLOYD_VMEM_BUDGET_BYTES
    wider = [t for t in pk.TILES_N if t > tile]
    assert all(pk._lloyd_working_bytes(k, d, t) > pk.LLOYD_VMEM_BUDGET_BYTES
               for t in wider)


def test_every_gated_shape_has_a_tile_under_the_budget():
    for k in (2, 3, 10, 64, 100, 256, 1000, 1536, 4096):
        for d in (1, 6, 32, 100, 128, 512, 1000, 2048, 4096):
            tile = pk.lloyd_tile(k, d)
            assert pk.lloyd_kernel_fits(k, d) == (tile > 0)
            if tile:
                assert pk._lloyd_working_bytes(
                    k, d, tile) <= pk.LLOYD_VMEM_BUDGET_BYTES
            elif d > 1:
                assert pk._lloyd_working_bytes(
                    k, d, pk.TILES_N[-1]) > pk.LLOYD_VMEM_BUDGET_BYTES
    assert pk.lloyd_tile(10, 100) == 4096      # the benchmark's shape
    assert pk.lloyd_tile(4096, 100) == 0       # k 4096 runs the XLA round
    # one feature too: its sums do not lower for the chip
    # (test_lloyd_gate_compiles.py)
    assert pk.lloyd_tile(10, 1) == 0 and pk.lloyd_tile(10, 2) == 4096
