"""What the suite can hold of the Lloyd programs without a chip, from the
traced programs themselves: every matrix product of an XLA program is
float32 at ``HIGHEST`` precision; inside the two kernels every product
multiplies bfloat16 parts that ``_split3`` made of a float32 operand (or
the one-hot, whose 0 and 1 are whole in bfloat16) and accumulates in
float32, the distance holding the six part-products of the float32 product
and the sums the three that are not zero, and no other bfloat16 value
exists in any program; a fit at a ragged row count neither pads the table
nor hands the kernel a per-row mask. What the parts add up to is computed,
on the CPU, in ``tests/test_lloyd_split_products.py``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.parallel import create_mesh

N, D, K, ROUNDS = 4100, 100, 10, 10


def eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (``pjit``, ``shard_map``, ``while``, a ``pallas_call``'s kernel)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                if isinstance(inner, jex_core.ClosedJaxpr):
                    inner = inner.jaxpr
                if isinstance(inner, jex_core.Jaxpr):
                    yield from eqns(inner)


def mesh_of(devices):
    return create_mesh(devices=jax.devices()[:devices])


def fit_args(n=N):
    return (jax.ShapeDtypeStruct((n, D), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((K, D), jnp.float32),
            jax.ShapeDtypeStruct((K,), jnp.float32))


def traced_programs(mesh):
    """name -> the traced program of every Lloyd fit and predict path."""
    x, n_valid, c, counts = fit_args()
    bound = jax.ShapeDtypeStruct((), jnp.int32)
    out = {}
    for use_kernel in (False, True):
        tier = "pallas" if use_kernel else "xla"
        for unroll in (False, True):
            out[f"{tier}-lloyd/{'unrolled' if unroll else 'while'}"] = (
                jax.make_jaxpr(km._build_lloyd_program(
                    mesh, "euclidean", ROUNDS, unroll=unroll,
                    use_kernel=use_kernel))(x, n_valid, c, counts))
        out[f"{tier}-lloyd-segments"] = jax.make_jaxpr(
            km._build_lloyd_segment_program(
                mesh, "euclidean", use_kernel=use_kernel))(
                    x, n_valid, c, counts, bound, bound)
        out[f"{tier}-assign"] = jax.make_jaxpr(km._build_assign_program(
            mesh, "euclidean", use_kernel))(x, c)
    out["host-rounds"] = jax.make_jaxpr(km._build_lloyd_round_program(
        mesh, "euclidean"))(x, n_valid, c)
    out["xla-lloyd/cosine"] = jax.make_jaxpr(km._build_lloyd_program(
        mesh, "cosine", ROUNDS))(x, n_valid, c, counts)
    out["xla-assign/cosine"] = jax.make_jaxpr(km._build_assign_program(
        mesh, "cosine"))(x, c)
    return out


PROGRAMS = ["xla-lloyd/while", "xla-lloyd/unrolled", "pallas-lloyd/while",
            "pallas-lloyd/unrolled", "xla-lloyd-segments",
            "pallas-lloyd-segments", "host-rounds", "xla-assign",
            "pallas-assign", "xla-lloyd/cosine", "xla-assign/cosine"]


@pytest.fixture(scope="module")
def programs():
    km._build_lloyd_program.cache_clear()
    km._build_lloyd_segment_program.cache_clear()
    km._build_assign_program.cache_clear()
    try:
        yield {devices: traced_programs(mesh_of(devices))
               for devices in (1, 4)}
    finally:
        km._build_lloyd_program.cache_clear()
        km._build_lloyd_segment_program.cache_clear()
        km._build_assign_program.cache_clear()


def kernel_eqns(jaxpr):
    """``(equations outside any kernel, [each kernel's equations])``."""
    kernels = [list(eqns(eqn.params["jaxpr"])) for eqn in eqns(jaxpr)
               if eqn.primitive.name == "pallas_call"]
    inside = {id(e) for kernel in kernels for e in kernel}
    outside = [e for e in eqns(jaxpr) if id(e) not in inside]
    return outside, kernels


def has_bfloat16(eqn):
    return any(getattr(v.aval, "dtype", None) == jnp.bfloat16
               for v in (*eqn.invars, *eqn.outvars))


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", PROGRAMS)
def test_every_product_is_float32_at_highest_precision(name, devices,
                                                       programs):
    """Outside the kernels: float32 operands at ``HIGHEST`` and no
    bfloat16 value anywhere. Inside: see ``assert_split_products``."""
    outside, kernels = kernel_eqns(programs[devices][name].jaxpr)
    assert bool(kernels) == name.startswith("pallas")
    dots = [e for e in outside if e.primitive.name == "dot_general"]
    assert dots or kernels, "a Lloyd round multiplies: distances, the sums"
    highest = jax.lax.Precision.HIGHEST
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [jnp.float32] * 2
        assert dot.params["precision"] in (highest, (highest, highest)), (
            name, dot.params["precision"])
        assert dot.params["preferred_element_type"] in (None, jnp.float32)
    assert not [e for e in outside if has_bfloat16(e)]
    for kernel in kernels:
        assert_split_products(kernel, sums="lloyd" in name)


def assert_split_products(kernel, sums: bool):
    """One kernel's products: bfloat16 parts in, float32 out. The distance
    is three products whose left operands are the centroids' parts stacked
    ``3k``, ``2k`` and ``k`` rows deep against the tile's high, middle and
    low part: six ``(k, tile)`` part-products. The sums are three, the
    one-hot against each part of the tile. Every bfloat16 value is a part
    (a float32 difference converted: ``_split3`` converts nothing else),
    the one-hot (a boolean converted) or parts stacked."""
    dots = [e for e in kernel if e.primitive.name == "dot_general"]
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [jnp.bfloat16] * 2
        assert dot.params["preferred_element_type"] == jnp.float32
    producer = {id(v): e for e in kernel for v in e.outvars}
    distance = [e for e in dots
                if e.params["dimension_numbers"][0] == ((1,), (0,))]
    summed = [e for e in dots if e not in distance]
    assert [e.invars[0].aval.shape[0] for e in distance] == [
        3 * K, 2 * K, K]
    x_parts = [e.invars[1] for e in distance]
    assert len({id(v) for v in x_parts}) == 3
    assert {v.aval.shape[0] for v in x_parts} == {D}
    if sums:
        # low, middle, high: each part of the same split, the one one-hot
        assert [id(e.invars[1]) for e in summed] == [
            id(v) for v in reversed(x_parts)]
        one_hot, = {id(e.invars[0]): e.invars[0] for e in summed}.values()
        made = producer[id(one_hot)]
        assert made.primitive.name == "convert_element_type"
        assert made.invars[0].aval.dtype == jnp.bool_
    else:
        assert not summed
    converts = [e for e in kernel if has_bfloat16(e)
                and e.primitive.name == "convert_element_type"]
    parts = [e for e in converts if e.invars[0].aval.dtype == jnp.float32]
    # two operands split (the tile, the centroids), three parts each
    assert len(parts) == 6 and len(converts) == 6 + sums
    for part in parts:
        assert producer[id(part.invars[0])].primitive.name == "sub"
    for eqn in kernel:
        if has_bfloat16(eqn):
            assert eqn.primitive.name in (
                "convert_element_type", "concatenate", "dot_general"), eqn


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name", [p for p in PROGRAMS if "lloyd" in p])
def test_a_ragged_fit_copies_no_table_and_masks_in_the_kernel(
        name, devices, programs):
    """4,100 rows (1,025 a device on four) are not whole kernel tiles: the
    program holds no ``pad`` of a row-sized operand, and the kernel's
    operands are the table (as ``(d, n)``), the centroids and one scalar —
    no ``(n, 1)`` or ``(n,)`` mask."""
    local = N // devices
    all_eqns = list(eqns(programs[devices][name].jaxpr))
    for eqn in all_eqns:
        if eqn.primitive.name == "pad":
            assert all(local not in v.aval.shape and N not in v.aval.shape
                       for v in eqn.invars), eqn
    kernels = [e for e in all_eqns if e.primitive.name == "pallas_call"]
    assert bool(kernels) == name.startswith("pallas")
    for kernel in kernels:
        shapes = sorted(v.aval.shape for v in kernel.invars)
        assert shapes == sorted([(1,), (D, local), (K, D)]), shapes


@pytest.mark.parametrize("devices", [1, 4])
def test_the_initial_rows_leave_the_column_without_a_gather(devices):
    """A gather on the resident column makes XLA copy the whole column to
    a row-major layout first (6.1 GB at 12M x 100): the k rows are k
    dynamic slices, and the shards' contributions one sum."""
    program = jax.make_jaxpr(km._build_init_rows_program(
        mesh_of(devices), K))(jax.ShapeDtypeStruct((N, D), jnp.float32),
                              jax.ShapeDtypeStruct((K,), jnp.int32))
    names = [e.primitive.name for e in eqns(program.jaxpr)]
    assert "gather" not in names and "pad" not in names
    assert names.count("dynamic_slice") == K
