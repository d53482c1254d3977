"""The Lloyd and assign kernels, compiled for the chip at the edges of their
gate. A kernel that ``lloyd_tile`` admits and Mosaic then refuses raises in
the fit that chose it (``pallas_supported``: no call site retries on the XLA
round), so what the gate admits has to compile: at every feature width here,
the largest k of every tile, where ``_lloyd_working_bytes`` comes nearest
its budget, and the smallest shapes. Ahead of time, for a v5e, by the TPU
compiler the installation brings: no chip is needed, and where there is no
such compiler the cases are skipped. What Mosaic itself counts at a shape is
``python scripts/lloyd_vmem_bisect.py k,d,tile``. The counting kernel of the
NaiveBayes fit (``category_counts``, gate ``counts_tile``) is held the same
way, and so are the selection programs of the RobustScaler fit (no kernel:
what is held is that, at the benchmark's 12M x 100, none keeps anything of
the table's size beside it), here and not in files of their own: one file,
one worker, one load of the TPU compiler.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flink_ml_tpu.ops import pallas_kernels as pk

#: feature widths: under a sublane tile, the benchmark's, whole lanes, wide
WIDTHS = (6, 100, 128, 512, 1000, 2048)


@functools.lru_cache(maxsize=None)
def four_chips():
    """The devices of a v5e host as the compiler sees them, or None."""
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception:  # noqa: BLE001 — no TPU compiler here
        return None


def chip():
    """One device of a v5e as the compiler sees it, or None."""
    return four_chips()[0] if four_chips() else None


def compile_both(k, d, rows):
    """Both kernels at ``(k, d)`` over ``rows`` rows, a ragged last tile."""
    one = SingleDeviceSharding(chip())

    def of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pk._lloyd_tiles.lower(of((rows, d)), of((), jnp.int32),
                          of((k, d))).compile()
    pk._assign_tiles.lower(of((rows, d)), of((k, d))).compile()


def edges(d):
    """``[(largest k the gate gives this tile, tile)]`` at width ``d``."""
    tiles = {}
    for k in range(1, 4097):
        tiles[pk.lloyd_tile(k, d)] = k
    return [(k, tile) for tile, k in tiles.items() if tile]


@pytest.mark.parametrize("d", WIDTHS)
def test_the_largest_k_of_every_tile_compiles_for_the_chip(d):
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    found = edges(d)
    assert found and {tile for _, tile in found} <= set(pk.TILES_N)
    for k, tile in found:
        assert pk.lloyd_tile(k + 16, d) < tile    # an edge: the next k has
        compile_both(k, d, 2 * tile + 77)         # a narrower tile, or none


@pytest.mark.parametrize("k,d", [(1, 2), (2, 2), (3, 3), (10, 7), (17, 9),
                                 (10, 100), (50, 100), (129, 100)],
                         ids=lambda v: str(v))
def test_small_and_unaligned_shapes_compile_for_the_chip(k, d):
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    assert pk.lloyd_tile(k, d)
    compile_both(k, d, 20_000)


def test_one_feature_is_gated_off_because_its_sums_do_not_lower():
    """Why ``lloyd_tile(k, 1)`` is 0: when this stops raising, the gate's
    ``d < 2`` can go."""
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    assert pk.lloyd_tile(10, 1) == 0
    with pytest.raises(Exception, match="vector.broadcast|verif"):
        compile_both(10, 1, 20_000)


# -- the counting kernel (NaiveBayes fit) --------------------------------------

def compile_counts(d, labels, values, rows):
    one = SingleDeviceSharding(chip())

    def of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pk._counts_tiles.lower(of((rows, d)), of((rows,)), of((), jnp.int32),
                           labels=labels, values=values).compile()


def count_edges(d, labels):
    """``[(largest V the gate gives this tile, tile)]`` at ``(d, L)``."""
    tiles = {}
    for values in range(1, 4097):
        tiles[pk.counts_tile(d, labels, values)] = values
    return [(values, tile) for tile, values in tiles.items() if tile]


@pytest.mark.parametrize("d,labels", [(6, 3), (100, 10), (100, 300),
                                      (512, 10), (1000, 2)],
                         ids=lambda v: str(v))
def test_the_most_values_of_every_counting_tile_compile_for_the_chip(
        d, labels):
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    found = count_edges(d, labels)
    assert found and {tile for _, tile in found} <= set(pk.COUNTS_TILES_N)
    for values, tile in found:
        compile_counts(d, labels, values, 2 * tile + 77)


@pytest.mark.parametrize("d,labels,values", [
    (2, 1, 1), (2, 2, 2), (7, 3, 41), (100, 10, 20), (100, 10, 33),
    (100, 10, 300), (128, 16, 64), (100, 100, 20)], ids=lambda v: str(v))
def test_small_and_unaligned_counting_shapes_compile_for_the_chip(
        d, labels, values):
    """Odd and even arities, one value, one label, the benchmark's shape,
    and an arity past ``COUNTS_UNROLL_MAX`` pairs (a loop, not unrolled)."""
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    assert pk.counts_tile(d, labels, values)
    compile_counts(d, labels, values, 20_000)


# -- the selection programs of the order statistics (RobustScaler fit) ---------

@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("program", ["head", "step", "step_ends", "finish"])
def test_a_selection_program_keeps_nothing_of_the_table_s_size_on_the_chip(
        program, chips):
    """Each program of a fit at the RobustScaler cell's shape, 12M x 100 on
    a v5e, and at four times the rows over four: its temporaries do not
    grow with the table (the 32-round program they replaced kept a 4.992 GB
    key image; the same passes inside a ``while_loop`` or behind a ``cond``
    make XLA copy the table row-major, 6.1 GB), its one large argument is
    the table where it lies, and its text holds no array of the table's
    shape but the table. The finishing pass (its own program, and the
    head's last step behind a branch) loops over slices of the table: any
    way of writing it as a reduction within blocks of rows made XLA write
    the keys out first, 4.6-11.1 GB (PERF.md section 6, PR 37)."""
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.ops import quantile
    from flink_ml_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(devices=four_chips()[:chips])

    def of(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    local, d, m = 12_000_000, 100, 3
    operands = [of((local * chips, d), jnp.float32, P("data", None)),
                of((m + 1,), jnp.int32)]
    if program != "head":
        operands.append(of((5 + quantile.PIVOTS, m, d), jnp.uint32))
    quantile.select_programs.cache_clear()
    quantile.finish_program.cache_clear()
    try:
        built = dict(zip(("head", "step", "step_ends"),
                         quantile.select_programs(mesh, m)),
                     finish=quantile.finish_program(mesh, m))[program]
        compiled = built.lower(*operands).compile()
    finally:
        quantile.select_programs.cache_clear()
        quantile.finish_program.cache_clear()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(4.992e9, rel=1e-3)
    assert memory.temp_size_in_bytes < 0.02e9
    text = compiled.as_text()
    assert "custom_call_target=\"tpu_custom_call\"" not in text
    if program == "finish":
        # nothing of the table's shape is made: not its keys, not a mask
        made = set(re.findall(rf"([a-z0-9]+)\[(?:{local},{d}|{d},{local})\]",
                              text))
        assert made == {"f32"}, made
        assert " while(" in text


# -- the grouped moments of the ANOVA F-test (UnivariateFeatureSelector fit) ---

def moment_edges(d):
    """``[(most labels the gate gives this tile, tile)]`` at width ``d``."""
    tiles = {}
    for labels in range(1, 257):
        tiles[pk.moments_tile(d, labels)] = labels
    return [(labels, tile) for tile, labels in tiles.items() if tile]


@pytest.mark.parametrize("d", [2, 6, 100, 128, 256], ids=lambda v: str(v))
def test_the_most_labels_of_every_moments_tile_compile_for_the_chip(d):
    """What ``moments_tile`` admits Mosaic has to take: the most labels of
    every tile at this width, over a ragged last tile (at five hundred
    features no tile of 1024 rows fits and the XLA form runs)."""
    assert not pk.moments_kernel_fits(512, 10)
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    one = SingleDeviceSharding(chip())

    def of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    found = moment_edges(d)
    assert found and {tile for _, tile in found} <= set(pk.MOMENTS_TILES_N)
    for labels, tile in found:
        rows = 2 * tile + 77
        pk._moments_tiles.lower(of((rows, d)), of((rows,)),
                                of((), jnp.int32), of((d,)), of((d,)),
                                labels=labels).compile()


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("program", ["look", "pallas", "xla"])
def test_a_moments_program_keeps_nothing_of_the_table_s_size_on_the_chip(
        program, chips):
    """Each program of a selector fit at the ANOVA cell's shape, 12M x 100
    with ten classes on a v5e, and the same table over four: no ``(n, L)``
    one-hot operand, no ``(n, d)`` intermediate, no copy of the label column
    (a ``(1, n)`` row of it costs the counting kernel 0.048 GB: the moments
    kernel reads it as it lies), so the temporaries do not grow with the
    table: the parent's two ``columnar.apply_multi`` calls kept an ``(n,
    c)`` one-hot and an ``(n, d)`` gather of the class means."""
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.ops import stats
    from flink_ml_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(devices=four_chips()[:chips])

    def of(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    n, d, labels = 12_000_000, 100, 10
    table = [of((n, d), jnp.float32, P("data", None)),
             of((n,), jnp.float32, P("data")), of((), jnp.int32)]
    stats.moments_look_program.cache_clear()
    stats.moments_program.cache_clear()
    try:
        if program == "look":
            compiled = stats.moments_look_program(mesh).lower(
                *table).compile()
        else:
            assert pk.moments_kernel_fits(d, labels)
            compiled = stats.moments_program(
                mesh, labels, program == "pallas").lower(
                    *table, of((d,), jnp.float32),
                    of((d,), jnp.float32)).compile()
    finally:
        stats.moments_look_program.cache_clear()
        stats.moments_program.cache_clear()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(
        5.040e9 / chips, rel=1e-3)
    assert memory.temp_size_in_bytes < 0.02e9
    assert ("custom_call_target=\"tpu_custom_call\"" in compiled.as_text()
            ) == (program == "pallas")


# -- a round of the SGD fit (LogisticRegression and its kin) -------------------

def computations(text):
    """``{name: [instruction lines]}`` of a compiled module's text."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            found[name] = []
        elif line == "}":
            name = None
        elif name:
            found[name].append(line.strip())
    return found


@pytest.mark.parametrize("chips", [1, 4])
def test_a_round_reads_its_batch_from_the_table_once_on_the_chip(chips):
    """The plain fit's program at the LR cells' shapes, 12M x 100 a chip and
    a batch of 100,000 rows (25,000 a task over four): the round's window
    is made once, in on-chip memory (space 1), and it is the one fusion in
    the loop's body that reads the table; both products read the window.
    The same pieces with a loop around the batch make XLA carry the table
    row-major through it, a 6.14 GB copy (PERF.md section 6, PR 41): the
    temporaries stay under 0.02 GB."""
    if chip() is None:
        pytest.skip("no TPU compiler in this installation")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.ops import optimizer
    from flink_ml_tpu.ops.losses import BinaryLogisticLoss
    from flink_ml_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(devices=four_chips()[:chips])

    def of(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    local, d, batch = 12_000_000, 100, 100_000
    prm = optimizer.SGDParams(learning_rate=0.1, global_batch_size=batch,
                              max_iter=20, tol=1e-6)
    optimizer._build_sgd_segment_program.cache_clear()
    try:
        compiled = optimizer._build_sgd_segment_program(
            BinaryLogisticLoss, mesh, prm, fused=True, weighted=False,
            fresh=True).lower(of((local * chips, d), P("data")),
                              of((local * chips,), P("data")), None,
                              of((d,), P())).compile()
    finally:
        optimizer._build_sgd_segment_program.cache_clear()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9
    text = compiled.as_text()
    body = computations(text)[re.search(r"body=%([^,\s]+)", text).group(1)]
    table = {m.group(1) for line in body for m in [re.match(
        rf"%(\S+) = f32\[{local},{d}\]\{{[^}}]*\}} get-tuple-element",
        line)] if m}
    readers = [line for line in body if " fusion(" in line and any(
        re.search(rf"%{re.escape(name)}[,)]", line) for name in table)]
    assert len(table) == 1 and len(readers) == 1, readers
    window = re.match(rf"%(\S+) = f32\[{d},{batch // chips}\]\{{([^}}]*)\}}",
                      readers[0])
    assert window and "S(1)" in window.group(2), readers[0]
    products = [line for line in body if " fusion(" in line
                and "dot_general" in line]
    assert len(products) == 2 and all(
        re.search(rf"%{re.escape(window.group(1))}[,)]", line)
        for line in products), products
