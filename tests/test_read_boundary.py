"""``iteration.read_boundary``: a boundary's leaves cross to the host under
ONE wait. Given a tuple or list of two leaves or more, every device leaf's
copy is started before the first leaf is materialised (a blocking read of a few bytes costs the
same round trip whatever it carries, so a fit pays it once, not once a
leaf: PERF.md section 6, PR 34); the values are ``np.asarray``'s, leaf by
leaf; ``ml.iteration boundaryFetches`` counts the leaves and
``boundaryWaits`` the calls; the collective deadline's guard sees the whole
tree, once, first. One device and a four-device mesh; replicated,
row-sharded and scalar leaves, NumPy and Python ones, and the fused form's
single stacked vector.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu.common.metrics import ML_GROUP, metrics
from flink_ml_tpu.iteration.iteration import read_boundary
from flink_ml_tpu.parallel import create_mesh, elastic

DEVICES = [1, 4]


def put(devices: int, value, spec=P()):
    mesh = create_mesh(devices=jax.devices()[:devices])
    return jax.device_put(value, NamedSharding(mesh, spec))


#: kind of leaf -> how one is made on a mesh of ``devices``
LEAVES = {
    "replicated": lambda devices: put(
        devices, np.linspace(-1, 1, 6).astype(np.float32)),
    "row-sharded": lambda devices: put(
        devices, np.arange(24, dtype=np.int32).reshape(8, 3), P("data")),
    "scalar": lambda devices: put(devices, np.float32(2.5)),
    "bool-scalar": lambda devices: put(devices, np.bool_(True)),
    "bundle": lambda devices: put(devices, np.asarray([7, 1], np.int32)),
    "numpy": lambda devices: np.arange(3, dtype=np.int64),
    "numpy-scalar": lambda devices: np.float64(0.25),
    "python-int": lambda devices: 7,
    "python-float": lambda devices: 1.5,
    "python-bool": lambda devices: False,
}
DEVICE_KINDS = ["replicated", "row-sharded", "scalar", "bool-scalar",
                "bundle"]
#: trees a caller hands over: an SGD fit's final state with its boundary,
#: KMeans' centroids and counts, NaiveBayes' single leaves, mixed ones
TREES = {
    "sgd-plain-fit": ["bundle", "replicated", "scalar"],
    "sgd-unfused": ["scalar", "bool-scalar", "replicated", "scalar"],
    "pair": ["replicated", "scalar"],
    "single": ["row-sharded"],
    "mixed": ["numpy", "row-sharded", "python-int", "scalar",
              "python-float", "replicated", "python-bool", "numpy-scalar"],
    "host-only": ["numpy", "python-int"],
    "empty": [],
}


def counters():
    snap = metrics.group(ML_GROUP, "iteration").snapshot()["counters"]
    return (snap.get("boundaryFetches", 0), snap.get("boundaryWaits", 0))


class Recording:
    """A leaf that says when its copy is started and when it is read."""

    def __init__(self, leaf, index, log):
        self.leaf, self.index, self.log = leaf, index, log

    def copy_to_host_async(self):
        self.log.append(("start", self.index))
        self.leaf.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.index))
        return np.asarray(self.leaf, dtype)


@pytest.mark.parametrize("container", [tuple, list])
@pytest.mark.parametrize("tree", [t for t in TREES if t != "empty"])
@pytest.mark.parametrize("devices", DEVICES)
def test_every_copy_is_started_before_the_first_leaf_is_read(
        devices, tree, container):
    log = []
    leaves = [LEAVES[kind](devices) for kind in TREES[tree]]
    on_device = [i for i, leaf in enumerate(leaves)
                 if isinstance(leaf, jax.Array)]
    wrapped = container(
        Recording(leaf, i, log) if i in on_device else leaf
        for i, leaf in enumerate(leaves))
    vals = read_boundary(wrapped)
    # a lone leaf has nothing to share its wait with: it is only read
    started = on_device if len(leaves) > 1 else []
    assert log == ([("start", i) for i in started]
                   + [("read", i) for i in on_device])
    assert len(vals) == len(leaves)
    for got, leaf in zip(vals, leaves):
        np.testing.assert_array_equal(got, np.asarray(leaf))


@pytest.mark.parametrize("kind", DEVICE_KINDS)
@pytest.mark.parametrize("devices", DEVICES)
def test_a_device_leafs_own_copy_is_started(devices, kind, monkeypatch):
    """No stand-in: the array's own ``copy_to_host_async`` runs, before
    NumPy asks for the value."""
    leaf = LEAVES[kind](devices)
    log = []
    real = type(leaf).copy_to_host_async
    monkeypatch.setattr(
        type(leaf), "copy_to_host_async",
        lambda self: (log.append("start"), real(self))[1])
    monkeypatch.setattr(
        type(leaf), "__array__",
        lambda self, *a, _real=type(leaf).__array__, **k: (
            log.append("read"), _real(self, *a, **k))[1])
    other = LEAVES["scalar"](devices)
    read_boundary((leaf, other))
    assert log == ["start", "start", "read", "read"]


@pytest.mark.parametrize("kind", LEAVES)
@pytest.mark.parametrize("devices", DEVICES)
def test_values_and_dtypes_are_np_asarrays_leaf_by_leaf(devices, kind):
    leaf = LEAVES[kind](devices)
    want = np.asarray(leaf)
    for tree, at in (((leaf,), 0),
                     ([leaf, LEAVES["scalar"](devices)], 0),
                     ((LEAVES["numpy"](devices), leaf), 1)):
        got = read_boundary(tree)[at]
        assert isinstance(got, np.ndarray)
        assert (got.dtype, got.shape) == (want.dtype, want.shape)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tree", TREES)
@pytest.mark.parametrize("devices", DEVICES)
def test_leaves_are_counted_as_fetches_and_the_call_as_one_wait(devices,
                                                                tree):
    leaves = tuple(LEAVES[kind](devices) for kind in TREES[tree])
    fetches, waits = counters()
    vals = read_boundary(leaves)
    assert len(vals) == len(leaves)
    assert counters() == (fetches + len(leaves), waits + 1)


@pytest.mark.parametrize("devices", DEVICES)
def test_a_stacked_vector_reads_as_one_transfer_of_its_scalars(devices):
    """The fused segment boundary, handed over bare: one transfer, one
    wait, its numbers in order."""
    bundle = LEAVES["bundle"](devices)
    fetches, waits = counters()
    vals = read_boundary(bundle)
    assert counters() == (fetches + 1, waits + 1)
    assert isinstance(vals, list)
    assert [(int(v), v.dtype) for v in vals] == [(7, np.int32),
                                                 (1, np.int32)]


@pytest.mark.parametrize("tree", ["sgd-plain-fit", "mixed", "bare-vector"])
@pytest.mark.parametrize("devices", DEVICES)
def test_the_guard_sees_the_whole_tree_once_and_first(devices, tree,
                                                      monkeypatch):
    boundary = (LEAVES["bundle"](devices) if tree == "bare-vector"
                else tuple(LEAVES[kind](devices) for kind in TREES[tree]))
    seen = []
    started = []
    if tree != "bare-vector":
        boundary = tuple(
            Recording(leaf, i, started) if isinstance(leaf, jax.Array)
            else leaf for i, leaf in enumerate(boundary))

    def guard(given, what="boundary"):
        seen.append((given, what, list(started)))
        return given

    monkeypatch.setattr(elastic, "guard_fetch", guard)
    read_boundary(boundary)
    (given, what, started_then), = seen
    assert given is boundary and what == "segment boundary"
    assert started_then == []


@pytest.mark.parametrize("devices", DEVICES)
def test_under_the_collective_deadline_the_tree_is_awaited_whole(
        devices, monkeypatch):
    """Armed, the guard blocks on every leaf under its watchdog and hands
    the same tree back: the values are still the leaves'."""
    monkeypatch.setenv(elastic.COLLECTIVE_TIMEOUT_ENV, "30")
    leaves = tuple(LEAVES[kind](devices) for kind in TREES["mixed"])
    vals = read_boundary(leaves)
    for got, leaf in zip(vals, leaves):
        np.testing.assert_array_equal(got, np.asarray(leaf))
