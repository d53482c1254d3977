"""Elastic multi-process training (parallel/elastic.py): worker-loss
detection, supervised relaunch with cross-N slice re-placement, and
straggler-aware partial-participation rounds.

Pins the ISSUE 17 contracts: WorkerLost is retryable and names the dead
process; the elastic budget surfaces as RestartsExhausted with
``budget="elastic"``; ``repad_leading`` trims/extends ONLY inert dim-0
zero padding (a nonzero tail is CorruptCheckpoint); ``renormalized_sum``
is bit-identical to the plain reduce at full participation and unbiased
at partial; RoundParticipation drops only deadline'd shards, never all,
and force-readmits after ``max_staleness``; ``launch(child_grace_s=)``
reports a crashed child without waiting out a wedged sibling; and
sharded-adam checkpoints re-place bit-exactly across a CHANGED mesh
size (N=4 -> N=2 and N=2 -> N=1) through the v2 manifest.
"""

import os
import shutil
import sys
import time

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.iteration.checkpoint import (CorruptCheckpoint,
                                               repad_leading)
from flink_ml_tpu.iteration.iteration import IterationConfig
from flink_ml_tpu.parallel import (
    DATA_AXIS,
    create_mesh,
    distributed as dist,
    elastic,
    mapreduce as mr,
    update_sharding as upd,
)
from flink_ml_tpu.resilience import (InjectedFault, RestartsExhausted,
                                     RetryPolicy, WorkerLost, faults)


def submesh(n):
    return create_mesh(devices=jax.devices()[:n])


@pytest.fixture(autouse=True)
def _clean_elastic_stats():
    elastic.reset_stats()
    yield
    elastic.reset_stats()


# -- failure classes ----------------------------------------------------------

def test_worker_lost_is_retryable():
    assert RetryPolicy().classify(WorkerLost(1, "gone")) == "retryable"


def test_worker_lost_names_the_process():
    e = WorkerLost(3, "collective deadline exceeded", timeout_s=20.0)
    assert "process 3" in str(e) and "20" in str(e)
    assert e.process_index == 3 and e.timeout_s == 20.0
    anon = WorkerLost(None, "x")
    assert "unidentified" in str(anon) and anon.process_index is None


def test_restarts_exhausted_names_elastic_budget():
    e = RestartsExhausted(2, "elastic budget exhausted: lost process 1",
                          budget="elastic")
    assert e.budget == "elastic" and "elastic budget" in str(e)
    # default stays the supervisor's restart budget (back-compat)
    assert RestartsExhausted(1, "x").budget == "restart"


# -- repad_leading (the cross-N re-placement primitive) -----------------------

def test_repad_noop_and_extend_and_trim():
    a = np.arange(10, dtype=np.float32)
    assert repad_leading(a, (10,)) is a
    grown = repad_leading(a, (12,))
    assert grown.shape == (12,)
    np.testing.assert_array_equal(grown[:10], a)
    assert not grown[10:].any()
    padded = np.concatenate([a, np.zeros(2, np.float32)])
    np.testing.assert_array_equal(repad_leading(padded, (10,)), a)


def test_repad_2d_trims_rows():
    m = np.zeros((6, 3))
    m[:4] = np.arange(12).reshape(4, 3)
    np.testing.assert_array_equal(repad_leading(m, (4, 3)), m[:4])


def test_repad_nonzero_tail_is_corrupt():
    a = np.arange(12, dtype=np.float32) + 1.0  # tail is NOT padding
    with pytest.raises(CorruptCheckpoint, match="nonzero"):
        repad_leading(a, (10,))


def test_repad_rejects_non_dim0_mismatch():
    with pytest.raises(CorruptCheckpoint):
        repad_leading(np.zeros((4, 3)), (4, 5))
    with pytest.raises(CorruptCheckpoint):
        repad_leading(np.float64(3.0), (2,))


def test_rescale_uniform_integer_progress():
    """The fit carry's per-shard ``offsets``: global progress is
    ``offset * n_old``, re-sharded as ``/ n_new`` (4 shards at offset
    40 = row 160 = 2 shards at offset 80)."""
    off = np.full(4, 40, dtype=np.int32)
    down = elastic.repad_or_rescale(off, (2,))
    assert down.tolist() == [80, 80] and down.dtype == np.int32
    up = elastic.repad_or_rescale(np.full(2, 80, np.int32), (4,))
    assert up.tolist() == [40, 40, 40, 40]
    same = elastic.repad_or_rescale(off, (4,))
    assert same is off


def test_rescale_rejects_bad_progress():
    with pytest.raises(CorruptCheckpoint, match="not uniform"):
        elastic.repad_or_rescale(np.array([40, 41], np.int32), (4,))
    with pytest.raises(CorruptCheckpoint, match="divide"):
        elastic.repad_or_rescale(np.full(4, 40, np.int32), (3,))
    # float leaves keep the zero-pad semantics even at 1-D
    with pytest.raises(CorruptCheckpoint, match="nonzero"):
        elastic.repad_or_rescale(np.full(4, 40.0), (2,))


# -- renormalized_sum ---------------------------------------------------------

def test_renormalized_full_participation_bit_identical(mesh8):
    parts = np.arange(16, dtype=np.float64).reshape(8, 2) + 1.0
    renorm = mr.map_shards(
        lambda a, inc: mr.renormalized_sum(a[0], inc[0]), mesh8,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)), out_specs=P())
    plain = mr.map_shards(lambda a: mr.reduce_sum(a[0]), mesh8,
                          in_specs=P(DATA_AXIS, None), out_specs=P())
    got = np.asarray(renorm(parts, np.ones(8)))
    assert np.array_equal(got, np.asarray(plain(parts)))


def test_renormalized_partial_is_unbiased(mesh8):
    parts = np.arange(16, dtype=np.float64).reshape(8, 2) + 1.0
    include = np.array([1.0, 1, 0, 1, 1, 0, 1, 1])
    prog = mr.map_shards(
        lambda a, inc: mr.renormalized_sum(a[0], inc[0]), mesh8,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)), out_specs=P())
    got = np.asarray(prog(parts, include))
    expected = (parts * include[:, None]).sum(0) * 8.0 / 6.0
    np.testing.assert_allclose(got, expected, rtol=0, atol=1e-9)


# -- RoundParticipation -------------------------------------------------------

def test_participation_full_when_unarmed(monkeypatch):
    monkeypatch.delenv(elastic.ROUND_DEADLINE_ENV, raising=False)
    rp = elastic.RoundParticipation(4)
    rp.observe([1.0, 2.0, 900.0, 3.0])
    assert rp.decide(0).tolist() == [1.0] * 4  # no deadline, no drops
    assert rp.participation_min == 1.0


def test_participation_drop_staleness_readmit():
    rp = elastic.RoundParticipation(4, deadline_ms=100.0, max_staleness=2)
    masks = []
    timings = [[10, 11, 12, 13], [10, 11, 180, 13], [10, 11, 180, 13],
               [10, 11, 180, 13]]
    for rnd, t in enumerate(timings):
        masks.append(rp.decide(rnd).tolist())
        rp.observe(t)
    masks.append(rp.decide(len(timings)).tolist())
    assert masks == [
        [1, 1, 1, 1],   # nothing observed yet
        [1, 1, 1, 1],   # all fast
        [1, 1, 0, 1],   # shard 2 dropped (stale=1)
        [1, 1, 0, 1],   # shard 2 dropped (stale=2 = max)
        [1, 1, 1, 1],   # force-readmitted
    ]
    assert rp.dropped_rounds == 2 and rp.participation_min == 0.75
    assert elastic.provenance()["participationMin"] == 0.75
    assert elastic.provenance()["elasticEvents"] == 2


def test_participation_never_drops_every_shard():
    rp = elastic.RoundParticipation(3, deadline_ms=50.0)
    rp.observe([900.0, 900.0, 900.0])
    assert rp.decide(1).tolist() == [1.0, 1.0, 1.0]


def test_participation_observe_validates_shape():
    rp = elastic.RoundParticipation(4, deadline_ms=50.0)
    with pytest.raises(ValueError, match="4 per-shard"):
        rp.observe([1.0, 2.0])


# -- detection: heartbeats + the collective watchdog --------------------------

def test_beat_and_stale_processes(monkeypatch, tmp_path):
    import glob
    import json

    monkeypatch.setenv(elastic.HEARTBEAT_DIR_ENV, str(tmp_path))
    elastic.beat(epoch=3)
    # a heartbeat IS a fleet beacon now (observability/fleet.py) — the
    # elastic watchdog and `mltrace fleet` read the same stamp
    beacons = glob.glob(str(tmp_path / "fleet-*.json"))
    assert len(beacons) == 1
    raw = json.loads(open(beacons[0]).read())
    assert raw["epoch"] == 3 and raw["role"] == "trainer"
    # processes 1 and 2 never beat; 0 is fresh
    assert elastic.stale_processes(30.0, num_processes=3) == [1, 2]
    raw["time"] = time.time() - 120.0
    with open(beacons[0], "w") as f:
        json.dump(raw, f)
    assert elastic.stale_processes(30.0, num_processes=3) == [0, 1, 2]


def test_stale_processes_empty_without_heartbeat_dir(monkeypatch):
    monkeypatch.delenv(elastic.HEARTBEAT_DIR_ENV, raising=False)
    assert elastic.stale_processes(1.0, num_processes=4) == []


def test_guard_fetch_noop_without_deadline(monkeypatch):
    monkeypatch.delenv(elastic.COLLECTIVE_TIMEOUT_ENV, raising=False)
    tree = {"a": np.ones(3)}
    assert elastic.guard_fetch(tree) is tree


def test_wait_with_deadline_passes_fast_tree():
    tree = {"a": jax.numpy.ones(3)}
    assert elastic.wait_with_deadline(tree, 10.0) is tree


def test_wait_with_deadline_raises_worker_lost(monkeypatch, tmp_path):
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda t: time.sleep(2.0))
    # a 3-process world where process 2's heartbeat went stale (0 and 1
    # get future mtimes so the sub-second test deadline can't age them
    # out mid-wait; real deadlines are tens of seconds)
    monkeypatch.setenv("FLINK_ML_TPU_NUM_PROCESSES", "3")
    monkeypatch.setenv(elastic.HEARTBEAT_DIR_ENV, str(tmp_path))
    import json

    now = time.time()
    # beats are fleet beacons keyed by processIndex; 0 and 1 get future
    # stamps so the sub-second test deadline can't age them out
    # mid-wait (a future stamp clamps to age 0 — clock-skew rule);
    # real deadlines are tens of seconds
    for k, stamp in ((0, now + 30.0), (1, now + 30.0),
                     (2, now - 120.0)):
        (tmp_path / f"fleet-p{k}-{1000 + k}.json").write_text(
            json.dumps({"schema": 1, "time": stamp, "pid": 1000 + k,
                        "process": k, "processIndex": k}))
    with pytest.raises(WorkerLost, match="process 2") as ei:
        elastic.wait_with_deadline({"x": 1}, 0.2, what="segment")
    assert ei.value.process_index == 2
    assert elastic.provenance()["elasticEvents"] == 1


def test_wait_with_deadline_reraises_worker_errors(monkeypatch):
    def boom(tree):
        raise ValueError("device melted")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    with pytest.raises(ValueError, match="melted"):
        elastic.wait_with_deadline({"x": 1}, 5.0)


# -- launcher liveness + elastic relaunch -------------------------------------

CRASH_THEN_WEDGE = """
import os, sys, time
pid = int(os.environ["FLINK_ML_TPU_PROCESS_ID"])
if pid == 0:
    sys.exit(1)
time.sleep(60)
"""


def test_launch_child_grace_reports_crash_early():
    t0 = time.monotonic()
    records = dist.launch([sys.executable, "-c", CRASH_THEN_WEDGE], 2,
                          timeout=120.0, child_grace_s=1.5)
    assert time.monotonic() - t0 < 30.0  # not held to the full timeout
    assert records[0]["returncode"] == 1
    assert records[0]["exitOrder"] == 0  # the crash was seen first
    assert records[1]["returncode"] < 0  # the wedged sibling was killed


ELASTIC_CHILD = """
import os, sys, time, signal
att = int(os.environ.get("FLINK_ML_TPU_ELASTIC_ATTEMPT", "0"))
pid = int(os.environ.get("FLINK_ML_TPU_PROCESS_ID", "0"))
if att == 0 and pid == 1:
    os.kill(os.getpid(), signal.SIGKILL)
if att == 0:
    time.sleep(60)
"""


def test_run_elastic_shrinks_and_recovers():
    records = elastic.run_elastic(
        [sys.executable, "-c", ELASTIC_CHILD], num_processes=3,
        min_processes=2, policy=RetryPolicy(max_restarts=2,
                                            backoff_s=0.05),
        timeout=60.0, child_grace_s=1.5)
    assert len(records) == 2  # the world shrank 3 -> 2
    assert all(r["returncode"] == 0 for r in records)
    prov = elastic.provenance()
    assert prov["elasticEvents"] >= 2  # one loss + one relaunch


def test_run_elastic_exhausts_below_min_processes():
    always_dies = ELASTIC_CHILD.replace("att == 0 and pid == 1",
                                        "pid == 1")
    with pytest.raises(RestartsExhausted) as ei:
        elastic.run_elastic(
            [sys.executable, "-c", always_dies], num_processes=2,
            min_processes=2, policy=RetryPolicy(max_restarts=3,
                                                backoff_s=0.05),
            timeout=60.0, child_grace_s=1.5)
    assert ei.value.budget == "elastic"
    assert "min_processes" in str(ei.value)


def test_run_elastic_rejects_bad_floor():
    with pytest.raises(ValueError, match="min_processes"):
        elastic.run_elastic(["true"], num_processes=1, min_processes=2)


# -- cross-N re-placement parity ----------------------------------------------

def _sgd_fit_cfg(mesh, seed, method, cfg):
    from flink_ml_tpu.ops.losses import BinaryLogisticLoss
    from flink_ml_tpu.ops.optimizer import SGD, SGDParams

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(400, 10))
    y = (x @ rng.normal(size=10) > 0).astype(np.float64)
    prm = SGDParams(learning_rate=0.1, global_batch_size=80, max_iter=8,
                    tol=0.0, reg=0.02, elastic_net=0.4, method=method)
    coeffs, loss = SGD(prm).optimize(BinaryLogisticLoss(), np.zeros(10),
                                     x, y, mesh=mesh, config=cfg)
    return coeffs, loss


@pytest.mark.parametrize("n_from,n_to", [(4, 2), (2, 1)])
def test_sharded_adam_replacement_across_n(monkeypatch, tmp_path,
                                           n_from, n_to):
    """The elastic recovery path's re-placement contract: a sharded-adam
    fit killed at a segment boundary on an ``n_from``-way mesh resumes
    on an ``n_to``-way mesh through the SAME v2 manifest — the padded
    1/N moment slices trim/re-pad losslessly, the per-shard offsets
    rescale to the same global row — it genuinely RESTORES (no
    quarantine, no fresh start), and two such resumes are
    bit-identical (the re-placed computation is deterministic)."""
    monkeypatch.setenv(upd.ENV, "1")
    ck = tmp_path / "ck"
    mgr = elastic.ElasticCheckpointManager(str(ck))
    cfg = IterationConfig(mode="device", checkpoint_interval=2,
                          checkpoint_manager=mgr)
    with faults.chaos(at={"epoch-boundary": [2]}):
        with pytest.raises(InjectedFault):
            _sgd_fit_cfg(submesh(n_from), 4, "adam", cfg)
    assert mgr.list_checkpoints()

    # freeze the mid-fit snapshot: every resume below starts from it
    frozen = tmp_path / "frozen"
    shutil.copytree(ck, frozen)

    def resume(n, tag):
        d = tmp_path / f"resume-{tag}"
        shutil.copytree(frozen, d)
        m = elastic.ElasticCheckpointManager(str(d))
        c = IterationConfig(mode="device", checkpoint_interval=2,
                            checkpoint_manager=m)
        coeffs, loss = _sgd_fit_cfg(submesh(n), 4, "adam", c)
        assert not m.list_checkpoints()  # success cleared them
        # the re-placement must have actually restored: a quarantined
        # checkpoint would silently restart the fit from scratch
        assert not [p for p in os.listdir(d) if p.endswith(".corrupt")]
        assert np.isfinite(loss)
        return np.asarray(coeffs)

    a = resume(n_to, "a")
    b = resume(n_to, "b")
    np.testing.assert_array_equal(a, b)  # bit-identical re-placement


def test_replacement_nonzero_tail_quarantined(monkeypatch, tmp_path):
    """Restoring onto a SMALLER parallelism is only lossless while the
    trimmed tail is the sharded update's inert zero pad; genuine state
    there means the checkpoint does not fit the new world — quarantine,
    not silent truncation."""
    base = elastic.ElasticCheckpointManager(str(tmp_path))
    carry = (np.arange(12, dtype=np.float64) + 1.0,)  # nonzero tail
    base.save(carry, epoch=2)
    tmpl = (np.zeros(10),)
    assert base.restore(tmpl) is None  # quarantined, no fallback left
    assert not base.list_checkpoints()


def test_elastic_ckpt_single_process_roundtrip(tmp_path):
    mgr = elastic.ElasticCheckpointManager(str(tmp_path))
    mesh = submesh(4)
    sharded = jax.device_put(
        np.arange(8, dtype=np.float32),
        jax.sharding.NamedSharding(mesh, P(DATA_AXIS)))
    carry = {"w": sharded, "step": np.int64(3)}
    mgr.save(carry, epoch=4)
    restored, epoch = mgr.restore({"w": sharded, "step": np.int64(0)})
    assert epoch == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(8, dtype=np.float32))
    assert restored["w"].sharding.is_equivalent_to(sharded.sharding,
                                                   ndim=1)
    assert int(restored["step"]) == 3
