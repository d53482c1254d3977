"""chip_smoke.py's phase functions at tiny sizes on the CPU mesh.

The smoke's real job is one run on the chip at full width; a chip call
must never be spent on a plain Python bug in the file itself — so tier-1
calls every phase function with the kernels patched to interpreter mode
(the ``interpreted_kernels`` fixture of conftest.py), and pins the contract of ``main``: it refuses any platform but ``tpu``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

KERNELS = ("assign_nearest", "category_counts", "grouped_moments",
           "knn_topk_indices", "lloyd_partial_sums", "segment_reduce_sum")


def test_main_refuses_a_non_tpu_platform():
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero in
    seconds and prints no result line — there is no switch that lets a
    CPU run pass."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program must fail too (the driver's
    contract): a copy in an empty directory cannot import the package."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_lr_fit_phase_tiny(mesh8):
    # 32768 x 100 keeps the device datagen path (>= 8 MB) and gives each
    # of the 8 shards 4096 rows and a 512-row batch share
    out = chip_smoke.lr_fit_phase(
        mesh8, stage={"maxIter": 4, "globalBatchSize": 4096},
        data={"numValues": 32768}, ckpt_rounds=4, ckpt_interval=2,
        sample_rows=4096, min_accuracy=0.5, max_loss=float(np.log(2.0)),
        ref_tol=1e-3, seg_tol=1e-3)
    assert out["vendored"]["executionPath"] == "xla-while"
    assert out["learnablePath"] == "xla-while"
    assert out["segmentPath"] == "xla-while-segments"
    assert out["input"] == {"shards": 8, "rowsPerShard": 4096}
    assert out["refRelErr"] < 1e-3


def test_lr_fit_phase_fails_on_a_wrong_path(mesh8, monkeypatch):
    """A fit whose row reports another path than the plain fit's one
    program must FAIL the phase, not say ok."""
    real = chip_smoke.run_row
    monkeypatch.setattr(
        chip_smoke, "run_row",
        lambda name, spec: {**real(name, spec),
                            "executionPath": "host-rounds"})
    with pytest.raises(chip_smoke.SmokeFailure, match="expected"):
        chip_smoke.lr_fit_phase(
            mesh8, stage={"maxIter": 2, "globalBatchSize": 4096},
            data={"numValues": 32768})


def test_kmeans_phase_tiny(mesh8, interpreted_kernels):
    out = chip_smoke.kmeans_phase(
        mesh8, stage={"maxIter": 3}, data={"numValues": 32768},
        sample_rows=4096, tie_tol=1e-3)
    assert out["vendored"]["executionPath"] == "pallas-lloyd"
    assert out["transformPath"] == "pallas-assign"
    assert out["inertiaFullFit"] < out["inertiaOneRound"]


def test_serving_phase_tiny(mesh8):
    out = chip_smoke.serving_phase(mesh8, dim=16, rows=3000, batch=1000,
                                   requests=12, prob_tol=1e-4)
    assert out["steadyCompiles"] == 0 and out["warmCompiles"] > 0
    assert out["shardedBuckets"] == [8, 32]
    assert out["trainPath"] == "device-batches"


def test_kernels_phase_tiny(interpreted_kernels, monkeypatch):
    import jax

    # the kernel check refuses the cpu backend by name
    monkeypatch.setattr(jax, "default_backend", lambda: "interpret-ci")
    out = chip_smoke.kernels_phase(shrink=64)
    assert out["rc"] == 0 and sorted(out["kernels"]) == sorted(KERNELS)


def test_kernels_phase_fails_when_a_kernel_raises(interpreted_kernels,
                                                  monkeypatch):
    import jax

    from flink_ml_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(jax, "default_backend", lambda: "interpret-ci")

    def broken(*a, **kw):
        raise NotImplementedError("Mosaic: unimplemented (synthetic)")

    monkeypatch.setattr(pk, "segment_reduce_sum", broken)
    with pytest.raises(chip_smoke.SmokeFailure, match="exited 3"):
        chip_smoke.kernels_phase(shrink=64)


def test_host_tier_phase_forks(monkeypatch):
    monkeypatch.setenv("FLINK_ML_TPU_HOST_PARALLELISM", "2")
    out = chip_smoke.host_tier_phase(num_values=1 << 17, array_size=4,
                                     distinct=20)
    assert out == {"workers": 2, "vocabulary": 20,
                   "childCpuS": out["childCpuS"]}
    assert out["childCpuS"] > 0


def test_multichip_phase_tiny(mesh8):
    out = chip_smoke.multichip_phase(mesh8, rows=4096, dim=16, max_iter=3,
                                     tol=1e-5)
    assert out["devices"] == 8 and out["relErrVsOneDevice"] < 1e-5


def test_run_phases_reports_every_phase_and_fails(capsys):
    """One failing phase makes the run fail, and later phases still run."""

    class Counter:
        backend_compiles = 0

    def bad():
        raise chip_smoke.SmokeFailure("wrong answer")

    ok = chip_smoke.run_phases(
        [("a", lambda: {"x": 1}), ("b", bad), ("c", lambda: {})], Counter())
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert ok is False
    assert [(r["phase"], r["ok"]) for r in lines] == [
        ("a", True), ("b", False), ("c", True)]
    assert "wrong answer" in lines[1]["error"]
