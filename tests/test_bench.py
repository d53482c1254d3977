"""bench.py: one process that measures on the chip or fails.

The workloads themselves are covered by the benchmark runner tests; these
pin the file's contract — it refuses any platform but ``tpu``, a raising
row fails the run, it starts no child, and the line names the device.
"""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

ROW = {"inputRecordNum": 10_000, "totalTimeMs": 10.0,
       "inputThroughput": 1_000_000.0, "deviceCount": 1,
       "meshShape": "data=1", "executionPath": "xla-lloyd"}


@pytest.fixture
def on_fake_tpu(monkeypatch, tmp_path):
    """jax.devices() reporting one TPU, and the compile cache pointed
    away from the checkout (configure() touches nothing when the
    variable is set)."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])


def test_refuses_a_non_tpu_platform(monkeypatch, tmp_path, capsys):
    import flink_ml_tpu.benchmark.runner as runner

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(runner, "best_of", lambda *a, **k: pytest.fail(
        "bench.py measured on a non-TPU platform"))
    assert bench.main() != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line off-chip
    assert "cpu" in captured.err


def test_line_names_the_device_and_attaches_northstar(on_fake_tpu,
                                                      monkeypatch, capsys):
    import flink_ml_tpu.benchmark.runner as runner

    monkeypatch.setattr(runner, "best_of",
                        lambda name, spec, runs=3: dict(ROW))
    assert bench.main() == 0
    (line,) = capsys.readouterr().out.strip().splitlines()
    doc = json.loads(line)
    assert (doc["platform"], doc["device_kind"], doc["device_count"]) == (
        "tpu", "TPU v5 lite", 1)
    assert doc["value"] == 1_000_000.0 and doc["vs_baseline"] > 0
    assert set(doc["northstar"]) == {
        "logisticregression", "KMeans", "KnnModel-predict",
        "OnlineLogisticRegression"}
    assert doc["northstar"]["KMeans"]["executionPath"] == "xla-lloyd"


def test_a_raising_row_fails_the_run(on_fake_tpu, monkeypatch, capsys):
    import flink_ml_tpu.benchmark.runner as runner

    def best_of(name, spec, runs=3):
        if name == "KMeans":
            raise RuntimeError("Mosaic failed to compile (synthetic)")
        return dict(ROW)

    monkeypatch.setattr(runner, "best_of", best_of)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        bench.main()  # sys.exit(main()) turns this into exit code 1
    assert capsys.readouterr().out == ""


def test_starts_no_child_process():
    with open(os.path.join(REPO, "bench.py")) as f:
        assert "subprocess" not in f.read()
