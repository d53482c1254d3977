"""run_benchmark_sweep exit-code contract (ADVICE r5 #3).

A validation regression (an intentionally invalid demo config that RAN)
must be a distinct, NON-retryable exit code 3 with a machine-readable
record in the results JSON — not a stdout line nothing parses — while
unmeasured rows stay the retryable exit 2. Exercised through main() with
an empty configs dir and a prepared --resume file, so no benchmark
actually runs.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import run_benchmark_sweep  # noqa: E402


def _measured(throughput=100.0):
    return {"configFile": "c.json", "runs": 2,
            "results": {"inputThroughput": throughput, "totalTimeMs": 10.0,
                        "inputRecordNum": 1000, "outputRecordNum": 1000,
                        "outputThroughput": throughput}}


def _run_main(tmp_path, resume_entries):
    pytest.importorskip("matplotlib")
    configs = tmp_path / "configs"
    configs.mkdir()
    out = tmp_path / "results.json"
    out.write_text(json.dumps(resume_entries))
    rc = run_benchmark_sweep.main([
        "--configs-dir", str(configs), "--output-file", str(out),
        "--chart", str(tmp_path / "chart.png"), "--resume"])
    return rc, json.loads(out.read_text())


def test_unexpected_success_exits_3_and_is_recorded(tmp_path, capsys):
    entry = dict(_measured(), unexpectedSuccess=True)
    rc, data = _run_main(tmp_path, {"ok": _measured(),
                                    "Undefined-Parameter": entry})
    assert rc == 3
    assert data["_meta"]["validationRegression"] == ["Undefined-Parameter"]
    assert "VALIDATION REGRESSION" in capsys.readouterr().out


def test_unmeasured_rows_stay_retryable_exit_2(tmp_path):
    resume = {"ok": _measured(),
              "dead": {"configFile": "c.json",
                       "exception": "RuntimeError: device lost"},
              "Undefined-Parameter": dict(_measured(),
                                          unexpectedSuccess=True)}
    rc, data = _run_main(tmp_path, resume)
    # retryable takes precedence: the wrapper must keep resuming until
    # everything is measured, THEN surface the terminal regression
    assert rc == 2
    assert data["_meta"]["validationRegression"] == ["Undefined-Parameter"]


def test_clean_sweep_exits_0_and_drops_stale_meta(tmp_path):
    resume = {"ok": _measured(),
              "Unmatch-Input": {"configFile": "c.json",
                                "exception": "ValueError: bad col",
                                "expectedFailure": True},
              "_meta": {"validationRegression": ["stale"]}}
    rc, data = _run_main(tmp_path, resume)
    assert rc == 0
    assert "_meta" not in data
