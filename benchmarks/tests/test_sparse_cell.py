"""What ``criteo_fit_ref20`` brought: the configuration against its public
source and upstream's stage, the scatter's count against the fit's, the CPU
rehearsal of the cell in both ``--trace`` modes, the four span readers and
the batch reads on a hand-made ring, and the scatter's two readers on a
hand-made trace reduction. The cell's ``per_layer`` entries are found by
name, never by place."""

import io
import json

import pytest

from benchmarks import run_cell
from benchmarks.harness import counts, readers, sparse_spans, spec
from benchmarks.harness.counts import sgd_sparse_scatter

CELL = "criteo_fit_ref20"
PEAKS = {"peak_flops_per_s": 1e12, "peak_hbm_bytes_per_s": 1e11}
SEED = 2**33 + 4343
NEW = tuple(f"sparse_span_{part}_ms" for part in sparse_spans.PARTS) + (
    "sparse_batch_reads_per_fit", "sparse_grad_device_ms",
    "sparse_grad_roofline")
#: 200,000 rows and a batch of 10,000: the published 20 rounds wrap the
#: table once
CUTS = {"inputData": {"numValues": 200_000},
        "stage": {"globalBatchSize": 10_000}}


def test_the_configuration_is_its_source_but_the_rows():
    cell = spec.load_cell(CELL)
    spec.check_source(cell.config)
    with open(spec.ROOT / cell.config["stage_vendored"]) as f:
        (job,) = (v for k, v in json.load(f).items() if k != "version")
    assert cell.config["stage"]["paramMap"] == job["stage"]["paramMap"]
    data = cell.config["inputData"]["paramMap"]
    scaled = cell.config["scaled"]["numValues"]
    assert (scaled["source"], scaled["here"]) == (45_840_617, 23_000_000)
    assert (data["numFeatures"], data["numericFields"],
            data["categoricalFields"]) == (1 << 18, 13, 26)
    assert set(cell.config["assumed"]) >= {"cardinalities", "zipfExponent"}
    assert cell.chips == 1 and cell.config["mesh"] == {"data": 1}
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= listed
    # the all-cell metrics read here too; the other cells' spans do not
    assert {"fit_device_roofline", "fit_mfu", "device_idle_pct",
            "programs_per_fit", "window_compiles"} <= listed
    assert not any(n.startswith(("fit_span_", "anova_", "nb_"))
                   for n in listed)
    for name in NEW:
        assert callable(readers.load(
            spec.layer_metric_file(name)["reader"]))


def test_the_scatter_count_is_the_fit_count_s_part():
    cell = spec.load_cell(CELL)
    stage, data = cell.stage_params(), cell.config["inputData"]["paramMap"]
    scatter = sgd_sparse_scatter.count(stage, data)
    assert scatter == sgd_sparse_scatter.from_fit(
        counts.per_fit("sgd_sparse", stage, data))
    # 2M rows of 39 ids and terms read once, the 2^18 gradient written once
    # a round; an add an entry
    assert scatter == {"rows": 2_000_000,
                       "bytes": 2_000_000 * 39 * 8 + 20 * 4 * (1 << 18),
                       "flops": 2_000_000 * 39}


def drive(trace: bool, seconds: float):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell.run(CELL, SEED, seconds, trace, require_tpu=False,
                      overrides=CUTS, peaks=PEAKS, out=out, err=err)
    assert rc == 0, err.getvalue()
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[0]), json.loads(lines[-1])


@pytest.mark.parametrize("trace", [False, True])
def test_the_rehearsal_runs_the_sparse_device_path(trace):
    """Off the chip the device trace holds no operation, so the device
    metrics are left out; everything else the cell lists is read."""
    info, result = drive(trace, 3.0 if trace else 1.0)
    assert info["execution_paths"] == ["sparse-device"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["coef_gap"]["limit"] == 3e-3
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"fit_rows_per_s", "setup_s"}
        return
    assert metrics["sparse_batch_reads_per_fit"]["value"] == 20
    parts = [metrics[f"sparse_span_{p}_ms"]["value"]
             for p in sparse_spans.PARTS]
    assert all(v >= 0 for v in parts)
    assert {"window_compiles", "fit_mfu", "setup_first_fit_s"} <= set(
        metrics)
    assert "sparse_grad_device_ms" not in metrics


def span(id_, name, parent, dur, trace=1, **attrs):
    return {"id": id_, "trace": trace, "name": name, "parent": parent,
            "dur_us": dur, "attrs": attrs}


def fit_records(trace, path="sparse-device", root_us=700_000):
    base = trace * 100
    return [
        span(base, "LogisticRegression.fit", None, root_us, trace,
             kind="fit"),
        span(base + 1, "fit.extract", base, 10, trace),
        span(base + 2, "sgd.optimize", base, root_us - 500, trace,
             path=path, batch_reads=20),
        span(base + 3, "sgd.place_inputs", base + 2, 300, trace),
        span(base + 4, "sgd.launch", base + 2, 400, trace),
        span(base + 5, "sgd.fetch", base + 2, root_us - 2_000, trace),
        span(base + 6, "fit.model", base, 40, trace),
    ]


def test_the_span_parts_sum_to_the_root_and_read_sparse_fits_alone():
    records = [r for t in range(1, 4) for r in fit_records(t)]
    records += fit_records(9, path="xla-while")     # a dense fit: not read
    found = sparse_spans.medians_ms(records)
    assert found["fits"] == 3 and found["batch_reads"] == 20
    assert found["place"] == 0.3 and found["launch"] == 0.4
    assert found["fetch"] == 698.0
    assert sum(found[p] for p in sparse_spans.PARTS) == pytest.approx(
        found["root"])
    assert sparse_spans.medians_ms(records[:14]) is None  # two fits


def test_the_scatter_readers_read_its_two_operations():
    cell = spec.load_cell(CELL)
    count = counts.per_fit("sgd_sparse", cell.stage_params(),
                           cell.config["inputData"]["paramMap"])
    ctx = {"count": count, "peaks": {"peak_flops_per_s": 197e12,
                                     "peak_hbm_bytes_per_s": 819e9},
           "chips": 1,
           "trace": {"cycles": 2, "device_ops": [
               ["fusion.20", 0.698], ["fusion.18", 0.690],
               ["fusion.21", 0.002], ["copy-done.2", 0.0005]]}}
    device = readers.load("sparse_grad_device_ms")
    roofline = readers.load("sparse_grad_roofline")
    assert device(ctx) == pytest.approx(350.0)
    least = sgd_sparse_scatter.count(
        cell.stage_params(), cell.config["inputData"]["paramMap"])[
        "bytes"] / 819e9
    assert roofline(ctx) == pytest.approx(100 * least / 0.350)
    assert 0 < roofline(ctx) < 100
    for trace in (None, {"cycles": 2, "device_ops": [["fusion.18", 1.0]]}):
        assert device(dict(ctx, trace=trace)) is None
        assert roofline(dict(ctx, trace=trace)) is None
