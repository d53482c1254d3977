"""``sparse_gradient_device_ms`` and ``sparse_gradient_roofline``: the
gradient's device time and its share of the roofline, keyed on the
operations the program names under the gradient's scope (``gradient_ops`` on
``sgd.launch``), on a hand-made ring and trace reduction."""

import json

import pytest

from benchmarks.harness import counts, readers, spec
from benchmarks.harness.counts import sgd_sparse_scatter
from benchmarks.tests.test_sparse_cell import CELL, fit_records

METRICS = ("sparse_gradient_device_ms", "sparse_gradient_roofline")
#: as a v5e compiles the cell's program: the wide entries' scatter-add, the
#: dictionary sums, and the hot and dictionary sums' add
OPS = ("fusion.142", "fusion.143", "fusion.145", "fusion.156")


def with_ops(records, ops=OPS):
    for r in records:
        if r["name"] == "sgd.launch":
            r["attrs"]["gradient_ops"] = list(ops)
    return records


def context(device_ops):
    cell = spec.load_cell(CELL)
    return {"count": counts.per_fit("sgd_sparse", cell.stage_params(),
                                    cell.config["inputData"]["paramMap"]),
            "peaks": {"peak_flops_per_s": 197e12,
                      "peak_hbm_bytes_per_s": 819e9},
            "chips": 1, "trace": {"cycles": 2, "device_ops": device_ops}}


def test_the_metrics_are_the_criteo_cell_s_alone():
    with open(spec.ROOT / "BENCHMARK.json") as f:
        workloads = json.load(f)["workloads"]
    for name in METRICS:
        assert readers.load(spec.layer_metric_file(name)["reader"])
        for work in workloads:
            listed = {m["name"] for m in
                      spec.load_cell(work["name"]).per_layer}
            assert (name in listed) == (work["name"] == CELL)


def test_the_readers_time_the_named_operations_alone():
    device, roofline = map(readers.load, METRICS)
    records = [r for t in range(1, 4) for r in with_ops(fit_records(t))]
    # a dense fit's launch names nothing of the gradient's
    records += with_ops(fit_records(9, path="xla-while"), ("fusion.18",))
    ctx = context([["fusion.143", 0.400], ["fusion.140", 0.380],
                   ["fusion.142", 0.004], ["fusion.18", 0.002],
                   ["fusion.156", 0.001], ["copy-done.2", 0.0005]])
    assert device(ctx, records) == pytest.approx(1e3 * 0.405 / 2)
    cell = spec.load_cell(CELL)
    least = sgd_sparse_scatter.count(
        cell.stage_params(), cell.config["inputData"]["paramMap"])[
        "bytes"] / 819e9
    assert roofline(ctx, records) == pytest.approx(100 * least / 0.2025)
    assert 0 < roofline(ctx, records) < 100


@pytest.mark.parametrize("what", ["older-program", "no-trace",
                                  "none-of-them-traced"])
def test_the_readers_give_nothing_where_nothing_is_named(what):
    device, roofline = map(readers.load, METRICS)
    records = [r for t in range(1, 4) for r in with_ops(fit_records(t))]
    ctx = context([["fusion.143", 0.400]])
    if what == "older-program":
        records = [r for t in range(1, 4) for r in fit_records(t)]
    elif what == "no-trace":
        ctx["trace"] = None
    else:
        ctx["trace"]["device_ops"] = [["fusion.20", 0.698]]
    assert device(ctx, records) is None
    assert roofline(ctx, records) is None
