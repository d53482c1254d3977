"""BENCHMARK.json against the contract's limits, and the files it names."""

import json
import re
import shutil

import pytest

from benchmarks.harness import counts, readers, references, spec, windows

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for cfg in bench["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
        assert all(NAME.match(k) for k in cfg["reduced"])
    assert len({c["source"] for c in bench["configs"]}) == len(
        bench["configs"])
    assert len({c["file"] for c in bench["configs"]}) == len(
        bench["configs"])
    for work in bench["workloads"]:
        assert set(work) == {"name", "config", "traffic", "chips", "why"}
        assert work["chips"] in (1, 4) and 1 <= len(work["why"]) <= 200
        assert NAME.match(work["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    rooflines = [m for m in bench["per_layer"] if "roofline" in m["name"]]
    assert rooflines and all(m["name"].endswith("_roofline")
                             and m["unit"] == "%" for m in rooflines)
    assert any("mfu" in m["name"].split("_") for m in bench["per_layer"])


def test_every_cell_resolves_by_name(bench):
    for work in bench["workloads"]:
        cell = spec.load_cell(work["name"])
        assert callable(windows.load(cell.traffic["kind"]))
        data = cell.config["inputData"]["paramMap"]
        assert counts.per_fit(cell.config["counts"], cell.stage_params(),
                              data)["rows"] > 0
        module = references.load(cell.config["correct"]["reference"])
        assert callable(module.run) and callable(module.compare)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        for metric in cell.per_layer:
            assert callable(readers.load(
                spec.layer_metric_file(metric["name"])["reader"]))
        limits = cell.config["correct"]["limits"]
        assert all(isinstance(v, (int, float)) for v in limits.values())
        scaled = set(cell.config["scaled"])
        listed = next(c for c in bench["configs"]
                      if c["name"] == cell.config_name)
        assert scaled == set(listed["reduced"])
        assert listed["source"] == cell.config["source"]
        assert (spec.ROOT / listed["file"]).is_file()


def test_the_published_stage_is_run_as_published(bench):
    """Every configuration is its source but for what ``scaled`` lists:
    each key of the vendored upstream file, or, for a public benchmark of
    the field, every generator key from the source or assumed, and the
    stage of ``stage_vendored``. Class names compare by their last part."""
    for listed in bench["configs"]:
        spec.check_source(json.loads((spec.ROOT / listed["file"]).read_text()))
    for work in bench["workloads"]:
        cell = spec.load_cell(work["name"])
        assert cell.stage_params() == cell.config["stage"]["paramMap"]


def _lr_config():
    return json.loads((spec.BENCH_DIR / "configs/lr-dense-100.json"
                       ).read_text())


def test_a_vendored_class_name_compares_by_its_last_part():
    config = _lr_config()
    config["stage"]["className"] = "flink_ml_tpu.models.LogisticRegression"
    spec.check_source(config)
    config["stage"]["className"] = "LinearSVC"
    with pytest.raises(spec.SpecError, match="vendored source"):
        spec.check_source(config)


def public_config():
    """A deployment sourced from a public benchmark of the field, with the
    stage block of upstream's LR benchmark."""
    stage = _lr_config()["stage"]
    return {
        "name": "criteo-hashed-lr",
        "source": "Criteo Display Advertising Challenge (Kaggle 2014), "
                  "train.txt",
        "stage_vendored": "flink_ml_tpu/benchmark/configs/"
                          "logisticregression-benchmark.json",
        "stage": stage,
        "inputData": {"className": "CriteoHashedGenerator",
                      "paramMap": dict(SPARSE_INPUT)},
        "scaled": {"numValues": {"source": 45840617, "here": 20000,
                                 "why": "a test's size"}},
        "from_source": {"numericFields": "I1-I13",
                        "categoricalFields": "C1-C26",
                        "numFeatures": "FeatureHasher's default"},
        "assumed": {"cardinalities": "DLRM's list, recalled",
                    "zipfExponent": "a power law of exponent 1.1"},
    }


SPARSE_INPUT = {
    "colNames": [["features", "label"]], "numValues": 20000,
    "numFeatures": 262144, "numericFields": 13, "categoricalFields": 26,
    "cardinalities": [1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3,
                      93145, 5683, 8351593, 3194, 27, 14992, 5461306, 10,
                      5652, 2173, 4, 7046547, 18, 15, 286181, 105, 142572],
    "zipfExponent": 1.1}


def test_a_public_source_is_admitted():
    spec.check_source(public_config())


def _without(config, *path):
    *parents, last = path
    for key in parents:
        config = config[key]
    del config[last]


@pytest.mark.parametrize("change,match", [
    (lambda c: _without(c, "assumed"), "assumes nothing"),
    (lambda c: _without(c, "scaled", "numValues", "source"),
     "source's own value"),
    (lambda c: c["stage"]["paramMap"].update(maxIter=120),
     "logisticregression-benchmark.json"),
    (lambda c: _without(c, "assumed", "zipfExponent"), "zipfExponent"),
    (lambda c: c["scaled"]["numValues"].update(here=30000), "scaled says"),
])
def test_what_a_public_source_leaves_out_is_refused(change, match):
    """No ``assumed``; a ``scaled`` key without the source's own number; a
    stage that is not its ``stage_vendored`` file's; a generator key that
    neither the source fixes nor ``assumed`` lists; a size that is not the
    one ``scaled`` states."""
    config = public_config()
    change(config)
    with pytest.raises(spec.SpecError, match=match):
        spec.check_source(config)


def test_collective_metric_only_in_the_four_chip_cell(bench):
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "collective_exposed_pct")
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert metric["workloads"] == four


def _copy_with(tmp_path, bench, traffic, work, metric=None):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / f"benchmarks/traffic/{traffic['name']}.json").write_text(
        json.dumps(traffic))
    bench = json.loads(json.dumps(bench))
    bench["workloads"].append(work)
    if metric:
        (root / f"benchmarks/layer_metrics/{metric['name']}.json"
         ).write_text(json.dumps({"reader": "programs_per_fit"}))
        bench["per_layer"].append(metric)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_readme_example_resolves_from_files_alone(tmp_path, bench):
    """The README's worked example: a new traffic mix, a new per-layer
    metric and one more ``workloads`` entry, no edit to the harness."""
    root = _copy_with(
        tmp_path, bench,
        {"name": "fit_rounds_120", "kind": "closed_loop_fits",
         "stage_overrides": {"maxIter": 120}, "what": "...", "who": "..."},
        {"name": "lr_fit_r120", "config": "lr-dense-100",
         "traffic": "fit_rounds_120", "chips": 1, "why": "example"},
        {"name": "r120_programs_per_fit", "unit": "count", "better": "lower",
         "source": "device_trace", "layer": "optimizer and programs",
         "moves": "fit_rows_per_s", "workloads": ["lr_fit_r120"]})
    cell = spec.load_cell("lr_fit_r120", root)
    assert cell.stage_params()["maxIter"] == 120
    assert "r120_programs_per_fit" in {m["name"] for m in cell.per_layer}
    assert spec.layer_metric_file("r120_programs_per_fit", root)[
        "reader"] == "programs_per_fit"
    others = spec.load_cell("lr_fit_ref20", root)
    assert others.stage_params()["maxIter"] == 20
    assert "r120_programs_per_fit" not in {m["name"]
                                           for m in others.per_layer}


@pytest.mark.parametrize("traffic,work,match", [
    ({"name": "t", "kind": "closed_loop_fits", "stage_overrides": {},
      "iteration": {"checkpoint_interval": 30}},
     {"config": "lr-dense-100", "chips": 1}, "does not read"),
    ({"name": "t", "kind": "closed_loop_fits",
      "stage_overrides": {"globalBatchSize": 1000}},
     {"config": "lr-dense-100", "chips": 1}, "does not allow"),
    ({"name": "t", "kind": "closed_loop_fits", "stage_overrides": {}},
     {"config": "lr-dense-100", "chips": 4}, "mesh"),
])
def test_what_the_harness_would_not_do_is_an_error(tmp_path, bench, traffic,
                                                   work, match):
    """A traffic key that nothing reads, an override the configuration
    does not allow, chips that are not the configuration's mesh: refused
    when the cell is loaded, never silently dropped."""
    work = dict(work, name="cell_x", traffic="t", why="x")
    root = _copy_with(tmp_path, bench, traffic, work)
    with pytest.raises(spec.SpecError, match=match):
        spec.load_cell("cell_x", root)


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such_cell")


def test_every_configuration_keeps_a_cell(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
