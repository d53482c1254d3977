"""Resolve one cell of ``BENCHMARK.json`` to its data files, by name.

Nothing here lists a configuration, a traffic mix or a metric: a later PR
adds ``configs/<c>.json``, ``traffic/<t>.json``, ``layer_metrics/<m>.json``
and one ``workloads`` entry, and the cell resolves.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

#: the checkout: ``benchmarks/harness/spec.py`` is two levels under it
ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"


class SpecError(Exception):
    """``BENCHMARK.json`` or one of the files it names is missing or wrong."""


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {path}") from None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration's own file
    traffic: dict         # the traffic mix's own file
    end_to_end: tuple     # BENCHMARK.json entries this cell reports
    per_layer: tuple      # BENCHMARK.json entries this cell reports

    def stage_params(self) -> dict:
        """The stage's paramMap as this cell runs it: the configuration's,
        with the traffic mix's overrides on top."""
        params = dict(self.config["stage"].get("paramMap", {}))
        params.update(self.traffic.get("stage_overrides", {}))
        return params


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


#: what a traffic file may hold; a key the harness would not read is an
#: error, never a parameter silently dropped
TRAFFIC_KEYS = {"name", "kind", "stage_overrides", "trace_capture_s",
                "what", "who"}


def _check(cell_name: str, chips: int, config: dict, traffic: dict) -> None:
    """What the files promise has to be what the run does."""
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise SpecError(f"traffic {traffic.get('name')!r} has keys the "
                        f"harness does not read: {sorted(unknown)}")
    barred = set(traffic.get("stage_overrides", {})) - set(
        config.get("traffic_may_override", ()))
    if barred:
        raise SpecError(f"traffic {traffic.get('name')!r} overrides "
                        f"{sorted(barred)}, which configuration "
                        f"{config.get('name')!r} does not allow")
    if config.get("mesh", {}).get("data") != chips:
        raise SpecError(f"workload {cell_name!r} asks for {chips} chips, "
                        f"its configuration's mesh is {config.get('mesh')}")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(by_name)}")
    work = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if work["config"] not in configs:
        raise SpecError(f"workload {name!r} names no known config")
    config = _read_json(root / configs[work["config"]]["file"])
    traffic = _read_json(
        root / "benchmarks" / "traffic" / f"{work['traffic']}.json")
    _check(name, int(work["chips"]), config, traffic)
    end_to_end = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in end_to_end}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _applies(m, name) and m["moves"] in reported)
    return Cell(name=name, chips=int(work["chips"]),
                config_name=work["config"], config=config, traffic=traffic,
                end_to_end=end_to_end, per_layer=per_layer)


def _short(class_name: str) -> str:
    """A class name as the program's registry resolves it: its last part."""
    return class_name.rsplit(".", 1)[-1]


def _same_class(ours: dict, theirs: dict) -> bool:
    """Two ``stage`` or ``inputData`` blocks, key for key, class names
    compared by their last part."""
    def plain(block):
        return dict(block, className=_short(block.get("className", "")))

    return plain(ours) == plain(theirs)


def _upstream(root: Path, path: str) -> dict:
    """The one job of a vendored upstream benchmark file."""
    (job,) = (v for k, v in _read_json(root / path).items()
              if k != "version")
    return job


def check_source(config: dict, root: Path = ROOT) -> None:
    """A configuration runs its source as published, but for what
    ``scaled`` lists with the source's own number beside it.

    Its source is upstream's benchmark file (``source_vendored``: the stage
    and every generator key are the file's), or a public benchmark of the
    field named in ``source``: then ``assumed`` lists every generator key
    the source does not fix, ``from_source`` where the source states the
    others, and ``stage_vendored``, where the stage is upstream's, names
    the file it must equal."""
    name = config.get("name")
    data = dict(config["inputData"]["paramMap"])
    scaled = config.get("scaled", {})
    for key, change in scaled.items():
        if change.get("source") is None:
            raise SpecError(f"configuration {name!r} scales {key!r} "
                            f"without the source's own value")
        if data.get(key) != change.get("here"):
            raise SpecError(f"configuration {name!r} runs {key!r} = "
                            f"{data.get(key)!r}, its scaled says "
                            f"{change.get('here')!r}")
    if "source_vendored" in config:
        job = _upstream(root, config["source_vendored"])
        theirs = dict(job["inputData"]["paramMap"])
        for key, change in scaled.items():
            if theirs.pop(key, None) != change["source"]:
                raise SpecError(f"configuration {name!r}: the source's "
                                f"{key!r} is not {change['source']!r}")
            data.pop(key)
        if not (_same_class(config["stage"], job["stage"])
                and _same_class(dict(config["inputData"], paramMap=data),
                                dict(job["inputData"], paramMap=theirs))):
            raise SpecError(f"configuration {name!r} is not its vendored "
                            f"source {config['source_vendored']}")
        return
    if "stage_vendored" in config and not _same_class(
            config["stage"], _upstream(root, config["stage_vendored"])[
                "stage"]):
        raise SpecError(f"configuration {name!r}: its stage is not the "
                        f"one of {config['stage_vendored']}")
    if not config.get("assumed"):
        raise SpecError(f"configuration {name!r} names a public source and "
                        f"assumes nothing: list in 'assumed' every "
                        f"generator key the source does not fix")
    unaccounted = set(data) - {"colNames"} - set(scaled) - set(
        config.get("from_source", {})) - set(config["assumed"])
    if unaccounted:
        raise SpecError(f"configuration {name!r}: {sorted(unaccounted)} "
                        f"neither come from the source nor are assumed")


def layer_metric_file(metric_name: str, root: Path = ROOT) -> dict:
    """``layer_metrics/<name>.json``: the reader the metric uses, by name."""
    return _read_json(
        root / "benchmarks" / "layer_metrics" / f"{metric_name}.json")
