"""Finding a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: each is a file of its own, found from the entry that names it.

- cell ``<config>.<traffic>``: one entry of ``workloads``;
- configuration: the ``file`` of its ``configs`` entry (sizes, source,
  ``reduced``, ``assumed``), with its plain reference at
  ``benchmark/reference/<reference>.py`` (``reference`` key of the
  file, default the configuration's name);
- traffic mix: ``benchmark/traffic/<traffic>.json``: ``kind`` plus
  parameters; the kind's driver is ``benchmark/drivers/<kind>.py``;
- per-layer metric: ``benchmark/metrics/<name>.json`` (unit, layer,
  moves, source, ``reader``) and ``benchmark/readers/<reader>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]      # the checkout


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list      # metric entries this cell reports
    per_layer: list


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_spec(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    traffic_file = root / "benchmark" / "traffic" / f"{w['traffic']}.json"
    with open(traffic_file) as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, name)])


def _module(sub: str, name: str, root: Path):
    """``benchmark/<sub>/<name>.py``: the package's module in this
    checkout, or the file of another root (a test's, say) loaded by
    path."""
    if root == ROOT:
        return importlib.import_module(f"benchmark.{sub}.{name}")
    path = root / "benchmark" / sub / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{sub}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver_for(cell: Cell, root: Path = ROOT):
    return _module("drivers", cell.traffic["kind"], root)


def reference_for(config: dict, config_name: str, root: Path = ROOT):
    return _module("reference", config.get("reference", config_name), root)


def metric_file(name: str, root: Path = ROOT) -> dict:
    with open(root / "benchmark" / "metrics" / f"{name}.json") as f:
        return json.load(f)


def reader_for(metric: dict, root: Path = ROOT):
    return _module("readers", metric["reader"], root)
