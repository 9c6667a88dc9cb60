"""BENCHMARK.json against the contract's limits and the files it names."""

import json
import re

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells

ROOT = cells.ROOT
SPEC = cells.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_are_exactly_the_contracts():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in SPEC["paths"])
    cells_n = len(SPEC["workloads"])
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200, "run_seconds does not fit a full check of 24 cells"
    assert cells_n <= 24


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_loads_and_reports_what_it_must(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
    loaded = cells.load_cell(cell["name"])
    names = [m["name"] for m in loaded.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert loaded.per_layer, "a cell reports at least one per-layer metric"
    assert (ROOT / "benchmark" / "drivers"
            / f"{loaded.traffic['kind']}.py").is_file()
    for m in loaded.per_layer:     # moves a metric this cell reports
        assert m["moves"] in names, (m["name"], m["moves"])


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_each_configuration_has_its_file_and_reference(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert body["reduced"] == cfg["reduced"]
    assert cfg["file"].startswith(tuple(SPEC["paths"]))
    ref = body.get("reference", cfg["name"])
    assert (ROOT / "benchmark" / "reference" / f"{ref}.py").is_file()
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])
    for limits in body["limits"].values():
        assert all(v > 0 for v in limits.values()), "a limit left unset"


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_each_metric_entry(metric):
    e2e = metric in SPEC["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(metric) <= allowed and NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    known = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", known)) <= known
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        spec = cells.metric_file(metric["name"])
        assert (ROOT / "benchmark" / "readers"
                / f"{spec['reader']}.py").is_file()
        for key in ("unit", "layer", "moves", "source", "better"):
            assert spec[key] == metric[key], (metric["name"], key)
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}


def test_an_unknown_device_kind_has_no_peaks():
    from benchmark.harness.device import peaks

    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9000")
