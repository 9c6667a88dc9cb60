"""The per-layer metrics ``xing4_29b_a4b.train_mtp`` brings: each file
against its entry, each reader on a trace recorded on the chip and where
there is nothing to read (a parent without the scopes, no trace, a CPU
run), and the cell on the lists a training cell stands on."""

import shutil
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.readers import op_scope_share, scope_roofline
from benchmark.reduce import host_spans, mhc_cost
from benchmark.reference import xing4

CELL = "xing4_29b_a4b.train_mtp"
REDUCE = Path(host_spans.__file__).resolve().parent
RECORDED = REDUCE / "recorded_spans.xplane.pb"
WITHOUT_SCOPES = REDUCE / "recorded_tiny.xplane.pb"
SCOPES = {"mhc_time_pct.train": ("lm/mhc", "hyper-connections"),
          "mtp_time_pct.train": ("lm/mtp", "multi-token prediction")}
ROOFLINE = "mhc_roofline_pct.train"
NEW = (*SCOPES, ROOFLINE)
SHARED_LISTS = ("train_img_per_s", "step_mfu_pct.train",
                "conv_time_pct.train", "device_idle_pct.train")


def _as_the_traced_run(monkeypatch, tmp_path, recorded):
    d = tmp_path / CELL / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "host.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)


def _facts(images=200, window_s=52.0, peak=197e12):
    return {"train": {"images": images, "window_s": window_s, "chips": 1,
                      "peak_flops": peak},
            "trace": {"busy_s": 7.9, "window_s": 8.0}}


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_file_equals_its_entry(name):
    spec = cells.metric_file(name)
    (entry,) = [m for m in cells.load_spec()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == [CELL]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (name, key)
    assert entry["moves"] == "train_img_per_s" and entry["unit"] == "%"
    assert hasattr(cells.reader_for(spec), "read") and spec["what"]
    if name in SCOPES:
        assert (spec["reader"], spec["scope"], spec["layer"]) == (
            "op_scope_share", *SCOPES[name])
    else:
        assert (spec["reader"], spec["scope"], spec["cost"],
                spec["config"]) == ("scope_roofline", "lm/mhc", "mhc_cost",
                                    "xing4_29b_a4b")


def test_the_cell_loads_with_its_files_and_stands_on_the_shared_lists():
    from benchmark.drivers import train_resident_mtp

    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_resident_mtp"
    assert cells.driver_for(cell) is train_resident_mtp
    assert cells.reference_for(cell.config, cell.config_name) is xing4
    assert [m["name"] for m in cell.end_to_end] == ["train_img_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "compile_s", *SHARED_LISTS[1:], *NEW}
    assert (cell.traffic["checked_steps"], cell.traffic["in_flight"],
            cell.traffic["trace_seconds"]) == (2, 2, 8.0)
    spec = cells.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in SHARED_LISTS:
            assert CELL in m["workloads"], m["name"]
        elif m["name"] not in NEW:
            assert CELL not in m.get("workloads", ()), m["name"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_leaves_the_metric_out(
        monkeypatch, tmp_path, name):
    """The parent's program has no such scope, and no trace is no trace:
    the reader returns nothing and does not raise."""
    spec = cells.metric_file(name)
    read = cells.reader_for(spec).read
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)
    assert read(_facts(), spec) is None
    _as_the_traced_run(monkeypatch, tmp_path, WITHOUT_SCOPES)
    assert read(_facts(), spec) is None


def test_the_roofline_reader_on_a_recorded_trace(monkeypatch, tmp_path):
    """Least seconds a sample x samples a second over the scope's busy
    seconds a traced second."""
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    spec = dict(cells.metric_file(ROOFLINE), scope="served/forward")
    got = scope_roofline.read(_facts(), spec)
    share = op_scope_share.scope_share(host_spans.newest_trace(spec),
                                       "served/forward")
    least = mhc_cost.least_seconds(
        cells.load_cell(CELL).config, xing4,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == 2_818_572_288 / 819e9
    want = 100 * least * (200 / 52.0) / (share * 7.9 / 8.0)
    assert got == pytest.approx(want, rel=1e-9)
    assert scope_roofline.read(_facts(peak=None), spec) is None
