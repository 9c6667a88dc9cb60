"""The per-layer metrics ``kanana2_30b_a3b.train_text8k`` brings: each
file against its entry, each reader on a trace recorded on the chip and
where there is nothing to read (a parent without the scopes, no trace, a
CPU run), and the cell on the lists a training cell is appended to. Two
of the files (the attention's share of the busy time and of its
roofline) are held without an entry until the accepted reader sees the
whole scope (PERF.md section 7); their readers are tested all the
same."""

import shutil
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.readers import op_scope_share, scope_roofline
from benchmark.reduce import host_spans, mla_attention_cost
from benchmark.reference import kanana2

CELL = "kanana2_30b_a3b.train_text8k"
REDUCE = Path(host_spans.__file__).resolve().parent
RECORDED = REDUCE / "recorded_spans.xplane.pb"
WITHOUT_SCOPES = REDUCE / "recorded_tiny.xplane.pb"
SCOPES = {"mla_proj_time_pct.train": "lm/mla/proj",
          "mla_attn_time_pct.train": "lm/mla/attn",
          "moe_routed_time_pct.train": "lm/moe/experts",
          "moe_shared_time_pct.train": "lm/moe/shared"}
ROOFLINE = "mla_attn_roofline_pct.train"
SKEW = "moe_bias_load_skew.train"
HELD = ("mla_attn_time_pct.train", ROOFLINE)
NEW = tuple(n for n in (*SCOPES, SKEW) if n not in HELD)
SHARED_LISTS = ("train_img_per_s", "step_mfu_pct.train",
                "conv_time_pct.train", "device_idle_pct.train")


def _as_the_traced_run(monkeypatch, tmp_path, recorded):
    d = tmp_path / CELL / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "host.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)


def _facts(images=56, window_s=52.0, peak=197e12):
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
    assert entry["moves"] == "train_img_per_s"
    assert hasattr(cells.reader_for(spec), "read") and spec["what"]
    if name in SCOPES:
        assert (spec["reader"], spec["scope"], spec["unit"]) == (
            "op_scope_share", SCOPES[name], "%")


@pytest.mark.parametrize("name", HELD)
def test_a_held_metric_has_its_file_and_no_entry(name):
    spec = cells.metric_file(name)
    assert "Held" in spec["what"] and spec["scope"] == "lm/mla/attn"
    assert hasattr(cells.reader_for(spec), "read")
    assert name not in {m["name"] for m in cells.load_spec()["per_layer"]}


def test_the_cell_loads_with_its_files_and_stands_on_the_shared_lists():
    from benchmark.drivers import train_resident_lm

    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_resident_lm"
    assert cells.driver_for(cell) is train_resident_lm
    assert cells.reference_for(cell.config, cell.config_name) is kanana2
    assert [m["name"] for m in cell.end_to_end] == ["train_img_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "compile_s", *SHARED_LISTS[1:], *NEW}
    assert (cell.traffic["checked_steps"], cell.traffic["in_flight"],
            cell.traffic["trace_seconds"]) == (2, 2, 8.0)
    spec = cells.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in SHARED_LISTS:
            assert m["workloads"][-1] == CELL, m["name"]
        elif m["name"] not in NEW:
            assert CELL not in m.get("workloads", ()), m["name"]
    # resnet50.train_dp4 is not a cell yet: under resnet50.json's limits
    # its fp8 control read correct at 1,024 rows (PERF.md section 7)
    assert all(w["chips"] == 1 for w in spec["workloads"])


@pytest.mark.parametrize("name", [*SCOPES, ROOFLINE])
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


@pytest.mark.parametrize("name", SCOPES)
def test_a_scope_share_reads_a_recorded_trace(monkeypatch, tmp_path, name):
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    spec = dict(cells.metric_file(name), scope="served/forward")
    got = op_scope_share.read({}, spec)
    path = host_spans.newest_trace(spec)
    assert got == pytest.approx(
        100 * op_scope_share.scope_share(path, "served/forward"), rel=1e-9)
    assert 0 < got < 100


def test_the_roofline_reader_on_a_recorded_trace(monkeypatch, tmp_path):
    """Least seconds a sample x samples a second over the scope's busy
    seconds a traced second."""
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    spec = dict(cells.metric_file(ROOFLINE), scope="served/forward")
    assert spec["cost"] == "mla_attention_cost"
    assert spec["config"] == cells.load_cell(CELL).config_name
    got = scope_roofline.read(_facts(), spec)
    share = op_scope_share.scope_share(host_spans.newest_trace(spec),
                                       "served/forward")
    least = mla_attention_cost.least_seconds(
        cells.load_cell(CELL).config, kanana2,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == 3 * 6 * 2 * 33_558_528 * 32 * (192 + 128) / 197e12
    want = 100 * least * (56 / 52.0) / (share * 7.9 / 8.0)
    assert got == pytest.approx(want, rel=1e-9)
    # a CPU run and an unknown chip read nothing
    assert scope_roofline.read(_facts(peak=None), spec) is None
    assert scope_roofline.read(_facts(peak=123e12), spec) is None
    # the same scope as the share of the busy time, on the same layer
    share_spec = cells.metric_file("mla_attn_time_pct.train")
    real = cells.metric_file(ROOFLINE)
    assert (real["scope"], real["layer"]) == (share_spec["scope"],
                                              share_spec["layer"])


def test_the_load_skew_reads_the_last_steps_counts():
    spec = cells.metric_file(SKEW)
    read = cells.reader_for(spec).read
    assert read({"train": {"moe_expert_tokens_max": 960.0,
                           "moe_expert_tokens_mean": 768.0}}, spec) == 1.25
    assert read({"train": {"images": 4}}, spec) is None     # resnet's facts
    assert read({}, spec) is None
