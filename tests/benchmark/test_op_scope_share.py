"""``readers/op_scope_share`` against the trace recorded on the chip
beside ``reduce/host_spans.py``: given the served program's scope it
reads what ``scope_time_share`` reads there, by its own reduction."""

import shutil
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.readers import op_scope_share
from benchmark.reduce import host_spans

REDUCE = Path(host_spans.__file__).resolve().parent
RECORDED = REDUCE / "recorded_spans.xplane.pb"
WITHOUT_SCOPES = REDUCE / "recorded_tiny.xplane.pb"
NEW = ("indexer_time_pct.train", "select_time_pct.train",
       "attn_time_pct.train", "moe_time_pct.train", "vision_time_pct.train")


def _as_the_traced_run(monkeypatch, tmp_path, recorded, cell):
    d = tmp_path / cell / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "host.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)


@pytest.mark.parametrize("scope", ["served/postprocess", "served/forward"])
def test_it_reads_what_scope_time_share_reads(monkeypatch, tmp_path, scope):
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED,
                       "yolov3.serve_steady")
    accepted = cells.metric_file("nms_time_pct.serve")
    accepted = dict(accepted, scope=scope)
    want = cells.reader_for(accepted).read({}, accepted)
    spec = dict(accepted, reader="op_scope_share")
    got = op_scope_share.read({}, spec)
    assert want is not None and 0 < want < 100
    assert got == pytest.approx(want, rel=1e-9)


def test_a_scope_no_operation_carries_reads_nothing(monkeypatch, tmp_path):
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED,
                       "yolov3.serve_steady")
    spec = dict(cells.metric_file("nms_time_pct.serve"),
                scope="lm/attn/select")
    assert op_scope_share.read({}, spec) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_leaves_the_metric_out(
        monkeypatch, tmp_path, name):
    """The parent's program has no such scope, and no trace is no trace:
    the reader returns nothing and does not raise."""
    spec = cells.metric_file(name)
    assert spec["reader"] == "op_scope_share" and spec["scope"]
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)
    assert op_scope_share.read({}, spec) is None
    _as_the_traced_run(monkeypatch, tmp_path, WITHOUT_SCOPES,
                       "keye_vl2_30b_a3b.train_seq8k")
    assert op_scope_share.read({}, spec) is None


@pytest.mark.parametrize("name", NEW + ("moe_load_skew.train",))
def test_each_new_metric_file_equals_its_entry(name):
    spec = cells.metric_file(name)
    (entry,) = [m for m in cells.load_spec()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == ["keye_vl2_30b_a3b.train_seq8k"]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (name, key)
    assert hasattr(cells.reader_for(spec), "read") and spec["what"]


def test_moe_load_skew_reads_the_last_steps_counts():
    spec = cells.metric_file("moe_load_skew.train")
    read = cells.reader_for(spec).read
    assert read({"train": {"moe_expert_tokens_max": 2600.0,
                           "moe_expert_tokens_mean": 2048.0}}, spec) \
        == pytest.approx(1.26953125)
    assert read({"train": {"images": 4}}, spec) is None     # resnet's facts
    assert read({}, spec) is None
