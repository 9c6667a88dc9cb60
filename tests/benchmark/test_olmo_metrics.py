"""The cell ``olmo_hybrid_7b.serve_longprompt``: its files against their
entries and lists, the delta rule's count by hand, and the roofline
reader where there is nothing to read and where there is."""

import json

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.readers import gen_scope_roofline, op_scope_share
from benchmark.reduce import gdn_cost, host_spans
from benchmark.reference import olmo_hybrid

CELL = "olmo_hybrid_7b.serve_longprompt"
NEW = {"gdn_time_pct.serve": ("op_scope_share", "linear attention"),
       "gdn_roofline_pct.serve": ("gen_scope_roofline", "linear attention"),
       "ttft_p95_ms.serve": ("serve_telemetry", "generation scheduler"),
       "decode_rows_mean.serve": ("serve_telemetry",
                                  "generation scheduler")}
CONFIG = cells.ROOT / "benchmark" / "configs" / "olmo_hybrid_7b.json"


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_file_equals_its_entry(name):
    spec = cells.metric_file(name)
    (entry,) = [m for m in cells.load_spec()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == [CELL]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (name, key)
    assert entry["moves"] == "serve_p95_ms"
    assert (spec["reader"], spec["layer"]) == NEW[name]
    assert hasattr(cells.reader_for(spec), "read") and spec["what"]


def test_the_cell_loads_with_its_files_and_stands_on_the_serving_lists():
    from benchmark.drivers import serve_generate

    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_generate"
    assert cells.driver_for(cell) is serve_generate
    assert cells.reference_for(cell.config, cell.config_name) is olmo_hybrid
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_p95_ms", "serve_goodput", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {"compile_s", *NEW}
    t = cell.traffic
    assert (t["prompt_min"], t["prompt_max"], t["max_new_tokens"],
            t["slots"], t["max_len"], t["prefill_chunk"]) == (
        2048, 16384, 128, 16, 16512, 2048)
    spec = cells.load_spec()
    for m in spec["end_to_end"]:
        if m["name"] in ("serve_p95_ms", "serve_goodput"):
            assert m["workloads"][-1] == CELL
    for m in spec["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", ("compile_s",)), m["name"]


def test_the_configuration_is_the_published_one_cut_in_depth():
    cfg = json.loads(CONFIG.read_text())
    (entry,) = [c for c in cells.load_spec()["configs"]
                if c["name"] == "olmo_hybrid_7b"]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["layer_types"][:8] == cfg["layer_types"]
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 2
    widths = {"hidden_size": 3840, "intermediate_size": 11008,
              "num_attention_heads": 30, "num_key_value_heads": 30,
              "linear_num_key_heads": 30, "linear_num_value_heads": 30,
              "linear_key_head_dim": 96, "linear_value_head_dim": 192,
              "linear_conv_kernel_dim": 4, "vocab_size": 100352}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["rope_theta"] == 500000 and "rope_theta" in cfg["assumed"]
    assert olmo_hybrid.param_count(cfg) == 2_435_748_072
    assert set(cfg["controls"]) == {"bf16_state", "conv_reset"}
    assert set(cfg["limits"]["serve"]) == {"precision_excess"}


def test_the_delta_rules_count_by_hand():
    """6 linear layers of 30 heads, dk 96, dv 192, sub-chunks of 64."""
    cfg = json.loads(CONFIG.read_text())
    c, dk, dv = 64, 96, 192
    per_chunk = (c * 63 * (2 * dk + dv) + c * 65 * (dk + dv)
                 + 6 * c * dk * dv)
    assert per_chunk == 1_548_288 + 1_198_080 + 7_077_888
    assert gdn_cost.prefill_flops_per_token(cfg) == 180 * per_chunk / 64
    assert gdn_cost.prefill_flops_per_token(cfg) == 27_630_720
    assert gdn_cost.decode_bytes_per_row(cfg) == 180 * 8 * dk * dv
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert gdn_cost.least_seconds(cfg, 1000, 10, peaks) == pytest.approx(
        1000 * 27_630_720 / 197e12 + 10 * 26_542_080 / 819e9)


def _facts(peak=197e12):
    return {"gen": {"prefill_tokens": 20_000, "decode_rows": 1_000,
                    "peak_flops": peak},
            "trace": {"busy_s": 3.5, "window_s": 4.0}}


def test_the_roofline_reads_nothing_where_there_is_nothing(monkeypatch,
                                                            tmp_path):
    spec = cells.metric_file("gdn_roofline_pct.serve")
    reader = cells.reader_for(spec)
    assert reader.read({}, spec) is None
    assert reader.read(_facts(peak=None), spec) is None       # a CPU run
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)   # no trace
    assert reader.read(_facts(), spec) is None
    monkeypatch.setattr(host_spans, "newest_trace", lambda s: "a.xplane.pb")
    monkeypatch.setattr(op_scope_share, "scope_share", lambda p, s: None)
    assert reader.read(_facts(), spec) is None    # a parent without lm/gdn


def test_the_roofline_divides_the_least_time_by_the_scopes(monkeypatch):
    spec = cells.metric_file("gdn_roofline_pct.serve")
    monkeypatch.setattr(host_spans, "newest_trace", lambda s: "a.xplane.pb")
    monkeypatch.setattr(op_scope_share, "scope_share",
                        lambda p, s: 0.2 if s == "lm/gdn" else None)
    cfg = json.loads(CONFIG.read_text())
    least = gdn_cost.least_seconds(
        cfg, 20_000, 1_000, {"bf16_flops_per_s": 197e12,
                             "hbm_bytes_per_s": 819e9})
    want = 100 * least / (0.2 * 3.5)
    assert gen_scope_roofline.read(_facts(), spec) == pytest.approx(want)
    assert 0 < want < 100
