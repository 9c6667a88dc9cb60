"""``sparse_attn_roofline_pct.train``: the count of the work against a
hand count at the toy size and at the cell's, the reader against a
trace recorded on the chip, and nothing where there is nothing to
read."""

import json
import shutil
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.readers import op_scope_share, scope_roofline
from benchmark.reduce import host_spans, sparse_attention_cost
from benchmark.reference import keye_vl2

NAME = "sparse_attn_roofline_pct.train"
CELL = "keye_vl2_30b_a3b.train_seq8k"
REDUCE = Path(host_spans.__file__).resolve().parent
RECORDED = REDUCE / "recorded_spans.xplane.pb"
WITHOUT_SCOPES = REDUCE / "recorded_tiny.xplane.pb"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _config(cells.ROOT / "benchmark/configs/keye_vl2_30b_a3b.json")


@pytest.fixture(scope="module")
def tiny():
    return _config(bench_helpers.FIXTURES
                   / "benchmark/configs/keye_vl2_tiny.json")


def _as_the_traced_run(monkeypatch, tmp_path, recorded):
    d = tmp_path / CELL / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "host.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)


def _facts(images=72, window_s=54.0, peak=197e12):
    return {"train": {"images": images, "window_s": window_s, "chips": 1,
                      "peak_flops": peak},
            "trace": {"busy_s": 5.9, "window_s": 6.0}}


def test_the_cells_count_is_the_hand_count(cfg):
    operations, moved = sparse_attention_cost.operations_and_bytes(
        cfg, keye_vl2)
    # logits and values over the selected pairs, x 3, 5 layers
    assert operations == 3 * 5 * 4 * 14_681_088 * 32 * 128
    assert 2 * operations == pytest.approx(7.2e12, rel=3e-3)   # a step of 2
    # q and o (32 heads), k and v (4) in bf16; scores in and target out
    # over the 33.6 M causal pairs in float32
    layer = (2 * 32 + 2 * 4) * 8192 * 128 * 2 + 2 * 4 * (8192 * 8193 // 2)
    assert moved == 3 * 5 * layer
    assert operations / 197e12 == pytest.approx(18.3e-3, rel=3e-3)
    assert moved / 819e9 == pytest.approx(7.7e-3, rel=3e-3)
    assert sparse_attention_cost.least_seconds(cfg, keye_vl2, V5E) \
        == operations / 197e12


def test_the_toy_sizes_count_is_the_hand_count(tiny):
    operations, moved = sparse_attention_cost.operations_and_bytes(
        tiny, keye_vl2)
    t, topk = keye_vl2.sizes(tiny)["seq"], tiny["sa_config"]["topk"]
    heads, groups = tiny["num_attention_heads"], tiny["num_key_value_heads"]
    hd, layers = tiny["head_dim"], tiny["num_hidden_layers"]
    pairs = sum(min(i + 1, topk) for i in range(t))
    assert operations == 3 * layers * 2 * 2 * pairs * heads * hd
    width = {"float32": 4, "bfloat16": 2}[tiny["compute_dtype"]]
    assert moved == 3 * layers * (
        2 * (heads + groups) * t * hd * width + 8 * (t * (t + 1) // 2))
    # a slow memory makes the bytes bind
    slow = dict(V5E, hbm_bytes_per_s=1.0)
    assert sparse_attention_cost.least_seconds(tiny, keye_vl2, slow) == moved


@pytest.mark.parametrize("scope", ["served/forward", "served/postprocess"])
def test_the_reader_on_a_recorded_trace(monkeypatch, tmp_path, cfg, scope):
    """Least seconds a sample x samples a second over the scope's busy
    seconds a traced second, the scope's share by ``op_scope_share``."""
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    spec = dict(cells.metric_file(NAME), scope=scope)
    facts = _facts()
    got = scope_roofline.read(facts, spec)
    path = host_spans.newest_trace(spec)
    share = op_scope_share.scope_share(path, scope)
    assert 0 < share < 1
    least = 3 * 5 * 4 * 14_681_088 * 32 * 128 / 197e12
    want = 100 * least * (72 / 54.0) / (share * 5.9 / 6.0)
    assert got == pytest.approx(want, rel=1e-9)
    # twice the rate in the same scope time is twice the share
    assert scope_roofline.read(_facts(images=144), spec) \
        == pytest.approx(2 * got, rel=1e-9)


@pytest.mark.parametrize("why", ["no_trace", "no_operation_in_the_scope",
                                 "a_cpu_run", "resnets_facts",
                                 "an_unknown_chip"])
def test_nothing_to_read_is_none_never_zero(monkeypatch, tmp_path, why):
    spec = cells.metric_file(NAME)
    facts = _facts()
    if why == "no_trace":
        monkeypatch.setattr(host_spans, "TRACE_ROOT", tmp_path)
    elif why == "no_operation_in_the_scope":    # the parent of PR 28
        _as_the_traced_run(monkeypatch, tmp_path, WITHOUT_SCOPES)
    else:
        _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
        spec = dict(spec, scope="served/forward")
        assert scope_roofline.read(facts, spec) is not None
        if why == "a_cpu_run":
            facts["train"]["peak_flops"] = None
        elif why == "resnets_facts":
            facts = {"train": facts["train"]}
        else:
            facts["train"]["peak_flops"] = 123e12
    assert scope_roofline.read(facts, spec) is None


def test_the_metric_file_is_ready_for_its_entry():
    """The files are in place; the entry in ``BENCHMARK.json`` waits for
    a ``benchmark`` PR (``test_run_keye`` pins the cell's per-layer set
    with ``==``: PERF.md section 7). Where the entry is, it equals the
    file."""
    spec = cells.metric_file(NAME)
    for entry in cells.load_spec()["per_layer"]:
        if entry["name"] == NAME:
            assert entry["workloads"] == [CELL]
            for key in ("name", "unit", "better", "source", "layer",
                        "moves"):
                assert spec[key] == entry[key], key
    assert (spec["name"], spec["unit"], spec["better"], spec["source"]) == (
        NAME, "%", "higher", "device_trace")
    assert (spec["layer"], spec["moves"]) == ("sparse attention",
                                              "train_img_per_s")
    assert cells.reader_for(spec) is scope_roofline and spec["what"]
    # the same scope as the accepted share of the busy time, on the
    # accepted cell's configuration
    accepted = cells.metric_file("attn_time_pct.train")
    assert (spec["scope"], spec["layer"]) == (accepted["scope"],
                                              accepted["layer"])
    assert spec["config"] == cells.load_cell(CELL).config_name
