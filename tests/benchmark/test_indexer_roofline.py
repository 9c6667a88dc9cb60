"""``indexer_roofline_pct.train``: the count of the work against a hand
count at the toy size and at the cell's, the reader against a trace
recorded on the chip, and nothing where there is nothing to read."""

import json
import shutil
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.readers import op_scope_share, scope_roofline
from benchmark.reduce import host_spans, indexer_cost
from benchmark.reference import keye_vl2

NAME = "indexer_roofline_pct.train"
CELL = "keye_vl2_30b_a3b.train_seq8k"
REDUCE = Path(host_spans.__file__).resolve().parent
RECORDED = REDUCE / "recorded_spans.xplane.pb"
WITHOUT_SCOPES = REDUCE / "recorded_tiny.xplane.pb"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the cell's: 8,192 positions, hidden 2,048, 16 heads of 64, 5 layers
CAUSAL = 8192 * 8193 // 2
SCORES = 2 * CAUSAL * 16 * 64
PROJECTIONS = 2 * 8192 * 2048 * (16 * 64 + 64 + 16)


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


def _facts(images=112, window_s=53.0, peak=197e12):
    return {"train": {"images": images, "window_s": window_s, "chips": 1,
                      "peak_flops": peak},
            "trace": {"busy_s": 5.9, "window_s": 6.0}}


def test_the_cells_count_is_the_hand_count(cfg):
    operations, moved = indexer_cost.operations_and_bytes(cfg, keye_vl2)
    # the two terms are the reference's count of the indexer
    assert 5 * (SCORES + PROJECTIONS) \
        == keye_vl2.forward_flops_parts(cfg)["indexer"]
    assert SCORES == pytest.approx(68.7e9, rel=1e-3)
    assert PROJECTIONS == pytest.approx(37.0e9, rel=2e-3)
    # scores x 3, projections x 2 (their input is detached), 5 layers
    assert operations == 5 * (3 * SCORES + 2 * PROJECTIONS)
    assert operations == pytest.approx(1.40e12, rel=2e-3)       # a sample
    # qi and ki in bf16 and w in float32 in, the float32 scores over the
    # causal pairs out, three passes
    layer = 2 * 8192 * (16 * 64 + 64) + 4 * 8192 * 16 + 4 * CAUSAL
    assert moved == 3 * 5 * layer
    assert operations / 197e12 == pytest.approx(7.1e-3, rel=3e-3)
    assert moved / 819e9 == pytest.approx(2.8e-3, rel=3e-3)
    assert indexer_cost.least_seconds(cfg, keye_vl2, V5E) \
        == operations / 197e12


def test_the_toy_sizes_count_is_the_hand_count(tiny):
    operations, moved = indexer_cost.operations_and_bytes(tiny, keye_vl2)
    sa, t = tiny["sa_config"], keye_vl2.sizes(tiny)["seq"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    d, layers = tiny["hidden_size"], tiny["num_hidden_layers"]
    causal = sum(i + 1 for i in range(t))
    scores = 2 * causal * heads * width
    projections = 2 * t * d * (heads * width + width + heads)
    assert operations == layers * (3 * scores + 2 * projections)
    narrow = {"float32": 4, "bfloat16": 2}[tiny["compute_dtype"]]
    assert moved == 3 * layers * (
        narrow * t * (heads * width + width) + 4 * t * heads + 4 * causal)
    # a slow memory makes the bytes bind
    slow = dict(V5E, hbm_bytes_per_s=1.0)
    assert indexer_cost.least_seconds(tiny, keye_vl2, slow) == moved


@pytest.mark.parametrize("scope", ["served/forward", "served/postprocess"])
def test_the_reader_on_a_recorded_trace(monkeypatch, tmp_path, scope):
    """Least seconds a sample x samples a second over the scope's busy
    seconds a traced second, the scope's share by ``op_scope_share``."""
    _as_the_traced_run(monkeypatch, tmp_path, RECORDED)
    spec = dict(cells.metric_file(NAME), scope=scope)
    got = scope_roofline.read(_facts(), spec)
    share = op_scope_share.scope_share(host_spans.newest_trace(spec), scope)
    assert 0 < share < 1
    least = 5 * (3 * SCORES + 2 * PROJECTIONS) / 197e12
    want = 100 * least * (112 / 53.0) / (share * 5.9 / 6.0)
    assert got == pytest.approx(want, rel=1e-9)
    # half the rate in the same scope time is half the share
    assert scope_roofline.read(_facts(images=56), spec) \
        == pytest.approx(got / 2, rel=1e-9)


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


def test_the_entry_equals_the_metric_file():
    spec = cells.metric_file(NAME)
    entry, = (e for e in cells.load_spec()["per_layer"]
              if e["name"] == NAME)
    assert entry["workloads"] == [CELL]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert (spec["unit"], spec["better"], spec["source"]) == (
        "%", "higher", "device_trace")
    assert cells.reader_for(spec) is scope_roofline and spec["what"]
    assert spec["cost"] == "indexer_cost"
    # the same scope as the accepted share of the busy time, on the
    # accepted cell's configuration
    accepted = cells.metric_file("indexer_time_pct.train")
    assert (spec["scope"], spec["layer"], spec["moves"]) == (
        accepted["scope"], accepted["layer"], accepted["moves"])
    assert spec["config"] == cells.load_cell(CELL).config_name
    assert NAME in {m["name"] for m in cells.load_cell(CELL).per_layer}
