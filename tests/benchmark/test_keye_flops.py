"""The keye_vl2_30b_a3b configuration's arithmetic: the parameters this
chip holds and the model FLOPs ``step_mfu_pct.train`` divides, against
the numbers worked out by hand from the published config."""

import json
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.reference import keye_vl2

CONFIGS = Path(keye_vl2.__file__).resolve().parents[1] / "configs"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIGS / "keye_vl2_30b_a3b.json") as f:
        return json.load(f)


def test_selected_pairs_of_an_8k_sequence():
    # all of them while t < 2048, then 2048 a query
    assert keye_vl2.selected_pairs(8192, 2048) == 14_681_088
    assert keye_vl2.selected_pairs(8192, 2048) == (
        2048 * 2049 // 2 + (8192 - 2048) * 2048)
    assert keye_vl2.selected_pairs(64, 100) == 64 * 65 // 2


def test_parameters_held_are_the_tables(cfg):
    held = keye_vl2.param_count(cfg)
    qkvo = 2048 * 128 * (32 + 4 + 4) + 32 * 128 * 2048          # 18.87 M
    indexer = 2048 * (16 * 64 + 64 + 16) + 2 * 64               # 2.26 M
    experts = 16 * 3 * 2048 * 768                               # 16 x 4.72 M
    norms = 2 * 2048 + 2 * 128
    layer = qkvo + indexer + 2048 * 128 + experts + norms
    assert layer == pytest.approx(96.9e6, rel=1e-3)
    assert held["decoder"] == 5 * layer + 2048 == 484_499_328
    assert held["embed_head"] == 2 * 18_992 * 2048 == 77_791_232
    tower_layer = 4 * (1152 * 1152 + 1152) + 2 * 1152 * 4304 + 4304 \
        + 1152 + 4 * 1152
    assert tower_layer == pytest.approx(15.2e6, rel=3e-3)
    projector = 4608 * 2048 + 2048 + 2048 * 2048 + 2048 + 2 * 4608
    assert projector == pytest.approx(13.6e6, rel=5e-3)
    assert held["vision"] == (6 * tower_layer + projector
                              + 588 * 1152 + 1152       # patch embedding
                              + 1024 * 1152 + 2 * 1152)  # positions, norm
    assert held["total"] == 669_232_864
    # 16 bytes a parameter: 10.7 GB of the chip's 16.9 GB
    assert held["total"] * 16 == pytest.approx(10.7e9, rel=2e-3)


def test_model_flops_of_one_sample(cfg):
    parts = keye_vl2.forward_flops_parts(cfg)
    per_layer = {k: parts[k] // 5 for k in
                 ("projections", "indexer", "attention", "router", "experts")}
    assert per_layer["projections"] == 2 * 8192 * 2048 * 128 * 72
    assert per_layer["attention"] == 4 * 14_681_088 * 32 * 128
    assert per_layer["indexer"] == (2 * 8192 * 2048 * (1024 + 64 + 16)
                                    + 2 * (8192 * 8193 // 2) * 1024)
    # one expected local expert a token: 8 of 128, 16 held
    assert per_layer["experts"] == 2 * 8192 * 3 * 2048 * 768
    assert sum(per_layer.values()) == pytest.approx(7.37e11, rel=2e-3)
    assert parts["head"] == 2 * 7936 * 2048 * 18_992
    assert parts["tower"] + parts["projector"] == pytest.approx(2.3e11,
                                                                rel=0.03)
    forward = keye_vl2.forward_flops_per_image(cfg)
    assert forward == sum(parts.values()) == 4_527_536_209_920
    assert keye_vl2.train_flops_per_image(cfg) == 3 * forward


def test_no_width_differs_from_the_catalog_row(cfg):
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    (row,) = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs <= set(cfg["reduced"])
    for key in differs:
        assert cfg["published"][key] == row["config"][key]
    assert cfg["num_local_experts"] // cfg["expert_share"][1] \
        == cfg["num_experts"]
    assert cfg["vocab_size"] * cfg["expert_share"][1] \
        == cfg["published"]["vocab_size"]
