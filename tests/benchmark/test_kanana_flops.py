"""The kanana2_30b_a3b configuration's arithmetic: its file against its
entry and the catalog row, the parameters this chip holds, the model
FLOPs ``step_mfu_pct.train`` divides and the work
``mla_attn_roofline_pct.train`` divides, against the numbers worked out
by hand from the published config."""

import json
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.reduce import mla_attention_cost
from benchmark.reference import kanana2

CONFIGS = Path(kanana2.__file__).resolve().parents[1] / "configs"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PAIRS = 33_558_528           # causal pairs of 8,192 positions


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIGS / "kanana2_30b_a3b.json") as f:
        return json.load(f)


def test_the_file_is_its_entrys(cfg):
    (entry,) = [c for c in cells.load_spec()["configs"]
                if c["name"] == "kanana2_30b_a3b"]
    assert entry["file"] == "benchmark/configs/kanana2_30b_a3b.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert cfg["program"]["model"] == "kanana2_ep8"
    assert set(cfg["limits"]["train"]) == {
        "loss_gap", "grad_gap", "update_gap", "bias_gap", "moe_dropped"}


def test_no_width_differs_from_the_catalog_row(cfg):
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    (row,) = [r for r in rows
              if r["name"] == "kanana-2-30b-a3b-instruct-2601"]
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "?") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs == set(cfg["reduced"])
    for key in differs:
        assert cfg["published"][key] == row["config"][key]
    # the widths the acceptance criteria name, as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"]) == (2048, 32)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"]) == (128, 64, 128, 512)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"]) == (
        6144, 768, 2, 6)
    assert cfg["routed_scaling_factor"] == 2.448
    # the deployment beside them, inside the guide's floors
    assert cfg["router_width"] == cfg["published"]["n_routed_experts"] == 128
    assert cfg["router_width"] // cfg["expert_share"][1] \
        == cfg["n_routed_experts"] == 16 >= 8
    assert cfg["vocab_size"] * cfg["expert_share"][1] \
        == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 5
    assert cfg["seq_len"] <= cfg["max_position_embeddings"]


def test_parameters_held_are_the_tables(cfg):
    held = kanana2.param_count(cfg)
    wq, wkva = 2048 * 32 * 192, 2048 * (512 + 64)
    wkvb, wo = 512 * 32 * (128 + 128), 32 * 128 * 2048
    assert (wq, wkva, wkvb, wo) == (12_582_912, 1_179_648, 4_194_304,
                                    8_388_608)
    attention = wq + wkva + wkvb + wo + 512            # the latent's norm
    assert attention == 26_345_984
    norms = 2 * 2048
    dense_mlp = 3 * 2048 * 6144
    assert dense_mlp == 37_748_736
    assert held["dense_layer"] == attention + dense_mlp + norms == 64_098_816
    shared, routed = 3 * 2048 * 1536, 16 * 3 * 2048 * 768
    assert (shared, routed) == (9_437_184, 16 * 4_718_592)
    router = 2048 * 128 + 128                          # and its bias
    layer = attention + shared + router + routed + norms
    assert layer == 111_547_008
    assert held["expert_layers"] == 5 * layer == 557_735_040
    assert held["embed_head"] == 2 * 16_032 * 2048 + 2048 == 65_669_120
    assert held["total"] == 687_502_976
    # 16 bytes a parameter: 11.0 GB of the chip's 16.9 GB (65%)
    assert held["total"] * 16 == pytest.approx(11.0e9, rel=1e-3)
    assert held["total"] * 16 / (15.75 * 2 ** 30) == pytest.approx(0.65,
                                                                   abs=5e-3)
    # the fallback of 1 + 4 layers the issue names
    assert held["total"] - layer == pytest.approx(576.0e6, rel=1e-4)


def test_model_flops_of_one_sample(cfg):
    parts = kanana2.forward_flops_parts(cfg)
    assert kanana2.causal_pairs(8192) == PAIRS
    assert parts["projections"] == 6 * 2 * 8192 * (
        12_582_912 + 1_179_648 + 4_194_304 + 8_388_608)
    assert parts["projections"] // 6 == pytest.approx(4.32e11, rel=2e-3)
    assert parts["attention"] == 6 * 2 * PAIRS * 32 * (192 + 128)
    assert parts["attention"] // 6 == pytest.approx(6.87e11, rel=1e-3)
    assert parts["shared"] == 5 * 2 * 8192 * 3 * 2048 * 1536
    assert parts["shared"] // 5 == pytest.approx(1.55e11, rel=3e-3)
    # 6 x 16 / 128 = 0.75 expected local routed experts a token
    assert parts["experts"] == 5 * int(2 * 8192 * 0.75 * 3 * 2048 * 768)
    assert parts["experts"] // 5 == pytest.approx(0.58e11, rel=2e-3)
    assert parts["router"] == 5 * 2 * 8192 * 2048 * 128
    assert parts["dense_mlp"] == 2 * 8192 * 3 * 2048 * 6144
    assert parts["dense_mlp"] == pytest.approx(6.18e11, rel=1e-3)
    assert parts["head"] == 2 * 8192 * 2048 * 16_032
    assert parts["head"] == pytest.approx(5.38e11, rel=1e-3)
    forward = kanana2.forward_flops_per_image(cfg)
    assert forward == sum(parts.values()) == 8_954_436_386_816
    assert kanana2.train_flops_per_image(cfg) == 3 * forward \
        == 26_863_309_160_448
    assert parts["attention"] / forward == pytest.approx(0.46, abs=5e-3)


def test_the_attentions_work_is_the_hand_count(cfg):
    operations, moved = mla_attention_cost.operations_and_bytes(cfg, kanana2)
    assert operations == 3 * 6 * 2 * 33_558_528 * 32 * (192 + 128)
    assert operations == pytest.approx(1.237e13, rel=1e-3)
    # q, k (192 wide), v, o (128 wide) of 32 heads in bf16 and their
    # gradients, once each, 6 layers
    assert moved == 2 * 6 * 2 * 8192 * 32 * (192 + 192 + 128 + 128)
    assert operations / 197e12 == pytest.approx(62.8e-3, rel=1e-3)
    assert moved / 819e9 == pytest.approx(4.9e-3, rel=5e-3)
    assert mla_attention_cost.least_seconds(cfg, kanana2, V5E) \
        == operations / 197e12
    # a slow memory makes the bytes bind
    slow = dict(V5E, hbm_bytes_per_s=1.0)
    assert mla_attention_cost.least_seconds(cfg, kanana2, slow) == moved


def test_the_toy_sizes_counts_are_the_hand_counts():
    with open(bench_helpers.FIXTURES
              / "benchmark/configs/kanana2_tiny.json") as f:
        tiny = json.load(f)
    held = kanana2.param_count(tiny)
    attention = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64 + 32
    assert held["dense_layer"] == attention + 3 * 64 * 96 + 2 * 64
    assert held["expert_layers"] == 2 * (
        attention + 3 * 64 * 32 + 64 * 8 + 8 + 4 * 3 * 64 * 32 + 2 * 64)
    operations, moved = mla_attention_cost.operations_and_bytes(tiny,
                                                                kanana2)
    assert operations == 3 * 3 * 2 * (64 * 65 // 2) * 4 * (24 + 16)
    assert moved == 2 * 3 * 2 * 64 * 4 * (24 + 24 + 16 + 16)
