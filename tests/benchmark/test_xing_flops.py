"""The xing4_29b_a4b configuration's arithmetic: its file against its
entry, the parameters this chip holds, the model FLOPs
``step_mfu_pct.train`` divides and the work ``mhc_roofline_pct.train``
divides, against the numbers worked out by hand from the published
config."""

import json
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.harness import cells
from benchmark.reduce import mhc_cost
from benchmark.reference import xing4

CONFIGS = Path(xing4.__file__).resolve().parents[1] / "configs"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "num_attention_heads", "vocab_size"]
# a block's attention: q_a, q_norm, q_b (4 heads of 192), kv_a, kv_norm,
# kv_b (4 heads of 128 + 128), o
ATTENTION = (3584 * 768 + 768 + 768 * 4 * 192 + 3584 * 576 + 512
             + 512 * 4 * 256 + 4 * 128 * 3584)
HYPER = 14336 * 24 + 3 + 24          # phi, alpha, b of one sublayer
EXPERT = 3 * 3584 * 1024             # one SiLU-gated expert of 1,024


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIGS / "xing4_29b_a4b.json") as f:
        return json.load(f)


def test_the_file_is_its_entrys(cfg):
    (entry,) = [c for c in cells.load_spec()["configs"]
                if c["name"] == "xing4_29b_a4b"]
    assert entry["file"] == "benchmark/configs/xing4_29b_a4b.json"
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"]
    assert set(cfg["reduced_how"]) == set(REDUCED) == set(cfg["published"])
    assert cfg["program"]["model"] == "xing4_ep8tp8"
    assert set(cfg["limits"]["train"]) == {
        "loss_gap", "grad_gap", "update_gap", "bias_gap", "moe_dropped"}


def test_the_published_widths_and_the_deployment(cfg):
    assert cfg["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "num_attention_heads": 32,
        "vocab_size": 131072}
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]) \
        == (3584, 768, 512)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]) == (128, 64, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"]) == (
        9216, 1024, 1, 4)
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
            cfg["num_nextn_predict_layers"]) == (4, 20, 1e-6, 1)
    assert cfg["rope_scaling"]["factor"] == 64
    # 8 chips share a layer: heads, experts and vocabulary rows are an
    # eighth each, within the guide's floors
    assert cfg["head_share"] == cfg["expert_share"] == [0, 8]
    assert cfg["num_attention_heads"] * 8 == 32
    assert cfg["router_width"] == 64 == 8 * cfg["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == 131072
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4


def test_parameters_held_are_the_tables(cfg):
    held = xing4.param_count(cfg)
    assert ATTENTION == 7_767_296 and 2 * HYPER == 688_182
    norms = 2 * 3584
    dense = ATTENTION + 2 * HYPER + norms + 3 * 3584 * 9216
    assert held["dense_layers"] == dense == 107_553_078
    router = 3584 * 64 + 64                          # and its bias
    block = ATTENTION + 2 * HYPER + norms + router + EXPERT + 8 * EXPERT
    assert block == 107_782_518
    assert held["expert_layers"] == 4 * block
    # M, one expert block, three norms
    assert held["mtp"] == 7168 * 3584 + block + 3 * 3584 == 133_483_382
    assert held["embed_head"] == 2 * 16384 * 3584 + 3584 == 117_444_096
    assert held["total"] == 789_610_628
    # 16 bytes a parameter: 12.63 GB, 74.7% of the chip's 16.9 GB
    assert held["total"] * 16 == pytest.approx(12.63e9, rel=1e-3)


def test_model_flops_of_one_sample(cfg):
    t, pairs = 2048, 2048 * 2049 // 2
    parts = xing4.forward_flops_parts(cfg)
    projections = ATTENTION - 768 - 512              # the norms multiply not
    assert parts["projections"] == 6 * 2 * t * projections
    assert parts["attention"] == 6 * 2 * pairs * 4 * (192 + 128)
    # the maps' product and the three mixes, two sublayers a block
    assert parts["hyper_connections"] == 6 * 2 * 2 * t * (
        14336 * 24 + 14336 + 4 * 14336 + 14336)
    assert parts["router"] == 5 * 2 * t * 3584 * 64
    assert parts["shared"] == 5 * 2 * t * EXPERT
    # 4 x 8 / 64 = 0.5 expected local routed experts a token
    assert parts["experts"] == 5 * int(2 * t * 0.5 * EXPERT)
    assert parts["dense_mlp"] == 2 * t * 3 * 3584 * 9216
    assert parts["mtp_proj"] == 2 * t * 7168 * 3584
    # the main head over 2,048 positions and the MTP's over 2,047
    assert parts["head"] == 2 * (2 * t - 1) * 3584 * 16384
    forward = xing4.forward_flops_per_image(cfg)
    assert forward == sum(parts.values()) == 1_579_171_184_640
    assert xing4.train_flops_per_image(cfg) == 3 * forward


def test_the_hyper_connections_work_is_the_hand_count(cfg):
    operations, moved = mhc_cost.operations_and_bytes(cfg, xing4)
    assert operations == 3 * xing4.forward_flops_parts(cfg)[
        "hyper_connections"] == 63_417_876_480
    # 4 streams of 3,584 of 2,048 positions in bf16, read and written
    # forward and backward, around 2 sublayers of 6 blocks
    assert moved == 6 * 2 * 4 * (2 * 2048 * 4 * 3584) == 2_818_572_288
    assert mhc_cost.least_seconds(cfg, xing4, V5E) == moved / 819e9
    assert moved / 819e9 == pytest.approx(3.44e-3, rel=1e-3)
    # a slow multiplier makes the operations bind
    slow = dict(V5E, bf16_flops_per_s=1.0)
    assert mhc_cost.least_seconds(cfg, xing4, slow) == operations


def test_the_toy_sizes_counts_are_the_hand_counts():
    with open(bench_helpers.FIXTURES
              / "benchmark/configs/xing4_tiny.json") as f:
        tiny = json.load(f)
    held = xing4.param_count(tiny)
    attention = (64 * 24 + 24 + 24 * 2 * 24 + 64 * 40 + 32 + 32 * 2 * 32
                 + 2 * 16 * 64)
    hyper = 256 * 24 + 3 + 24
    assert held["dense_layers"] == attention + 2 * hyper + 2 * 64 \
        + 3 * 64 * 96
    block = attention + 2 * hyper + 2 * 64 + 64 * 8 + 8 + 5 * 3 * 64 * 32
    assert held["expert_layers"] == 2 * block
    assert held["mtp"] == 128 * 64 + block + 3 * 64
    operations, moved = mhc_cost.operations_and_bytes(tiny, xing4)
    assert moved == 4 * 2 * 4 * (2 * 64 * 4 * 64)
    assert operations == 3 * 4 * 2 * 2 * 64 * (256 * 24 + 256 + 4 * 256
                                                + 256)
