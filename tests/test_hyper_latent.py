"""The hyper-connected token model (models/hyper_latent.py) against its
plain reference (benchmark/reference/xing4.py) at a toy size on the CPU:
hidden 64 in 4 streams, 1 dense + 2 expert blocks and the MTP block, 2
of 4 heads held (24/16 wide, a query latent of 24, a key latent of 32),
4 of 8 experts top-2 behind a selection bias with one shared expert, 64
positions; seeded random weights."""

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import kanana2  # noqa: E402
from benchmark.reference import plain  # noqa: E402
from benchmark.reference import xing4 as ref  # noqa: E402
from deepvision_tpu.models import get_model  # noqa: E402
from deepvision_tpu.models import hyper_latent as H  # noqa: E402
from deepvision_tpu.models import latent_moe as L  # noqa: E402
from deepvision_tpu.models import transformer as T  # noqa: E402

CFG = json.loads((ROOT / "tests/benchmark/fixtures/benchmark/configs/"
                  "xing4_tiny.json").read_text())
F32 = jnp.float32
GAMMA = CFG["bias_update_rate"]
BIASES = (("layers", "moe", "bias"), ("mtp", "block", "moe", "bias"))


@pytest.fixture(scope="module")
def seeded():
    key = plain.seed_key(2 ** 31 + 5)
    weights = jax.jit(lambda k: ref.make_weights(CFG, k))(key)
    batch = jax.jit(lambda k: ref.make_batch(CFG, k, 2))(key)
    return weights, batch


def _leaf_gaps(got, want):
    """Per leaf, the norm of the difference over the norm of the
    reference's leaf, or over the median leaf's where that is larger."""
    g, w = plain.tree_paths(got), plain.tree_paths(want)
    assert set(g) == set(w)
    norm = lambda a: float(np.linalg.norm(np.asarray(a, np.float64)))
    floor = float(np.median([norm(v) for v in w.values()]))
    return {k: norm(np.asarray(g[k], np.float64)
                    - np.asarray(w[k], np.float64))
            / max(norm(w[k]), floor) for k in w}


@pytest.fixture(scope="module")
def reference_grads(seeded):
    weights, batch = seeded
    with jax.default_matmul_precision("highest"):
        (value, _), grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(CFG, p, batch), has_aux=True))(weights)
    return value, grads


def _program_loss(model, params, batch, weight=CFG["mtp_loss_weight"]):
    out = model.apply({"params": params}, batch, train=True)
    return jnp.mean(out["nll"]) + weight * jnp.mean(out["mtp_nll"]), out


def test_the_programs_tree_is_the_references(seeded):
    from benchmark.harness import checks

    model = get_model("xing4_tiny")
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model.sample_input()))
    checks.require_same_tree(shapes["params"], seeded[0], "parameter")
    p = shapes["params"]
    # 2 of 4 heads held: q_b's and kv_b's columns, o's rows
    assert p["layers"]["attn"]["q_b"].shape == (2, 24, 2 * 24)
    assert p["layers"]["attn"]["o"].shape == (2, 2 * 16, 64)
    # one map for H_pre, H_post and H_res of 4 streams
    assert p["dense"]["attn_hc"]["phi"].shape == (1, 4 * 64, 24)
    assert p["mtp"]["block"]["moe"]["bias"].shape == (8,)
    assert p["mtp"]["proj"].shape == (128, 64)


@pytest.mark.parametrize("biased", [False, True])
def test_float32_losses_logits_and_counts_match_the_reference(seeded,
                                                               biased):
    weights, batch = seeded
    if biased:      # biases large enough to move choices
        rng = np.random.default_rng(0)
        weights = jax.tree.map(lambda a: a, weights)
        weights["layers"]["moe"]["bias"] = jnp.asarray(
            0.05 * rng.standard_normal((2, 8)), F32)
        weights["mtp"]["block"]["moe"]["bias"] = jnp.asarray(
            0.05 * rng.standard_normal(8), F32)
    model = get_model("xing4_tiny", dtype=F32, capture=True)
    out = jax.jit(lambda p, b: model.apply({"params": p}, b, logits=True))(
        weights, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda t: ref.forward_sample(
            CFG, weights, t, capture=True)))(batch["tokens"])
    for name in ("logits", "nll", "mtp_nll"):
        np.testing.assert_allclose(out[name], want[name], atol=2e-5)
    assert out["mtp_nll"].shape == (2, 63)
    np.testing.assert_array_equal(out["expert_counts"],
                                  want["expert_counts"])
    np.testing.assert_array_equal(np.sort(out["experts"], -1),
                                  np.sort(want["experts"], -1))
    np.testing.assert_array_equal(out["expert_tokens"],
                                  np.asarray(want["expert_counts"])[..., :4])
    # 2 expert blocks and the MTP block, every token choosing two
    assert out["expert_counts"].shape == (2, 3, 8)
    assert np.asarray(out["expert_counts"]).sum((1, 2)).tolist() == [384] * 2
    assert int(jnp.max(out["moe_dropped"])) == 0
    assert np.asarray(out["causal_pairs"]).tolist() == [4 * 64 * 65 // 2] * 2
    assert 0 <= float(jnp.max(out["mhc_sinkhorn_err"])) < 1e-5


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    # float32 against float32 at HIGHEST: rounding order only
    ("float32", 1e-5, 1e-4),
    # bf16 operands and streams: over three seeds the worst leaf reads
    # 0.01-0.05 (a router's or an expert's where a choice flips)
    ("bfloat16", 5e-3, 0.25),
])
def test_loss_and_gradients_match_the_reference(seeded, reference_grads,
                                                dtype, loss_tol, grad_tol):
    weights, batch = seeded
    want_loss, want = reference_grads
    model = get_model("xing4_tiny", dtype=jnp.dtype(dtype))
    (value, _out), grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, batch), has_aux=True))(weights)
    assert abs(float(value) - float(want_loss)) / float(want_loss) < loss_tol
    gaps = _leaf_gaps(grads, want)
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < grad_tol, (worst, gaps[worst])
    # the maps of every hyper-connection are trained
    assert float(jnp.max(jnp.abs(grads["mtp"]["block"]["moe_hc"]["phi"]))) > 0
    for path in BIASES:       # no gradient reaches a selection bias
        leaf = grads
        for k in path:
            leaf = leaf[k]
        assert not np.any(np.asarray(leaf))


# ------------------------------------------------------- the share test


def _attention_weights(heads, seed=0, d=64, qr=24, rank=32, dn=16, dr=8,
                       dv=16):
    ks = jax.random.split(jax.random.key(seed), 8)
    w = lambda k, *s: 0.2 * jax.random.normal(k, s, F32)
    return {"q_a": w(ks[0], d, qr),
            "q_norm": {"scale": 1 + 0.1 * jax.random.normal(ks[1], (qr,))},
            "q_b": w(ks[2], qr, heads * (dn + dr)),
            "kv_a": w(ks[3], d, rank + dr),
            "kv_norm": {"scale": 1 + 0.1 * jax.random.normal(ks[4], (rank,))},
            "kv_b": w(ks[5], rank, heads * (dn + dv)),
            "o": w(ks[6], heads * dv, d)}


def _heads_of(p, index, of, dq=24, dkv=32, dv=16):
    """The weights of share ``(index, of)``: its heads' columns of q_b
    and kv_b, its heads' rows of o; the rest is replicated."""
    held = p["q_b"].shape[1] // dq // of
    cols = lambda a, w: a[:, index * held * w:(index + 1) * held * w]
    return {**p, "q_b": cols(p["q_b"], dq), "kv_b": cols(p["kv_b"], dkv),
            "o": p["o"][index * held * dv:(index + 1) * held * dv]}


def test_the_heads_shares_add_up_to_the_uncut_attention():
    """Share ``(i, 8)`` of 16 heads holds 2; its output is its heads' part
    of ``o``. Summed over ``i`` they are the uncut reference's attention
    (the projections every chip computes alike counted once: they are
    inside each share's part and add nothing of their own)."""
    p = _attention_weights(16)
    h = jax.random.normal(jax.random.key(4), (1, 64, 64), F32)
    cfg = dict(CFG, num_attention_heads=16)
    angles = ref.rope_angles(cfg, 64)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(cfg, p, h[0], angles, plain.HIGHEST)
    total = 0.0
    for i in range(8):
        c = L.LatentConfig(
            heads=16, nope_dim=16, rope_dim=8, v_dim=16, kv_rank=32,
            dense_width=96, num_experts=8, experts_per_token=2,
            expert_share=(0, 2), moe_width=32, shared_experts=1,
            norm_topk=True, gate_scale=2.0, rms_eps=1e-6, key_block=32,
            q_chunk=8, dtype=F32, q_rank=24, head_share=(i, 8),
            softmax_scale=ref.softmax_scale(cfg))
        total = total + L._LatentAttention(c).apply(
            {"params": _heads_of(p, i, 8)}, h, angles)[0]
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_the_experts_shares_add_up_to_the_uncut_layer():
    """Each share ``(i, 8)`` of 64 experts holds 8; summed over ``i``,
    the shared expert (replicated) counted once, they are the uncut
    reference's expert layer."""
    ks = jax.random.split(jax.random.key(2), 9)
    w = lambda k, *s: 0.1 * jax.random.normal(k, s, F32)
    d, f, e = 64, 32, 64
    p = {"router": jax.random.normal(ks[0], (d, e), F32),
         "bias": 0.05 * jax.random.normal(ks[1], (e,), F32),
         "gate": w(ks[2], e, d, f), "up": w(ks[3], e, d, f),
         "down": w(ks[4], e, f, d),
         "shared": {"gate": w(ks[5], d, f), "up": w(ks[6], d, f),
                    "down": w(ks[7], f, d)}}
    h = jax.random.normal(ks[8], (96, d), F32)
    cfg = dict(CFG, router_width=e, num_experts_per_tok=4,
               expert_share=[0, 1])
    with jax.default_matmul_precision("highest"):
        want, counts, _ = kanana2.moe(cfg, p, h, plain.HIGHEST)
    total = 0.0
    for i in range(8):
        sl = slice(8 * i, 8 * (i + 1))
        out, _experts, dropped = T.moe_layer(
            h, p["router"], p["gate"][sl], p["up"][sl], p["down"][sl],
            experts_per_token=4, norm_topk=True, expert_share=(i, 8),
            dtype=F32, scoring="sigmoid", bias=p["bias"], gate_scale=2.0)
        assert int(dropped) == 0
        total = total + out
    s = p["shared"]
    shared = L.gated_mlp(h, s["gate"], s["up"], s["down"], F32)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    assert int(np.sum(counts)) == 96 * 4


# ---------------------------------------------------- hyper-connections


def test_sinkhorn_gives_doubly_stochastic_matrices_and_the_clamp_holds():
    # logits as the seeded b_res gives them and a trained map may: 20
    # rounds bring rows and columns to 1 within a few hc_eps (with logits
    # of std 2 the rows are still 0.03 off after 20 rounds)
    logits = 0.5 * jax.random.normal(jax.random.key(0), (256, 4, 4), F32)
    m = H.sinkhorn(jnp.exp(logits), 20, 1e-6)
    err = H.sinkhorn_error(m)
    assert float(jnp.max(err)) < 10 * 4 * 1e-6
    assert float(jnp.min(m)) > 0
    np.testing.assert_allclose(m, ref.sinkhorn(jnp.exp(logits), 20, 1e-6),
                               rtol=1e-6)
    # logits far past the clamp stay finite: exp(+-30) at most
    maps = H.HyperMaps(H.HyperConfig(4, 20, 1e-6, 30.0, 1e-6))
    x = jax.random.normal(jax.random.key(1), (1, 8, 4, 16), F32)
    params = maps.init(jax.random.key(2), x)["params"]
    params = dict(params, b=params["b"].at[8:].set(
        jnp.linspace(-1e4, 1e4, 16)))
    pre, post, res, err = maps.apply({"params": params}, x)
    assert np.all(np.isfinite(np.asarray(res)))
    np.testing.assert_allclose(jnp.sum(res, -2), 1.0, atol=1e-5)
    assert pre.shape == post.shape == (1, 8, 4) and err.shape == (1,)
    assert float(jnp.min(post)) >= 0 and float(jnp.max(post)) <= 2


def test_a_sublayer_sees_the_pre_mix_and_writes_through_the_post_mix():
    """``X' = H_res X + H_post^T F(H_pre X)`` written out by hand."""
    maps = H.HyperMaps(H.HyperConfig(4, 20, 1e-6, 30.0, 1e-6))
    x = jax.random.normal(jax.random.key(3), (2, 8, 4, 16), F32)
    params = maps.init(jax.random.key(4), x)["params"]
    bound = maps.bind({"params": params})
    seen = {}

    def sublayer(u):
        seen["u"] = u
        return jnp.tanh(u), "aux"

    out, _err, aux = H.hyper_sublayer(bound, x, sublayer, F32)
    assert aux == "aux"
    pre, post, res, _ = bound(x)
    np.testing.assert_allclose(seen["u"], jnp.einsum("btn,btnc->btc", pre, x),
                               atol=1e-5)
    want = (jnp.einsum("btnm,btmc->btnc", res, x)
            + post[..., None] * jnp.tanh(seen["u"])[:, :, None])
    np.testing.assert_allclose(out, want, atol=1e-5)


# ------------------------------------------------------------- yarn


def test_yarn_angles_and_scale_at_the_published_shapes():
    cfg = json.loads((ROOT / "benchmark/configs/xing4_29b_a4b.json")
                     .read_text())
    inv = ref.yarn_inverse_frequencies(cfg)
    extra = 10000.0 ** (-np.arange(32) / 32)
    # pairs 0-10 keep their frequency, 23-31 are divided by 64, a ramp
    # over (i - 10) / 13 between
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], extra[23:] / 64, rtol=1e-12)
    ramp = (15 - 10) / 13
    assert inv[15] == pytest.approx(extra[15] / 64 * ramp
                                    + extra[15] * (1 - ramp))
    got = T.yarn_angles(4096, 32, 10000.0, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_array_equal(got, ref.rope_angles(cfg, 4096))
    assert ref.softmax_scale(cfg) == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192))
    assert T.yarn_mscale(64.0, 1.0) ** 2 == pytest.approx(2.00475, rel=1e-5)


# ------------------------------------------------------ MTP and the step


def test_the_mtp_module_predicts_the_token_after_next(seeded):
    """A token changed at ``j`` leaves the MTP losses of positions before
    ``j - 2`` alone and changes that of ``j - 2``, whose label it is."""
    weights, batch = seeded
    model = get_model("xing4_tiny", dtype=F32)
    run = jax.jit(lambda b: model.apply({"params": weights}, b))
    j = 40
    other = batch["tokens"].at[:, j].set((batch["tokens"][:, j] + 1) % 128)
    a, b = run(batch), run({"tokens": other})
    np.testing.assert_array_equal(a["mtp_nll"][:, :j - 2],
                                  b["mtp_nll"][:, :j - 2])
    assert np.all(np.asarray(a["mtp_nll"][:, j - 2] != b["mtp_nll"][:, j - 2]))
    np.testing.assert_array_equal(a["nll"][:, :j - 1], b["nll"][:, :j - 1])
