"""The text token model (models/latent_moe.py) against its plain
reference (benchmark/reference/kanana2.py) at a toy size on the CPU:
hidden 64, 1 dense + 2 expert layers, 4 heads of 24/16 with a rotary
part of 8 and a latent of 32, 8 experts top-2 behind a selection bias
with one shared expert, 64 positions; seeded random weights."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import kanana2 as ref  # noqa: E402
from benchmark.reference import plain  # noqa: E402
from deepvision_tpu.models import get_model  # noqa: E402
from deepvision_tpu.models import latent_moe as L  # noqa: E402
from deepvision_tpu.models import transformer as T  # noqa: E402

CFG = json.loads((ROOT / "tests/benchmark/fixtures/benchmark/configs/"
                  "kanana2_tiny.json").read_text())
F32 = jnp.float32
GAMMA = CFG["bias_update_rate"]


@pytest.fixture(scope="module")
def seeded():
    key = plain.seed_key(2 ** 31 + 5)
    weights = jax.jit(lambda k: ref.make_weights(CFG, k))(key)
    batch = jax.jit(lambda k: ref.make_batch(CFG, k, 2))(key)
    return weights, batch


def _with_bias(weights, bias):
    return {**weights, "layers": {**weights["layers"], "moe": {
        **weights["layers"]["moe"], "bias": jnp.asarray(bias, F32)}}}


def _program_loss(model, params, batch):
    out = model.apply({"params": params}, batch, train=True)
    return jnp.mean(out["nll"]), out


def _leaf_gaps(got, want):
    """Per leaf, the norm of the difference over the norm of the
    reference's leaf, or over the median leaf's where that is larger
    (the selection bias's gradient is 0)."""
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
        (value, stats), grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(CFG, p, batch), has_aux=True))(weights)
    return float(value), stats, grads


def test_the_programs_tree_is_the_references(seeded):
    from benchmark.harness import checks

    model = get_model("kanana2_tiny")
    assert model.sample_input().keys() == {"tokens"}
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model.sample_input()))
    checks.require_same_tree(shapes["params"], seeded[0], "parameter")
    # the leading dense layer is not part of the scanned stack
    assert shapes["params"]["dense"]["mlp"]["gate"].shape == (64, 96)
    assert shapes["params"]["layers"]["moe"]["bias"].shape == (2, 8)


@pytest.mark.parametrize("biased", [False, True])
def test_float32_logits_and_counts_match_the_reference(seeded, biased):
    weights, batch = seeded
    if biased:      # a bias large enough to move choices
        weights = _with_bias(weights, 0.05 * np.random.default_rng(0)
                             .standard_normal((2, 8)))
    model = get_model("kanana2_tiny", dtype=F32, capture=True)
    out = jax.jit(lambda p, b: model.apply({"params": p}, b, logits=True))(
        weights, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda t: ref.forward_sample(
            CFG, weights, t, capture=True)))(batch["tokens"])
    np.testing.assert_allclose(out["logits"], want["logits"], atol=2e-5)
    np.testing.assert_allclose(out["nll"], want["nll"], atol=2e-5)
    np.testing.assert_array_equal(out["expert_counts"],
                                  want["expert_counts"])
    np.testing.assert_array_equal(np.sort(out["experts"], -1),
                                  np.sort(want["experts"], -1))
    # the held experts are the first half of the eight
    np.testing.assert_array_equal(out["expert_tokens"],
                                  np.asarray(want["expert_counts"])[..., :4])
    assert int(jnp.max(out["moe_dropped"])) == 0
    # every token chooses two experts in each of the two expert layers
    assert np.asarray(out["expert_counts"]).sum((1, 2)).tolist() == [256] * 2
    assert np.asarray(out["causal_pairs"]).tolist() == [3 * 64 * 65 // 2] * 2


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    # float32 against float32 at HIGHEST: rounding order only
    ("float32", 1e-5, 1e-4),
    # bf16 operands round to 2^-9. Over five seeds the worst leaf reads
    # 0.008 where no routing choice flips and 0.05-0.17 where one does
    # (a held expert sees about 32 tokens here, so one token more or
    # less is seen in its gradient); the reference with fp8 operands
    # reads 0.19-0.35. This seed: 0.17 against 0.33
    ("bfloat16", 5e-3, 0.25),
])
def test_loss_and_gradients_match_the_reference(seeded, reference_grads,
                                                dtype, loss_tol, grad_tol):
    weights, batch = seeded
    want_loss, _stats, want = reference_grads
    model = get_model("kanana2_tiny", dtype=jnp.dtype(dtype))
    (value, _out), grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, batch), has_aux=True))(weights)
    assert abs(float(value) - want_loss) / want_loss < loss_tol
    gaps = _leaf_gaps(grads, want)
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < grad_tol, (worst, gaps[worst])
    # no gradient reaches the selection bias, in program or reference
    assert not np.any(np.asarray(grads["layers"]["moe"]["bias"]))
    assert not np.any(np.asarray(want["layers"]["moe"]["bias"]))


def _latent_inputs(t=64, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (t, 4, 24), F32)
    k = jax.random.normal(ks[1], (t, 4, 24), F32)
    v = jax.random.normal(ks[2], (t, 4, 16), F32)
    return q, k, v


@pytest.mark.parametrize("blocks", [(64, 64), (32, 8), (16, 16)])
def test_causal_attention_is_plain_multi_head_attention(blocks):
    q, k, v = _latent_inputs()
    got = L.causal_attention(q, k, v, key_block=blocks[0],
                             q_chunk=blocks[1], dtype=F32)
    logits = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(24.0)
    logits = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), logits, -jnp.inf)
    want = jnp.einsum("hts,shd->thd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(got, want.reshape(64, 64), atol=2e-5)


def test_the_latent_attention_is_attention_over_the_materialised_heads(
        seeded):
    """The module's output equals plain multi-head attention over
    ``k_h = [k_nope_h | k_rope]`` and ``v_h`` built by hand from its
    weights: one rotary key for all heads, heads of 24 for the scores and
    of 16 for the values."""
    weights, _ = seeded
    p = weights["dense"]["attn"]
    h = jax.random.normal(jax.random.key(3), (1, 64, 64), F32)
    angles = T.rope_angles(64, 4, 1e6)
    cfg = L.LatentConfig(
        heads=4, nope_dim=16, rope_dim=8, v_dim=16, kv_rank=32,
        dense_width=96, num_experts=8, experts_per_token=2,
        expert_share=(0, 2), moe_width=32, shared_experts=1, norm_topk=True,
        gate_scale=2.448, rms_eps=1e-6, key_block=32, q_chunk=8, dtype=F32)
    got = L._LatentAttention(cfg).apply({"params": p}, h, angles)[0]

    x = h[0]
    q = (x @ p["q"]).reshape(64, 4, 24)
    c = x @ p["kv_a"]
    c_kv = T.rms_norm(c[:, :32], p["kv_norm"]["scale"], 1e-6)
    kv = (c_kv @ p["kv_b"]).reshape(64, 4, 32)
    k_rope = T.rotate(c[:, None, 32:], angles)               # [64, 1, 8]
    q = jnp.concatenate([q[..., :16], T.rotate(q[..., 16:], angles)], -1)
    k = jnp.concatenate([kv[..., :16], jnp.tile(k_rope, (1, 4, 1))], -1)
    v = kv[..., 16:]
    logits = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(24.0)
    logits = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), logits, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(logits, -1), v)
    want = o.reshape(64, 64) @ p["o"]
    np.testing.assert_allclose(got, want, atol=2e-5)


def _route(h, router, bias=None, **kwargs):
    return T.route(h, router, experts_per_token=2, norm_topk=True,
                   scoring="sigmoid", bias=bias, gate_scale=2.448, **kwargs)


def test_the_bias_moves_the_choice_and_not_the_gates():
    ks = jax.random.split(jax.random.key(0), 2)
    h = jax.random.normal(ks[0], (96, 64), F32)
    router = 0.1 * jax.random.normal(ks[1], (64, 8), F32)
    scores = jax.nn.sigmoid(h @ router)
    plain_e, plain_g = _route(h, router, jnp.zeros(8))
    np.testing.assert_array_equal(plain_e, _route(h, router)[0])
    # gates sum to the scale, and are the chosen scores renormalised
    np.testing.assert_allclose(jnp.sum(plain_g, -1), 2.448, rtol=1e-6)
    picked = jnp.take_along_axis(scores, plain_e, -1)
    np.testing.assert_allclose(
        plain_g, 2.448 * picked / jnp.sum(picked, -1, keepdims=True),
        rtol=1e-5)
    # a large bias on expert 5 puts it into every token's choice ...
    bias = jnp.zeros(8).at[5].set(10.0)
    experts, gates = _route(h, router, bias)
    assert bool(jnp.all(jnp.any(experts == 5, -1)))
    assert not bool(jnp.all(jnp.any(plain_e == 5, -1)))
    # ... and the gates are still the unbiased scores of the chosen
    picked = jnp.take_along_axis(scores, experts, -1)
    np.testing.assert_allclose(
        gates, 2.448 * picked / jnp.sum(picked, -1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.448, rtol=1e-6)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: jnp.sum(_route(h, router, b)[1] ** 2))(bias)
    assert not np.any(np.asarray(grad))


def test_the_softmax_router_is_what_it_was():
    """``keye_vl2``'s call: softmax, no bias, no scale."""
    ks = jax.random.split(jax.random.key(1), 2)
    h = jax.random.normal(ks[0], (32, 64), F32)
    router = jax.random.normal(ks[1], (64, 8), F32)
    experts, gates = T.route(h, router, experts_per_token=2, norm_topk=True)
    probs = jax.nn.softmax(
        jnp.dot(h, router, precision=jax.lax.Precision.HIGHEST), -1)
    top, want = jax.lax.top_k(probs, 2)
    np.testing.assert_array_equal(experts, want)
    np.testing.assert_allclose(gates, top / jnp.sum(top, -1, keepdims=True),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="scoring rule"):
        T.route(h, router, experts_per_token=2, norm_topk=True,
                scoring="tanh")


def _moe_weights(seed=0, experts=8, d=64, f=32):
    ks = jax.random.split(jax.random.key(seed), 9)
    w = lambda k, *shape: 0.1 * jax.random.normal(k, shape, F32)
    return {"router": jax.random.normal(ks[0], (d, experts), F32),
            "bias": 0.05 * jax.random.normal(ks[1], (experts,), F32),
            "gate": w(ks[2], experts, d, f), "up": w(ks[3], experts, d, f),
            "down": w(ks[4], experts, f, d),
            "shared": {"gate": w(ks[5], d, f), "up": w(ks[6], d, f),
                       "down": w(ks[7], f, d)},
            "h": jax.random.normal(ks[8], (96, d), F32)}


def _moe_share(p, index, of, capacity_factor=2.0):
    held = p["gate"].shape[0] // of
    sl = slice(index * held, (index + 1) * held)
    return T.moe_layer(
        p["h"], p["router"], p["gate"][sl], p["up"][sl], p["down"][sl],
        experts_per_token=2, norm_topk=True, expert_share=(index, of),
        capacity_factor=capacity_factor, dtype=F32, scoring="sigmoid",
        bias=p["bias"], gate_scale=2.448)


def _moe_uncut(p):
    cfg = dict(CFG, expert_share=[0, 1])
    with jax.default_matmul_precision("highest"):
        return ref.moe(cfg, p, p["h"], plain.HIGHEST)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Every share ``(i, 8)`` summed over ``i``, the shared expert
    counted once, equals the uncut reference's layer."""
    p = _moe_weights()
    want, counts, _experts = _moe_uncut(p)
    total, counted = 0.0, []
    for i in range(8):
        out, experts, dropped = _moe_share(p, i, 8)
        assert int(dropped) == 0
        total = total + out
        counted.append(int(jnp.sum(experts == i)))
    s = p["shared"]
    shared = L.gated_mlp(p["h"], s["gate"], s["up"], s["down"], F32)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    assert counted == np.asarray(counts).tolist()
    assert sum(counted) == 96 * 2


def test_a_bias_that_sends_every_token_to_one_expert_drops_none_and_agrees():
    p = _moe_weights(seed=3)
    # experts 0 and 1 take every token by the bias alone: both are held
    # by share (0, 4), which then sees 4 times its expected load and
    # takes the worst-case rows instead of the usual ones
    p["bias"] = jnp.zeros(8).at[:2].set(5.0)
    want, counts, _ = _moe_uncut(p)
    assert np.asarray(counts).tolist() == [96, 96, 0, 0, 0, 0, 0, 0]
    shared = L.gated_mlp(p["h"], *(p["shared"][n] for n in
                                   ("gate", "up", "down")), F32)
    out, experts, dropped = _moe_share(p, 0, 4, capacity_factor=1.25)
    assert int(dropped) == 0
    np.testing.assert_allclose(out + shared, want, atol=5e-5)
    # the same with rows for the worst case only
    again, _e, dropped = _moe_share(p, 0, 4, capacity_factor=100.0)
    assert int(dropped) == 0
    np.testing.assert_allclose(again, out, atol=1e-6)


def _state(model, weights, tx):
    from deepvision_tpu.train.state import TrainState

    return TrainState(step=jnp.zeros((), jnp.int32), params=weights,
                      batch_stats={}, opt_state=tx.init(weights),
                      apply_fn=model.apply, tx=tx)


def test_a_step_moves_the_bias_by_the_rule_and_nothing_else_does(seeded):
    """After a step an overloaded expert's entry has fallen by gamma and
    an underloaded one's risen; Adam left the leaf alone."""
    import optax

    from deepvision_tpu.train.steps import lm_train_step

    weights, batch = seeded
    model = get_model("kanana2_tiny", dtype=F32)
    counts = np.asarray(jnp.sum(model.apply(
        {"params": weights}, batch, train=True)["expert_counts"], 0))
    state = _state(model, weights, optax.adam(1e-3))
    new, metrics = jax.jit(lm_train_step)(state, batch, jax.random.key(0))
    bias = np.asarray(new.params["layers"]["moe"]["bias"])
    mean = counts.mean(-1, keepdims=True)            # 2 x 128 / 8 = 32
    assert mean.ravel().tolist() == [32.0, 32.0]
    np.testing.assert_allclose(bias, GAMMA * np.sign(mean - counts),
                               rtol=1e-6)
    assert (bias[counts > mean.repeat(8, -1)] < 0).all()
    assert (bias[counts < mean.repeat(8, -1)] > 0).all()
    assert float(metrics["moe_bias_abs_mean"]) == pytest.approx(
        np.abs(bias).mean(), rel=1e-6)
    mu = optax.tree_utils.tree_get(new.opt_state, "mu")
    assert not np.any(np.asarray(mu["layers"]["moe"]["bias"]))
    # a second step reads the moved bias and moves it again
    newer, _ = jax.jit(lm_train_step)(new, batch, jax.random.key(0))
    assert np.abs(np.asarray(
        newer.params["layers"]["moe"]["bias"])).max() <= 2 * GAMMA * 1.0001


@pytest.mark.parametrize("dtype,tol", [
    ("float32", 1e-4),
    # bf16: Adam's first update is the rate x sign(gradient), so a
    # leaf's move differs by 2 sqrt(the share of entries whose sign
    # flipped). Over three seeds the worst leaf reads 0.09-0.28 (an
    # expert's or the bias's, where a routing choice flipped), the
    # reference with fp8 operands 0.40-0.65. This seed: 0.28 against 0.65
    ("bfloat16", 0.45),
])
def test_one_whole_step_matches_the_reference(seeded, dtype, tol):
    """Adam and the bias rule: parameters after one step of the
    program's compiled step against the reference's ``train_steps``."""
    from benchmark.drivers import train_resident_lm as driver
    from deepvision_tpu.core import create_mesh

    weights, batch = seeded
    cfg = dict(CFG, compute_dtype=dtype)
    p0 = jax.tree.map(np.asarray, weights)
    step, make_state = driver.build_program(cfg, create_mesh(1, 1), weights)
    state, metrics = step(make_state(jax.tree.map(jnp.asarray, p0)), batch,
                          jax.random.key(0))
    with jax.default_matmul_precision("highest"):
        losses, _first, after = ref.train_steps(
            cfg, jax.tree.map(jnp.asarray, p0), batch, 1)
    assert float(metrics["loss"]) == pytest.approx(float(losses[0]),
                                                   rel=5e-3)
    moved = lambda p: jax.tree.map(lambda a, b: np.asarray(a) - b, p, p0)
    gaps = _leaf_gaps(moved(state.params), moved(after))
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < tol, (worst, gaps[worst])
    if dtype == "float32":      # the same counts, so the same signs
        np.testing.assert_allclose(state.params["layers"]["moe"]["bias"],
                                   after["layers"]["moe"]["bias"], atol=1e-9)


def test_the_step_reports_its_counts_and_the_registry_folds_them(seeded):
    import optax

    from deepvision_tpu.obs.metrics import Registry, record_token_step
    from deepvision_tpu.train.steps import lm_train_step

    weights, batch = seeded
    model = get_model("kanana2_tiny")
    state = _state(model, weights, optax.adam(1e-3))
    new, metrics = jax.jit(lm_train_step)(state, batch, jax.random.key(0))
    host = {k: float(v) for k, v in metrics.items()}
    assert host["moe_dropped"] == 0 and np.isfinite(host["loss"])
    assert "index_loss" not in host and "dsa_selected_pairs" not in host
    # 2 samples x 64 tokens x 2 choices, half of the experts held, 2 layers
    assert 0.5 * 256 < host["moe_local_assignments"] < 1.5 * 256
    assert host["moe_expert_tokens_max"] >= host["moe_expert_tokens_mean"]
    assert host["attn_causal_pairs"] == 2 * 3 * 64 * 65 // 2
    assert 0 < host["moe_bias_abs_mean"] <= GAMMA * 1.0001
    assert int(new.step) == 1
    reg = Registry()
    record_token_step(host, reg)
    record_token_step(host, reg)
    record_token_step({"loss": 1.0}, reg)        # a conv model's step
    assert reg.value_of("attn_causal_pairs") == 2 * host["attn_causal_pairs"]
    assert reg.value_of("moe_bias_abs_mean") == host["moe_bias_abs_mean"]
    assert set(reg.names()) == {
        "moe_local_assignments", "moe_dropped", "attn_causal_pairs",
        "moe_expert_tokens_max", "moe_expert_tokens_mean",
        "moe_bias_abs_mean"}


def test_train_py_trains_saves_and_resumes_with_the_bias_carried(tmp_path):
    import subprocess

    base = [sys.executable, str(ROOT / "train.py"), "-m", "kanana2_tiny",
            "--platform", "cpu", "--synthetic-size", "32",
            "--steps-per-epoch", "2", "--workdir", str(tmp_path)]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TF_CPP_MIN_LOG_LEVEL": "2",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    first = subprocess.run(base + ["--epochs", "1"], capture_output=True,
                           text=True, env=env, timeout=600)
    assert first.returncode == 0, first.stderr[-2000:]
    assert "[epoch 0]" in first.stdout and "train_moe_dropped=0" in \
        first.stdout
    # two steps of the rule: every entry within 2 gamma, the mean under it
    assert "train_moe_bias_abs_mean=0.00" in first.stdout
    assert (tmp_path / "kanana2_tiny" / "ckpt").is_dir()
    again = subprocess.run(base + ["--epochs", "2", "--resume"],
                           capture_output=True, text=True, env=env,
                           timeout=600)
    assert again.returncode == 0, again.stderr[-2000:]
    assert "resumed at epoch 1" in again.stdout
    assert "[epoch 1]" in again.stdout and "[epoch 0]" not in again.stdout
    # the bias came back with the checkpoint and the rule went on from
    # it: after four steps its mean magnitude has passed what two steps
    # from zero can reach
    carried = float(again.stdout.split("train_moe_bias_abs_mean=")[1]
                    .split()[0])
    before = float(first.stdout.split("train_moe_bias_abs_mean=")[1]
                   .split()[0])
    assert carried > before and carried > 2 * GAMMA * 0.5
