"""The token model (models/transformer.py) against its plain reference
(benchmark/reference/keye_vl2.py) at a toy size on the CPU: hidden 64,
2 decoder layers, 8 experts top-2, top-16 sparse attention at 64
positions, a 2-layer tower; seeded random weights."""

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

from benchmark.reference import keye_vl2 as ref  # noqa: E402
from benchmark.reference import plain  # noqa: E402
from deepvision_tpu.models import get_model  # noqa: E402
from deepvision_tpu.models import transformer as T  # noqa: E402

CFG = json.loads((ROOT / "tests/benchmark/fixtures/benchmark/configs/"
                  "keye_vl2_tiny.json").read_text())
F32 = jnp.float32


@pytest.fixture(scope="module")
def seeded():
    key = plain.seed_key(2 ** 31 + 5)
    weights = jax.jit(lambda k: ref.make_weights(CFG, k))(key)
    batch = jax.jit(lambda k: ref.make_batch(CFG, k, 2))(key)
    return weights, batch


def _program_loss(model, params, batch):
    from deepvision_tpu.train.steps import _vlm_losses

    out = model.apply({"params": params}, batch, train=True)
    return _vlm_losses(out, CFG["index_loss_weight"])[0], out


def _leaf_gaps(got, want):
    """Per leaf, the norm of the difference over the norm of the
    reference's leaf, or over the median leaf's where that is larger: a
    key bias of softmax attention has a gradient of nought but rounding."""
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

    model = get_model("keye_vl2_tiny")
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model.sample_input()))
    checks.require_same_tree(shapes["params"], seeded[0], "parameter")


def test_float32_logits_and_counts_match_the_reference(seeded):
    weights, batch = seeded
    model = get_model("keye_vl2_tiny", dtype=F32, capture=True)
    out = jax.jit(lambda p, b: model.apply({"params": p}, b, logits=True))(
        weights, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda i, t: ref.forward_sample(
            CFG, weights, i, t, capture=True)))(batch["image"],
                                                batch["tokens"])
    np.testing.assert_allclose(out["logits"], want["logits"], atol=2e-5)
    np.testing.assert_allclose(out["nll"], want["nll"], atol=2e-5)
    np.testing.assert_allclose(out["index_kl"], want["index_kl"], rtol=1e-5)
    np.testing.assert_array_equal(out["masks"], want["masks"])
    np.testing.assert_array_equal(out["expert_tokens"],
                                  want["expert_tokens"])
    np.testing.assert_array_equal(out["selected_pairs"],
                                  want["selected_pairs"])
    assert int(jnp.max(out["moe_dropped"])) == 0
    # ties at the threshold (a score of exactly 0 where every relu is
    # shut) are all kept, so the count is the formula's or a little more
    least = CFG["num_hidden_layers"] * ref.selected_pairs(
        64, CFG["sa_config"]["topk"])
    assert np.all(np.asarray(out["selected_pairs"]) >= least)
    assert np.all(np.asarray(out["selected_pairs"]) < 1.02 * least)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    # float32 against float32 at HIGHEST: rounding order only
    ("float32", 1e-5, 2e-3),
    # bf16 operands round to 2^-9. At 64 positions and top-16 one
    # flipped key is a sixteenth of a query's set, and the gradients of
    # q, k and the indexer are small differences of near-uniform
    # softmaxes: their leaves read 0.13-0.28 over seeds (median leaf
    # 0.02), where the reference with fp8 operands reads 0.45-0.5
    ("bfloat16", 5e-3, 0.35),
])
def test_loss_and_gradients_match_the_reference(seeded, reference_grads,
                                                dtype, loss_tol, grad_tol):
    weights, batch = seeded
    want_loss, _stats, want = reference_grads
    model = get_model("keye_vl2_tiny", dtype=jnp.dtype(dtype))
    (value, _out), grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(model, p, batch), has_aux=True))(weights)
    assert abs(float(value) - want_loss) / want_loss < loss_tol
    gaps = _leaf_gaps(grads, want)
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < grad_tol, (worst, gaps[worst])


def test_each_loss_reaches_only_its_own_parameters(seeded):
    weights, batch = seeded
    model = get_model("keye_vl2_tiny", dtype=F32)

    def part(name):
        return jax.jit(jax.grad(lambda p: jnp.mean(model.apply(
            {"params": p}, batch, train=True)[name])))(weights)

    lm = plain.tree_paths(part("nll"))
    index = plain.tree_paths(part("index_kl"))
    for path in lm:
        is_indexer = "indexer" in path
        lm_zero = not np.any(np.asarray(lm[path]))
        index_zero = not np.any(np.asarray(index[path]))
        assert lm_zero == is_indexer, path
        assert index_zero == (not is_indexer), path


def _attention_inputs(t=64, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (t, 4, 16), F32)
    k = jax.random.normal(ks[1], (t, 2, 16), F32)
    v = jax.random.normal(ks[2], (t, 2, 16), F32)
    qi = jax.random.normal(ks[3], (t, 4, 16), F32)
    ki = jax.random.normal(ks[4], (t, 16), F32)
    w = jax.random.normal(ks[5], (t, 4), F32) * 0.125
    return q, k, v, qi, ki, w


def _masked(args, topk, key_block=32, q_chunk=8):
    blocks = dict(key_block=key_block, q_chunk=q_chunk, dtype=F32)
    thr = T.selection_thresholds(*args[3:], topk=topk, **blocks)
    return T.sparse_attention(*args, thr, **blocks)[:3]


@pytest.mark.parametrize("topk", [16, 64, 100])
def test_masked_and_gathered_attention_agree(topk):
    args = _attention_inputs()
    o_m, kl_m, n_m = _masked(args, topk)
    o_g, kl_g, n_g = T.gathered_attention(*args, topk=topk, dtype=F32)
    np.testing.assert_allclose(o_m, o_g, atol=2e-5)
    np.testing.assert_allclose(kl_m, kl_g, rtol=1e-4)
    assert int(n_m) == int(n_g) == ref.selected_pairs(64, topk)


@pytest.mark.parametrize("blocks", [(64, 64), (32, 8), (16, 16)])
def test_topk_of_the_length_is_dense_causal_attention(blocks):
    q, k, v, qi, ki, w = args = _attention_inputs()
    out, _kl, pairs = _masked(args, 64, *blocks)
    qg = q.reshape(64, 2, 2, 16)
    logits = jnp.einsum("tgrd,sgd->grts", qg, k) / 4.0
    logits = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), logits, -jnp.inf)
    want = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(out, want.reshape(64, 64), atol=2e-5)
    assert int(pairs) == 64 * 65 // 2


@pytest.mark.parametrize("k", [1, 16, 128, 471, 500])
def test_kth_largest_is_top_ks_last(k):
    """The bisection on the bits is exact, ties, signed zeros and
    ``-inf`` (a masked key) included."""
    x = jax.random.normal(jax.random.key(0), (8, 500), F32)
    x = x.at[:, 100:130].set(-jnp.inf).at[3].set(-jnp.inf)
    x = x.at[4, :490].set(-jnp.inf).at[5, 7:].set(0.0)
    x = x.at[6].set(jnp.where(jnp.arange(500) % 2 == 0, -0.0, 0.0))
    got = jax.jit(lambda a: T.kth_largest(a, k))(x)
    np.testing.assert_array_equal(got, jax.lax.top_k(x, k)[0][:, -1])


def test_text_only_mrope_is_one_dimensional_rope():
    t, hd, theta = 24, 16, 1e7
    pos = np.broadcast_to(np.arange(t), (3, t))
    angles = T.mrope_angles(pos, hd, theta, (2, 3, 3))
    inv = theta ** (-np.arange(hd // 2) / (hd // 2))
    np.testing.assert_allclose(angles, np.arange(t)[:, None] * inv,
                               rtol=1e-6)
    x = jax.random.normal(jax.random.key(1), (t, 2, hd), F32)
    got = T.rotate(x, angles)
    cos, sin = np.cos(angles)[:, None], np.sin(angles)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # and the image's tokens do differ from text at the same index
    img = T.mrope_positions(2, 4)
    assert img[:, :4].tolist() == [[0, 0, 0, 0], [0, 0, 1, 1], [0, 1, 0, 1]]
    assert img[:, 4:].tolist() == [[2, 3, 4, 5]] * 3


def _moe_weights(seed=0, experts=8, d=64, f=32):
    ks = jax.random.split(jax.random.key(seed), 5)
    return {"router": jax.random.normal(ks[0], (d, experts), F32),
            "gate": 0.1 * jax.random.normal(ks[1], (experts, d, f), F32),
            "up": 0.1 * jax.random.normal(ks[2], (experts, d, f), F32),
            "down": 0.1 * jax.random.normal(ks[3], (experts, f, d), F32),
            "h": jax.random.normal(ks[4], (96, d), F32)}


def _moe_share(p, index, of, capacity_factor=2.0):
    held = p["gate"].shape[0] // of
    sl = slice(index * held, (index + 1) * held)
    return T.moe_layer(
        p["h"], p["router"], p["gate"][sl], p["up"][sl], p["down"][sl],
        experts_per_token=2, norm_topk=True, expert_share=(index, of),
        capacity_factor=capacity_factor, dtype=F32)


def _moe_uncut(p):
    cfg = dict(CFG, expert_share=[0, 1])
    with jax.default_matmul_precision("highest"):
        return ref.moe(cfg, p, p["h"], plain.HIGHEST)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    p = _moe_weights()
    want, tokens, _experts = _moe_uncut(p)
    total, counted = 0.0, []
    for i in range(8):
        out, experts, dropped = _moe_share(p, i, 8)
        assert int(dropped) == 0
        total = total + out
        counted.append(int(jnp.sum(experts == i)))
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert counted == np.asarray(tokens).tolist()
    assert sum(counted) == 96 * 2


def test_one_expert_taking_every_token_drops_none_and_agrees():
    p = _moe_weights(seed=3)
    # experts 0 and 1 win every token: both are held by share (0, 4),
    # which then sees 8 times its expected load and takes the worst-case
    # rows instead of the usual ones
    p["router"] = p["router"].at[:, 0].set(0.0).at[:, 1].set(0.0)
    bias = jnp.zeros((64, 8)).at[:, :2].set(jnp.abs(p["h"][:1].T) * 9.0)
    p["h"] = jnp.abs(p["h"])
    p["router"] = p["router"] * 0.01 + bias
    want, tokens, _ = _moe_uncut(p)
    assert np.asarray(tokens)[:2].tolist() == [96, 96]
    out, experts, dropped = _moe_share(p, 0, 4, capacity_factor=1.25)
    assert int(dropped) == 0
    np.testing.assert_allclose(out, want, atol=2e-5)
    # the same with rows for the worst case only
    again, _e, dropped = _moe_share(p, 0, 4, capacity_factor=100.0)
    assert int(dropped) == 0
    np.testing.assert_allclose(again, out, atol=1e-6)


def test_the_step_reports_its_routing_and_selection_counts(seeded):
    import optax

    from deepvision_tpu.obs.metrics import Registry, record_token_step
    from deepvision_tpu.train.state import TrainState
    from deepvision_tpu.train.steps import vlm_train_step

    weights, batch = seeded
    model = get_model("keye_vl2_tiny")
    tx = optax.adam(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=weights,
                       batch_stats={}, opt_state=tx.init(weights),
                       apply_fn=model.apply, tx=tx)
    new, metrics = jax.jit(vlm_train_step)(state, batch, jax.random.key(0))
    host = {k: float(v) for k, v in metrics.items()}
    assert host["moe_dropped"] == 0 and np.isfinite(host["loss"])
    assert host["loss"] == pytest.approx(
        host["lm_loss"] + host["index_loss"], rel=1e-6)
    # 2 samples x 64 tokens x 2 choices, half of the experts held, 2 layers
    assert 0.5 * 256 < host["moe_local_assignments"] < 1.5 * 256
    assert host["moe_expert_tokens_max"] >= host["moe_expert_tokens_mean"]
    assert host["dsa_selected_pairs"] >= 2 * 2 * ref.selected_pairs(64, 16)
    assert int(new.step) == 1
    reg = Registry()
    record_token_step(host, reg)
    record_token_step(host, reg)
    record_token_step({"loss": 1.0}, reg)        # a conv model's step
    assert reg.value_of("dsa_selected_pairs") == 2 * host[
        "dsa_selected_pairs"]
    assert reg.value_of("moe_expert_tokens_max") == host[
        "moe_expert_tokens_max"]
    assert set(reg.names()) == {
        "moe_local_assignments", "moe_dropped", "dsa_selected_pairs",
        "moe_expert_tokens_max", "moe_expert_tokens_mean"}


def test_the_familys_optimiser_warms_up_to_its_peak():
    """Adam's first update is the rate x sign(gradient): update ``n``
    (from 1) of ``TRAINING_CONFIG['keye_vl2_ep8']`` moves a weight by
    ``1e-4 * n / 2000``, and from update 2,000 on by the peak."""
    import optax

    from deepvision_tpu.train.configs import TRAINING_CONFIG
    from deepvision_tpu.train.optimizers import make_optimizer
    from deepvision_tpu.train.schedules import linear_warmup

    tx, controller = make_optimizer(dict(TRAINING_CONFIG["keye_vl2_ep8"]),
                                    100)
    assert controller is None
    params = {"w": jnp.ones((3,), F32)}
    grads = {"w": jnp.asarray([2.0, -0.5, 1e-3], F32)}
    state = tx.init(params)
    for n in (1, 2):
        updates, state = tx.update(grads, state, params)
        np.testing.assert_allclose(
            updates["w"], -1e-4 * n / 2000 * np.sign(grads["w"]), rtol=1e-4)
    assert optax.tree_utils.tree_get(state, "mu") is not None
    rate = linear_warmup(1e-4, 2000)
    assert [float(rate(n)) for n in (0, 999, 1999, 5000)] == pytest.approx(
        [5e-8, 5e-5, 1e-4, 1e-4])


def test_train_py_trains_saves_and_resumes_the_tiny_preset(tmp_path):
    import subprocess

    base = [sys.executable, str(ROOT / "train.py"), "-m", "keye_vl2_tiny",
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
    assert (tmp_path / "keye_vl2_tiny" / "ckpt").is_dir()
    again = subprocess.run(base + ["--epochs", "2", "--resume"],
                           capture_output=True, text=True, env=env,
                           timeout=600)
    assert again.returncode == 0, again.stderr[-2000:]
    assert "resumed at epoch 1" in again.stdout
    assert "[epoch 1]" in again.stdout and "[epoch 0]" not in again.stdout
