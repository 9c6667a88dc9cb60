"""The hyper-connected token model's train step (models/hyper_latent.py
through train/steps.lm_train_step) at the toy size of
test_hyper_latent.py, on the CPU: the MTP loss's weight, the bias rule
over two bias leaves, one whole step of Adam against the plain
reference, the registry's counts, and ``train.py`` on the preset. A file
of its own so that the suite's workers share the compiles."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_hyper_latent import (  # noqa: F401  (seeded is a fixture)
    BIASES,
    CFG,
    F32,
    GAMMA,
    ROOT,
    _leaf_gaps,
    ref,
    seeded,
)

from deepvision_tpu.models import get_model  # noqa: E402


def _state(model, weights, tx):
    from deepvision_tpu.train.state import TrainState

    return TrainState(step=jnp.zeros((), jnp.int32), params=weights,
                      batch_stats={}, opt_state=tx.init(weights),
                      apply_fn=model.apply, tx=tx)


@pytest.fixture(scope="module")
def stepped(seeded):
    """One float32 ``lm_train_step`` program, the MTP weight an argument:
    -> {weight: (new state, metrics)} at 0.3 and at 0."""
    import optax

    from deepvision_tpu.train.steps import lm_train_step

    weights, batch = seeded
    state = _state(get_model("xing4_tiny", dtype=F32), weights,
                   optax.adam(1e-3))
    step = jax.jit(lambda s, b, w: lm_train_step(s, b, jax.random.key(0),
                                                 mtp_weight=w))
    return {w: step(state, batch, jnp.float32(w)) for w in (0.3, 0.0)}


def test_with_no_mtp_weight_the_loss_is_the_next_token_loss(stepped):
    none, some = stepped[0.0][1], stepped[0.3][1]
    assert float(none["loss"]) == float(none["lm_loss"])
    assert float(some["loss"]) == pytest.approx(
        float(some["lm_loss"]) + 0.3 * float(some["mtp_loss"]), rel=1e-6)
    assert float(some["mtp_loss"]) > 0
    assert float(none["lm_loss"]) == float(some["lm_loss"])


def test_a_step_moves_both_biases_by_the_rule(seeded, stepped):
    """The stacked blocks' bias takes the first rows of the counts and
    the MTP block's the last; Adam leaves both leaves alone."""
    weights, batch = seeded
    model = get_model("xing4_tiny", dtype=F32)
    counts = np.asarray(jnp.sum(model.apply(
        {"params": weights}, batch, train=True)["expert_counts"], 0))
    new, metrics = stepped[0.3]
    want = GAMMA * np.sign(counts.mean(-1, keepdims=True) - counts)
    np.testing.assert_allclose(new.params["layers"]["moe"]["bias"], want[:2],
                               rtol=1e-6)
    np.testing.assert_allclose(new.params["mtp"]["block"]["moe"]["bias"],
                               want[2], rtol=1e-6)
    assert float(metrics["moe_bias_abs_mean"]) == pytest.approx(
        np.abs(want).mean(), rel=1e-6)
    assert 0 <= float(metrics["mhc_sinkhorn_err"]) < 1e-5


@pytest.mark.parametrize("dtype,tol", [
    ("float32", 1e-4),
    # bf16: Adam's first update is the rate x sign(gradient); as
    # test_latent_moe's, a leaf whose signs flip moves apart
    ("bfloat16", 0.45),
])
def test_one_whole_step_matches_the_reference(seeded, dtype, tol):
    """Adam and the bias rule: parameters after one step of the
    program's compiled step against the reference's ``train_steps``."""
    from benchmark.drivers import train_resident_mtp as driver
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
    if dtype == "float32":
        # Adam's first update is g / (|g| + eps) an entry: of the
        # hyper-connections' small leaves (alpha, b) those entries whose
        # gradients sit near rounding (a Sinkhorn projection is invariant
        # to a constant added to a row or column of its logits) read up
        # to 1.3e-3 here, every other leaf under 1e-4
        hc = {k: v for k, v in gaps.items()
              if len(k) > 1 and k[-2].endswith("_hc")}
        assert max(hc.values()) < 5e-3, hc
        gaps = {k: v for k, v in gaps.items() if k not in hc}
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < tol, (worst, gaps[worst])
    if dtype == "float32":      # the same counts, so the same signs
        for path in BIASES:
            got, want = state.params, after
            for k in path:
                got, want = got[k], want[k]
            np.testing.assert_allclose(got, want, atol=1e-9)


def test_the_step_reports_its_counts_and_the_registry_folds_them(stepped):
    from deepvision_tpu.obs.metrics import Registry, record_token_step

    _new, metrics = stepped[0.3]
    host = {k: float(v) for k, v in metrics.items()}
    assert host["moe_dropped"] == 0 and np.isfinite(host["loss"])
    assert host["attn_causal_pairs"] == 2 * 4 * 64 * 65 // 2
    reg = Registry()
    record_token_step(host, reg)
    assert reg.value_of("mtp_loss") == host["mtp_loss"]
    assert reg.value_of("mhc_sinkhorn_err") == host["mhc_sinkhorn_err"]


def test_train_py_trains_the_tiny_preset(tmp_path):
    import subprocess

    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TF_CPP_MIN_LOG_LEVEL": "2",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    run = subprocess.run(
        [sys.executable, str(ROOT / "train.py"), "-m", "xing4_tiny",
         "--platform", "cpu", "--synthetic-size", "32",
         "--steps-per-epoch", "2", "--workdir", str(tmp_path),
         "--epochs", "1"], capture_output=True, text=True, env=env,
        timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    assert "[epoch 0]" in run.stdout and "train_moe_dropped=0" in run.stdout
    assert "train_mtp_loss=" in run.stdout
