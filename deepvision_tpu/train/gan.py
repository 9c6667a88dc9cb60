"""GAN training: two-optimizer states, DCGAN/CycleGAN steps, ImagePool.

Re-expresses the reference's GAN trainers as pure compiled step functions:

- DCGAN alternating G/D Adam updates computed from the SAME forward pass
  (both losses share one fake batch and one discriminator dropout mask,
  exactly the reference's two-tape step — ref: DCGAN/tensorflow/main.py:57-76).
- CycleGAN two-phase step: generator phase (LSGAN + cycle + identity
  losses over both generators, ref: CycleGAN/tensorflow/train.py:150-205)
  then discriminator phase on POOLED fakes (ref: :207-255, :249-255).
- ``ImagePool`` redesigned as an on-device functional ring buffer: the
  reference's version mutates Python state and is documented eager-only
  (ref: CycleGAN/tensorflow/utils.py:31-61); here the pool is part of the
  train-state pytree and the query is a ``lax.scan``, so the whole step
  (G update → pool query → D update) compiles into ONE XLA program.

States mirror TrainState's field names (params/batch_stats/opt_state/step
plus ``extra_vars`` for the pools) so the Orbax CheckpointManager handles
them unchanged — the reference's `tf.train.Checkpoint` of both optimizers
and nets (ref: DCGAN/tensorflow/main.py:34-40, CycleGAN/train.py:133-148).
"""

from __future__ import annotations

import time
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

LAMBDA_CYCLE = 10.0  # ref: CycleGAN/tensorflow/train.py:16
LAMBDA_ID = 5.0  # ref: train.py:17
POOL_SIZE = 50  # ref: train.py:18


@flax.struct.dataclass
class GANState:
    """Two-network train state. ``params``/``batch_stats`` are dicts keyed
    by network role; ``opt_state`` holds one optax state per optimizer
    ('generator' spans all generator nets, 'discriminator' all critics —
    the reference's optimizer pairing, ref: CycleGAN/train.py:126-127).

    ``loss_scale`` (core/precision.py): ONE shared DynamicLossScale
    over both phases when the precision policy scales — a non-finite
    grad in EITHER tape skips both updates for the step and backs the
    scale off (the two-network coupling means half an update is worse
    than none). None = empty pytree, f32-era states flatten identically.
    """

    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    extra_vars: Any
    g_apply: Callable = flax.struct.field(pytree_node=False)
    d_apply: Callable = flax.struct.field(pytree_node=False)
    g_tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    d_tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    noise_dim: int = flax.struct.field(pytree_node=False, default=100)
    loss_scale: Any = None
    # core.sharding.Zero1Plan when fit_gan turned on weight-update
    # sharding; static (hashable) — same contract as TrainState's.
    zero1_plan: Any = flax.struct.field(pytree_node=False, default=None)

    def scale_loss(self, loss):
        """Loss scaled for a backward (identity without a scaler)."""
        if self.loss_scale is None:
            return loss
        return self.loss_scale.scale_loss(loss)


def _bce(logits, is_real: bool, smooth: float = 0.0):
    """``smooth`` > 0 applies one-sided label smoothing (real targets
    become 1-smooth; Salimans et al. 2016) — the standard fix when the
    discriminator saturates and starves the generator of gradient."""
    target = (jnp.full_like(logits, 1.0 - smooth) if is_real
              else jnp.zeros_like(logits))
    return jnp.mean(optax.sigmoid_binary_cross_entropy(logits, target))


def _lsgan(pred, is_real: bool):
    target = jnp.ones_like(pred) if is_real else jnp.zeros_like(pred)
    return jnp.mean((pred - target) ** 2)


def _l1(a, b):
    return jnp.mean(jnp.abs(a - b))


def _gan_apply_gradients(state: "GANState", g_grads, d_grads, *,
                         g_params, d_params, batch_stats, assemble,
                         extra_vars=None):
    """Shared two-optimizer update for both GAN steps: with a
    DynamicLossScale on the state, unscale both tapes' grads, gate the
    WHOLE step (params, opt states, BN stats, pools) on their joint
    finiteness, and grow/backoff the scale; plain updates otherwise.
    ``assemble(new_gp, new_dp)`` rebuilds the full params dict from the
    updated subsets. Returns ``(new_state, mp_metrics)``."""
    from deepvision_tpu.core.precision import (
        all_finite,
        precision_metrics,
        tree_select,
    )

    # ZeRO-1 reduce-scatter point (core.sharding.Zero1Plan, same
    # bracketing as TrainState.apply_gradients): both tapes' grads and
    # updates pinned to the weight-update sharding, updated params
    # all-gathered back to replicated. The plan is shape-driven, so one
    # plan serves both subtrees.
    plan = state.zero1_plan
    if plan is not None:
        g_grads, d_grads = plan.shard_update(g_grads), \
            plan.shard_update(d_grads)
    ls = state.loss_scale
    new_ls, finite = None, None
    if ls is not None:
        g_grads, d_grads = ls.unscale(g_grads), ls.unscale(d_grads)
        finite = all_finite({"g": g_grads, "d": d_grads})
        new_ls = ls.adjust(finite)
        # zero non-finite grads BEFORE the optimizer so inf*0 NaNs
        # cannot poison the moment estimates ahead of the select
        zero = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda g: jnp.where(finite, g, jnp.zeros_like(g)), t)
        g_grads, d_grads = zero(g_grads), zero(d_grads)
    g_up, g_opt = state.g_tx.update(
        g_grads, state.opt_state["generator"], g_params)
    d_up, d_opt = state.d_tx.update(
        d_grads, state.opt_state["discriminator"], d_params)
    if plan is not None:
        g_up, d_up = plan.shard_update(g_up), plan.shard_update(d_up)
    new_gp = optax.apply_updates(g_params, g_up)
    new_dp = optax.apply_updates(d_params, d_up)
    if plan is not None:
        new_gp, new_dp = plan.replicate(new_gp), plan.replicate(new_dp)
    new_params = assemble(new_gp, new_dp)
    new_opt = {"generator": g_opt, "discriminator": d_opt}
    new_ev = state.extra_vars if extra_vars is None else extra_vars
    if ls is not None:
        new_params = tree_select(finite, new_params, state.params)
        new_opt = tree_select(finite, new_opt, state.opt_state)
        batch_stats = tree_select(finite, batch_stats, state.batch_stats)
        if extra_vars is not None:
            new_ev = tree_select(finite, new_ev, state.extra_vars)
    new_state = state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=batch_stats,
        opt_state=new_opt,
        extra_vars=new_ev,
        loss_scale=new_ls if ls is not None else None,
    )
    return new_state, precision_metrics(new_state)


# --------------------------------------------------------------- DCGAN


def create_dcgan_state(
    generator, discriminator, *, noise_dim: int = 100,
    lr: float = 1e-4, rng: int | jax.Array = 0,
    sample_image_shape=(28, 28, 1),
    policy=None,
) -> GANState:
    """Both Adams at 1e-4 (ref: DCGAN/tensorflow/main.py:31-32).
    ``policy`` (core/precision.MixedPolicy) attaches the shared
    DynamicLossScale when the precision policy scales the loss."""
    if isinstance(rng, int):
        rng = jax.random.key(rng)
    kg, kd = jax.random.split(rng)
    z = jnp.zeros((1, noise_dim), jnp.float32)
    gv = generator.init({"params": kg}, z, train=True)
    x = jnp.zeros((1, *sample_image_shape), jnp.float32)
    dv = discriminator.init({"params": kd, "dropout": kd}, x, train=True)
    params = {"generator": gv["params"], "discriminator": dv["params"]}
    stats = {
        "generator": gv.get("batch_stats", {}),
        "discriminator": dv.get("batch_stats", {}),
    }
    g_tx, d_tx = optax.adam(lr), optax.adam(lr)
    return GANState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=stats,
        opt_state={"generator": g_tx.init(params["generator"]),
                   "discriminator": d_tx.init(params["discriminator"])},
        extra_vars={},
        g_apply=generator.apply,
        d_apply=discriminator.apply,
        g_tx=g_tx,
        d_tx=d_tx,
        noise_dim=noise_dim,
        loss_scale=(policy.make_loss_scale() if policy is not None
                    else None),
    )


def dcgan_train_step(state: GANState, batch: dict, key: jax.Array,
                     label_smooth: float = 0.0):
    """One simultaneous G+D update on {'image'} — both gradients are taken
    at the PRE-update parameters from one shared forward, like the
    reference's two tapes over a single noise batch (ref: main.py:57-76).

    ``label_smooth``: one-sided label smoothing on the discriminator's
    REAL targets only (generator loss untouched). Off by default —
    reference parity; the synthetic gate enables it because the
    deterministic blob set lets D saturate (measured d_loss 0.04 /
    g_loss 4.2 collapse without it).
    """
    real = batch["image"]
    kz, kdrop_fake, kdrop_real = jax.random.split(key, 3)
    z = jax.random.normal(kz, (real.shape[0], state.noise_dim))

    def d_forward(d_params, images, drop_key, stats):
        out, mut = state.d_apply(
            {"params": d_params, "batch_stats": stats},
            images, train=True, mutable=["batch_stats"],
            rngs={"dropout": drop_key},
        )
        return out, mut.get("batch_stats", stats)

    def g_loss_fn(g_params):
        fake, g_mut = state.g_apply(
            {"params": g_params, "batch_stats": state.batch_stats["generator"]},
            z, train=True, mutable=["batch_stats"],
        )
        fake_logits, _ = d_forward(
            state.params["discriminator"], fake, kdrop_fake,
            state.batch_stats["discriminator"],
        )
        loss = _bce(fake_logits, True)
        return state.scale_loss(loss), (
            loss,
            g_mut.get("batch_stats", state.batch_stats["generator"]), fake
        )

    (_, (g_loss, g_stats, fake)), g_grads = jax.value_and_grad(
        g_loss_fn, has_aux=True
    )(state.params["generator"])

    def d_loss_fn(d_params):
        real_logits, d_stats = d_forward(
            d_params, real, kdrop_real, state.batch_stats["discriminator"]
        )
        fake_logits, d_stats = d_forward(
            d_params, jax.lax.stop_gradient(fake), kdrop_fake, d_stats
        )
        loss = (_bce(real_logits, True, smooth=label_smooth)
                + _bce(fake_logits, False))
        return state.scale_loss(loss), (loss, d_stats)

    (_, (d_loss, d_stats)), d_grads = jax.value_and_grad(
        d_loss_fn, has_aux=True
    )(state.params["discriminator"])

    new_state, mp = _gan_apply_gradients(
        state, g_grads, d_grads,
        g_params=state.params["generator"],
        d_params=state.params["discriminator"],
        batch_stats={"generator": g_stats, "discriminator": d_stats},
        assemble=lambda new_gp, new_dp: {"generator": new_gp,
                                         "discriminator": new_dp},
    )
    return new_state, {"g_loss": g_loss, "d_loss": d_loss, **mp}


def dcgan_sample(state: GANState, key: jax.Array, n: int = 16):
    """Sample n images in eval mode (ref: DCGAN/tensorflow/inference.py:26-29)."""
    z = jax.random.normal(key, (n, state.noise_dim))
    return state.g_apply(
        {"params": state.params["generator"],
         "batch_stats": state.batch_stats["generator"]},
        z, train=False,
    )


# ----------------------------------------------------------- ImagePool


def create_pool(size: int, image_shape, dtype=jnp.float32) -> dict:
    return {
        "images": jnp.zeros((size, *image_shape), dtype),
        "count": jnp.zeros((), jnp.int32),
    }


def pool_query(pool: dict, images: jnp.ndarray, key: jax.Array):
    """Historical-fake buffer query (ref semantics, utils.py:38-61):
    per image — fill the buffer while not full (return the image);
    afterwards 50%: swap with a random stored image and return the old
    one, else return the image. Pure: returns (out_images, new_pool)."""
    size = pool["images"].shape[0]
    keys = jax.random.split(key, images.shape[0])

    def body(carry, x):
        buf, count = carry
        img, k = x
        kp, ki = jax.random.split(k)
        p = jax.random.uniform(kp)
        rid = jax.random.randint(ki, (), 0, size)

        def insert(_):
            return (
                jax.lax.dynamic_update_index_in_dim(buf, img, count, 0),
                count + 1,
                img,
            )

        def mature(_):
            stored = buf[rid]
            take = p > 0.5
            new_buf = jnp.where(take, buf.at[rid].set(img), buf)
            out = jnp.where(take, stored, img)
            return new_buf, count, out

        buf2, count2, out = jax.lax.cond(count < size, insert, mature, None)
        return (buf2, count2), out

    (buf, count), outs = jax.lax.scan(
        body, (pool["images"], pool["count"]), (images, keys)
    )
    return outs, {"images": buf, "count": count}


# ------------------------------------------------------------ CycleGAN


def create_cyclegan_state(
    generator, discriminator, *, image_size: int = 256,
    lr_schedule=2e-4, beta1: float = 0.5, pool_size: int = POOL_SIZE,
    rng: int | jax.Array = 0, policy=None,
) -> GANState:
    """Two Adams (β1=0.5) over {G_a2b+G_b2a} and {D_a+D_b}
    (ref: CycleGAN/tensorflow/train.py:122-127); ``lr_schedule`` may be a
    float or an optax schedule (schedules.linear_decay for ref parity)."""
    if isinstance(rng, int):
        rng = jax.random.key(rng)
    ks = jax.random.split(rng, 4)
    x = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    nets = {}
    for name, net, k in (
        ("gen_a2b", generator, ks[0]), ("gen_b2a", generator, ks[1]),
        ("dis_a", discriminator, ks[2]), ("dis_b", discriminator, ks[3]),
    ):
        nets[name] = net.init({"params": k}, x, train=True)
    params = {n: v["params"] for n, v in nets.items()}
    stats = {n: v.get("batch_stats", {}) for n, v in nets.items()}
    gp = {k: params[k] for k in ("gen_a2b", "gen_b2a")}
    dp = {k: params[k] for k in ("dis_a", "dis_b")}
    g_tx = optax.adam(lr_schedule, b1=beta1)
    d_tx = optax.adam(lr_schedule, b1=beta1)
    shape = (image_size, image_size, 3)
    return GANState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=stats,
        opt_state={"generator": g_tx.init(gp),
                   "discriminator": d_tx.init(dp)},
        extra_vars={"pool_a2b": create_pool(pool_size, shape),
                    "pool_b2a": create_pool(pool_size, shape)},
        g_apply=generator.apply,
        d_apply=discriminator.apply,
        g_tx=g_tx,
        d_tx=d_tx,
        loss_scale=(policy.make_loss_scale() if policy is not None
                    else None),
    )


def cyclegan_train_step(state: GANState, batch: dict, key: jax.Array):
    """One two-phase step on {'a','b'} image batches (ref: train.py:249-255).

    Phase 1 updates both generators (LSGAN + λ·cycle + λ_id·identity);
    phase 2 updates both discriminators on real vs POOLED fakes ×0.5.
    Discriminator BN statistics also update during phase 1, mirroring the
    reference's ``training=True`` critic calls inside the generator tape
    (ref: train.py:170-175).
    """
    real_a, real_b = batch["a"], batch["b"]
    k_pool_a2b, k_pool_b2a = jax.random.split(key)

    def gen_apply(params, stats, x):
        out, mut = state.g_apply(
            {"params": params, "batch_stats": stats},
            x, train=True, mutable=["batch_stats"],
        )
        return out, mut.get("batch_stats", stats)

    def dis_apply(params, stats, x):
        out, mut = state.d_apply(
            {"params": params, "batch_stats": stats},
            x, train=True, mutable=["batch_stats"],
        )
        return out, mut.get("batch_stats", stats)

    # ---- Phase 1: generators (ref: train.py:150-205)
    def g_loss_fn(gp):
        s = dict(state.batch_stats)
        fake_a2b, s["gen_a2b"] = gen_apply(
            gp["gen_a2b"], s["gen_a2b"], real_a
        )
        recon_b2a, s["gen_b2a"] = gen_apply(
            gp["gen_b2a"], s["gen_b2a"], fake_a2b
        )
        fake_b2a, s["gen_b2a"] = gen_apply(
            gp["gen_b2a"], s["gen_b2a"], real_b
        )
        recon_a2b, s["gen_a2b"] = gen_apply(
            gp["gen_a2b"], s["gen_a2b"], fake_b2a
        )
        identity_a2b, s["gen_a2b"] = gen_apply(
            gp["gen_a2b"], s["gen_a2b"], real_b
        )
        identity_b2a, s["gen_b2a"] = gen_apply(
            gp["gen_b2a"], s["gen_b2a"], real_a
        )
        logits_b, s["dis_b"] = dis_apply(
            state.params["dis_b"], s["dis_b"], fake_a2b
        )
        logits_a, s["dis_a"] = dis_apply(
            state.params["dis_a"], s["dis_a"], fake_b2a
        )
        loss_gan_a2b = _lsgan(logits_b, True)
        loss_gan_b2a = _lsgan(logits_a, True)
        loss_cycle_a = _l1(recon_b2a, real_a)
        loss_cycle_b = _l1(recon_a2b, real_b)
        loss_id_a2b = _l1(identity_a2b, real_b)
        loss_id_b2a = _l1(identity_b2a, real_a)
        total = (
            loss_gan_a2b + loss_gan_b2a
            + (loss_cycle_a + loss_cycle_b) * LAMBDA_CYCLE
            + (loss_id_a2b + loss_id_b2a) * LAMBDA_ID
        )
        metrics = {
            "loss_gen_a2b": loss_gan_a2b, "loss_gen_b2a": loss_gan_b2a,
            "loss_cycle_a2b2a": loss_cycle_a, "loss_cycle_b2a2b": loss_cycle_b,
            "loss_id_a2b": loss_id_a2b, "loss_id_b2a": loss_id_b2a,
            "loss_gen_total": total,
        }
        return state.scale_loss(total), (s, fake_a2b, fake_b2a, metrics)

    gp = {k: state.params[k] for k in ("gen_a2b", "gen_b2a")}
    (_, (stats1, fake_a2b, fake_b2a, g_metrics)), g_grads = (
        jax.value_and_grad(g_loss_fn, has_aux=True)(gp)
    )

    # ---- Pool query on the fresh fakes (ref: train.py:251-252)
    pooled_a2b, pool_a2b = pool_query(
        state.extra_vars["pool_a2b"], jax.lax.stop_gradient(fake_a2b),
        k_pool_a2b,
    )
    pooled_b2a, pool_b2a = pool_query(
        state.extra_vars["pool_b2a"], jax.lax.stop_gradient(fake_b2a),
        k_pool_b2a,
    )

    # ---- Phase 2: discriminators (ref: train.py:207-245)
    def d_loss_fn(dp):
        s = dict(stats1)
        ra, s["dis_a"] = dis_apply(dp["dis_a"], s["dis_a"], real_a)
        fa, s["dis_a"] = dis_apply(dp["dis_a"], s["dis_a"], pooled_b2a)
        rb, s["dis_b"] = dis_apply(dp["dis_b"], s["dis_b"], real_b)
        fb, s["dis_b"] = dis_apply(dp["dis_b"], s["dis_b"], pooled_a2b)
        loss_a = (_lsgan(ra, True) + _lsgan(fa, False)) * 0.5
        loss_b = (_lsgan(rb, True) + _lsgan(fb, False)) * 0.5
        total = loss_a + loss_b
        return state.scale_loss(total), (
            s, {"loss_dis_a": loss_a, "loss_dis_b": loss_b,
                "loss_dis_total": total})

    dp = {k: state.params[k] for k in ("dis_a", "dis_b")}
    (_, (stats2, d_metrics)), d_grads = jax.value_and_grad(
        d_loss_fn, has_aux=True
    )(dp)
    new_state, mp = _gan_apply_gradients(
        state, g_grads, d_grads, g_params=gp, d_params=dp,
        batch_stats=stats2,
        assemble=lambda new_gp, new_dp: {**new_gp, **new_dp},
        extra_vars={"pool_a2b": pool_a2b, "pool_b2a": pool_b2a},
    )
    return new_state, {**g_metrics, **d_metrics, **mp}


def cyclegan_translate(state: GANState, images, direction: str = "a2b"):
    """Eval-mode translation (ref: CycleGAN/tensorflow/inference.py:34-68)."""
    name = f"gen_{direction}"
    return state.g_apply(
        {"params": state.params[name],
         "batch_stats": state.batch_stats[name]},
        images, train=False,
    )


def fit_gan(
    state: GANState,
    train_step,
    train_data,
    mesh,
    *,
    epochs: int,
    workdir: str = "runs/gan",
    save_every: int = 2,
    log_every: int = 50,
    resume: bool = False,
    resume_epoch: int | None = None,
    check_numerics: bool = False,
    shard_weight_update: bool = False,
    async_checkpoint: bool = False,
    preempt=None,
    watchdog=None,
    prefetch_depth: int = 2,
):
    """Minimal GAN epoch loop: compiled step + loggers + TB + Orbax saves
    every ``save_every`` epochs keeping 3 (ref: DCGAN/tensorflow/main.py:39,
    80-83; CycleGAN saves every epoch with the epoch tracked in the
    checkpoint, ref: train.py:329-333 — pass save_every=1).

    ``preempt``: optional zero-arg callable polled at every epoch
    boundary; when truthy the loop saves off-cadence and stops (the GAN
    analog of Trainer's SIGTERM handling — epoch-granular because GAN
    epochs on the reference workloads are short; resume restarts at the
    next epoch).

    ``watchdog``: optional Trainer.StallWatchdog — started here, beaten
    per step/drain, stopped on exit (same hang-detection contract as
    Trainer.fit).

    ``prefetch_depth``: device batches kept in flight ahead of the step
    by the async feed (data/prefetch.py); 1 = classic double
    buffering."""
    from deepvision_tpu.core.step import (
        compile_checked_train_step,
        compile_train_step,
    )
    from deepvision_tpu.train.checkpoint import CheckpointManager
    from deepvision_tpu.train.loggers import Loggers, TensorBoardWriter

    mgr = CheckpointManager(f"{workdir}/ckpt", async_save=async_checkpoint)
    loggers = Loggers()
    tb = TensorBoardWriter(f"{workdir}/tb")
    start_epoch = 0
    if resume and mgr.latest_epoch() is not None:
        state, meta = mgr.restore(state, resume_epoch)
        start_epoch = meta["epoch"] + 1
        if meta.get("loggers"):
            loggers = meta["loggers"]
    state_spec = None
    if shard_weight_update:
        from deepvision_tpu.core.sharding import zero1_plan
        from deepvision_tpu.core.step import weight_update_sharding

        plan = zero1_plan(mesh)
        if plan is None:
            raise ValueError(
                "--zero1 asked for weight-update sharding but the "
                "[[shardcheck.rule]] opt_state row does not prescribe a "
                "largest(...) spec — declare it in the table first")
        state = state.replace(zero1_plan=plan)
        state_spec = weight_update_sharding(state, mesh)
    compiler = (
        compile_checked_train_step if check_numerics else compile_train_step
    )
    step = compiler(train_step, mesh, state_spec=state_spec)
    base_key = jax.random.key(np.uint32(1234))
    if watchdog is not None:
        watchdog.start()
    try:
        state, loggers = _gan_epoch_loop(
            state, step, train_data, mesh, start_epoch, epochs,
            base_key, mgr, loggers, tb, save_every, log_every,
            preempt, watchdog, prefetch_depth,
        )
    finally:
        # an exception mid-epoch must still stop the daemon watchdog
        # (abort=True could otherwise os._exit(75) during unrelated
        # exception handling, masking the real traceback) and close the
        # manager so staged async saves commit or are cleanly dropped
        tb.flush()
        mgr.close()
        if watchdog is not None:
            watchdog.stop()
    return state, loggers


def _gan_epoch_loop(state, step, train_data, mesh, start_epoch, epochs,
                    base_key, mgr, loggers, tb, save_every, log_every,
                    preempt, watchdog, prefetch_depth=2):
    from deepvision_tpu.core.prng import KeySeq
    from deepvision_tpu.data.prefetch import DevicePrefetcher, FeedTelemetry
    from deepvision_tpu.obs.trace import span
    from deepvision_tpu.train.loggers import input_wait_metrics

    for epoch in range(start_epoch, epochs):
        # epoch-derived noise stream (core.prng.KeySeq, the blessed
        # threading idiom — jaxlint JX103): resume reproduces the
        # uninterrupted run's z draws / pool coin flips (same rationale
        # as Trainer)
        keys = KeySeq(jax.random.fold_in(base_key, epoch))
        t0 = time.time()
        # pending/drain split (same as Trainer.train_epoch): metrics stay
        # device-side until a drain, so the dispatch queue keeps running —
        # per-batch float() here serialized a D2H round trip per metric
        # per batch and stalled the device between steps.
        pending: list[dict] = []  # device scalars not yet fetched
        fetched: list[dict] = []  # host floats; each metric fetched ONCE

        def drain():
            # completed-step heartbeats, same rationale as Trainer
            if not pending:
                return
            with span("drain", cat="train"):
                for m in pending:
                    fetched.append({k: float(v) for k, v in m.items()})
                    if watchdog is not None:
                        watchdog.beat()
                pending.clear()

        # async H2D feed (data/prefetch.py, same as Trainer.train_epoch):
        # producer-thread sharding keeps `prefetch_depth` transfers in
        # flight; close() in the finally stops the thread on every exit.
        # Spans (obs/trace.py) mirror the Trainer's epoch/step/drain
        # attribution; no-ops unless the tracer is enabled (--trace).
        tel = FeedTelemetry()
        with span("epoch", cat="train", args={"epoch": int(epoch)},
                  encloses=True):
            feed = DevicePrefetcher(train_data(epoch), mesh,
                                    depth=prefetch_depth, telemetry=tel)
            try:
                for i, device_batch in enumerate(feed):
                    with span("step", cat="train"):
                        state, metrics = step(state, device_batch,
                                              next(keys))
                        pending.append(metrics)
                    # beats land only in drain() (per COMPLETED step) — a
                    # dispatch-side beat would mask a wedged device until
                    # the dispatch queue itself blocked; cadence bounded
                    # at 32 batches regardless of log_every (same fix as
                    # Trainer)
                    if watchdog is not None \
                            and i % min(32, log_every or 32) == 0:
                        drain()
                    if log_every and i % log_every == 0:
                        drain()  # syncs mostly-finished work; O(n) total
                        print(f"[epoch {epoch} batch {i}] " + " ".join(
                            f"{k}={v:.4f}"
                            for k, v in sorted(fetched[-1].items())
                        ), flush=True)
            finally:
                feed.close()
            drain()  # drains the dispatch queue — precedes the timing read
        epoch_metrics = {
            k: float(np.mean([m[k] for m in fetched]))
            for k in (fetched[0] if fetched else {})
        }
        # per-stage feed telemetry, same metric names as the Trainer
        epoch_metrics.update(input_wait_metrics(tel.summary()))
        loggers.log_metrics(epoch, epoch_metrics)
        for k, v in epoch_metrics.items():
            tb.scalar(k, v, epoch)
        # wall-clock per epoch, the reference's only perf signal
        # (ref: DCGAN/tensorflow/main.py:85, CycleGAN/train.py:335-336)
        print(f"[epoch {epoch}] " + " ".join(
            f"{k}={v:.4f}" for k, v in sorted(epoch_metrics.items())
        ) + f" time={time.time() - t0:.1f}s", flush=True)
        stop = preempt is not None and preempt()
        if (epoch + 1) % save_every == 0 or epoch == epochs - 1 or stop:
            with span("checkpoint", cat="train"):
                mgr.save(epoch, state, loggers=loggers)
        if stop:
            print(f"[preempted] after completed epoch {epoch}", flush=True)
            break
    return state, loggers
