"""Optimizer factories: reference ``training_config`` semantics → optax.

torch-SGD weight decay is L2-added-to-grad BEFORE momentum accumulation, so
the optax chain is ``add_decayed_weights → sgd(momentum)``; torch RMSprop's
``alpha``/``eps`` map to optax ``decay``/``eps``
(ref configs: ResNet/pytorch/train.py:26-215).

Plateau-scheduled configs wrap the whole chain in
``optax.inject_hyperparams`` over a ``lr_scale`` factor so the host-side
PlateauController can rescale the LR without recompiling the step.
"""

from __future__ import annotations

from typing import Any

import optax

from deepvision_tpu.train import schedules


def _base_tx(opt: str, lr, params: dict[str, Any]) -> optax.GradientTransformation:
    wd = params.get("weight_decay", 0.0)
    parts = []
    if opt == "sgd":
        if wd:
            parts.append(optax.add_decayed_weights(wd))
        parts.append(optax.sgd(lr, momentum=params.get("momentum", 0.0)))
    elif opt == "rmsprop":
        if wd:
            parts.append(optax.add_decayed_weights(wd))
        parts.append(optax.rmsprop(lr, decay=params.get("alpha", 0.9),
                                   eps=params.get("eps", 1e-8)))
    elif opt == "adam":
        parts.append(optax.adam(lr, b1=params.get("beta1", 0.9),
                                b2=params.get("beta2", 0.999),
                                eps=params.get("eps", 1e-8)))
    else:
        raise ValueError(f"unknown optimizer {opt!r}")
    return optax.chain(*parts)


def make_optimizer(cfg: dict, steps_per_epoch: int):
    """-> (tx, plateau_controller | None) from a training_config entry."""
    opt = cfg["optimizer"]
    p = dict(cfg.get("optimizer_params", {}))
    base_lr = p.pop("lr")
    sched_name = cfg.get("scheduler")
    sched_p = cfg.get("scheduler_params", {})

    if sched_name == "plateau":
        controller = schedules.PlateauController(
            mode=sched_p.get("mode", "max"),
            factor=sched_p.get("factor", 0.1),
            patience=sched_p.get("patience", 10),
        )

        def make(lr_scale):
            return _base_tx(opt, base_lr * lr_scale, p)

        tx = optax.inject_hyperparams(make)(lr_scale=1.0)
        return tx, controller

    if sched_name == "step":
        lr = schedules.step_decay(base_lr, steps_per_epoch,
                                  sched_p["step_size"], sched_p["gamma"])
    elif sched_name == "inception_poly":
        lr = schedules.inception_poly(base_lr, steps_per_epoch)
    elif sched_name == "linear_decay":
        lr = schedules.linear_decay(base_lr, sched_p["total_steps"],
                                    sched_p["decay_start"])
    elif sched_name == "warmup":
        lr = schedules.linear_warmup(base_lr, sched_p["warmup_steps"])
    elif sched_name in (None, "constant"):
        lr = base_lr
    else:
        raise ValueError(f"unknown scheduler {sched_name!r}")
    return _base_tx(opt, lr, p), None


def set_lr_scale(opt_state, scale: float):
    """Write the PlateauController's scale into inject_hyperparams state."""
    import jax.numpy as jnp

    hp = dict(opt_state.hyperparams)
    hp["lr_scale"] = jnp.asarray(scale, jnp.float32)
    return opt_state._replace(hyperparams=hp)
