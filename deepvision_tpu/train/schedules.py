"""LR schedules matching the reference's three mechanisms.

- StepLR (VGG step_size=10 gamma=0.5; MobileNet 2/0.94 —
  ref: ResNet/pytorch/train.py:95-99,205-209)
- LambdaLR polynomial-then-floor for Inception (ref: train.py:128-135)
- ReduceLROnPlateau on val top-1 (AlexNet/ResNet — ref: train.py:45-49,
  applied at train.py:412-415): inherently host-side control flow, so it is
  a host ``PlateauController`` driving an ``optax.inject_hyperparams`` LR —
  the jitted step never sees Python control flow.
- LinearDecay for CycleGAN (constant, then linear to 0 —
  ref: CycleGAN/tensorflow/utils.py:5-28).
- Linear warm-up to a constant peak for the token model (Adam's first
  steps are about lr x sign(gradient) whatever the gradient's size;
  without a warm-up the first tens of steps move every weight by the
  peak rate along one direction).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import optax


def step_decay(base_lr: float, steps_per_epoch: int, step_size_epochs: int,
               gamma: float) -> optax.Schedule:
    def schedule(count):
        epoch = count // steps_per_epoch
        return base_lr * gamma ** (epoch // step_size_epochs)
    return schedule


def inception_poly(base_lr: float, steps_per_epoch: int) -> optax.Schedule:
    """(1 - e/60)^0.5 for e<60, then 1e-2, then 1e-3 of base —
    ref: ResNet/pytorch/train.py:132-134."""
    def schedule(count):
        epoch = count // steps_per_epoch
        frac = jnp.sqrt(jnp.maximum(1.0 - epoch / 60.0, 0.0))
        scale = jnp.where(epoch < 60, frac, jnp.where(epoch < 75, 0.01, 0.001))
        return base_lr * scale
    return schedule


def linear_decay(base_lr: float, total_steps: int, decay_start: int) -> optax.Schedule:
    """Constant until ``decay_start``, then linear to 0 at ``total_steps``."""
    def schedule(count):
        frac = jnp.clip(
            (count - decay_start) / jnp.maximum(total_steps - decay_start, 1),
            0.0, 1.0,
        )
        return base_lr * (1.0 - frac)
    return schedule


def linear_warmup(peak_lr: float, warmup_steps: int) -> optax.Schedule:
    """Update ``n`` (from 0) takes ``peak_lr * (n + 1) / warmup_steps``,
    from update ``warmup_steps - 1`` on the peak."""
    def schedule(count):
        return peak_lr * jnp.minimum((count + 1) / warmup_steps, 1.0)
    return schedule


@dataclasses.dataclass
class PlateauController:
    """torch ReduceLROnPlateau semantics (mode/factor/patience/threshold).

    ``update(metric)`` returns the new LR scale in (0, 1]; the Trainer writes
    it into the optimizer's injected hyperparams.
    """

    mode: str = "max"
    factor: float = 0.1
    patience: int = 10
    threshold: float = 1e-4
    min_scale: float = 1e-8

    scale: float = 1.0
    best: float | None = None
    bad_epochs: int = 0

    def update(self, metric: float) -> float:
        if self.best is None:
            self.best = metric
            return self.scale
        if self.mode == "max":
            improved = metric > self.best * (1 + self.threshold)
        else:
            improved = metric < self.best * (1 - self.threshold)
        if improved:
            self.best = metric
            self.bad_epochs = 0
        else:
            self.bad_epochs += 1
            if self.bad_epochs > self.patience:
                self.scale = max(self.scale * self.factor, self.min_scale)
                self.bad_epochs = 0
        return self.scale

    def state_dict(self) -> dict:
        return {"scale": self.scale, "best": self.best,
                "bad_epochs": self.bad_epochs}

    def load_state_dict(self, d: dict) -> None:
        self.scale = d["scale"]
        self.best = d["best"]
        self.bad_epochs = d["bad_epochs"]
