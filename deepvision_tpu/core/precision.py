"""Numerics-policy engine: bf16 compute on the MXU, f32 master state,
dynamic loss scaling — the framework-wide mixed-precision contract.

The reference trains everything in f32 (cuDNN-era defaults). On TPU the
MXU natively multiplies bf16 with f32 accumulation, so the policy every
training surface threads through here is:

- **f32 master weights**: parameters and optimizer state live in f32
  (the Flax ``param_dtype`` default). Layers cast params to the compute
  dtype AT USE (linen's cast-at-use convention via the module ``dtype``
  attribute), so the forward/backward runs bf16 activations and
  gradients while the optimizer update happens against full-precision
  masters — the grads flow back up through the per-param cast as f32.
- **bf16 activations/gradients**: the model ``dtype`` (``compute_dtype``
  here) is what the HBM-resident activation tensors carry; BN
  statistics, softmax and loss accumulation stay in ``reduce_dtype``
  (f32) — the ``force_float32_reductions`` linen default.
- **dynamic loss scaling** (:class:`DynamicLossScale`): a pytree-borne
  scale multiplied into the loss before the backward and divided back
  out of the grads before the update, grown every ``growth_interval``
  clean steps and backed off on non-finite grads — a backoff SKIPS the
  update (master weights and optimizer state untouched) instead of
  corrupting training, and is reported through ``mp_*`` step metrics so
  the PR 10 sentinel treats it as handled, not as a trip. bf16 shares
  f32's exponent range, so scaling exists as a guard for the loss
  surfaces with wide dynamic range (heatmap MSE, GAN couplings), not as
  the fp16 necessity.

- **float32 islands under bf16 compute** (the token models,
  ``models/transformer.py``): a multiply takes compute-dtype operands
  and accumulates in f32 (:func:`compute_dot`, :func:`compute_einsum`);
  norms, every softmax, the router's logits (:func:`float32_dot`: f32
  operands at ``Precision.HIGHEST``, because the top-k behind them is
  discrete), the indexer's scores and the loss are kept in f32.

Models take ``dtype``/``param_dtype`` in the Flax convention so tests
can force full f32 for parity checks against the PyTorch reference.
Per-model remat policies (the other half of the HBM diet) are declared
in ``models/registry.py`` and threaded by ``train/configs.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Precision:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    # BN statistics / softmax / loss accumulation dtype.
    reduce_dtype: jnp.dtype = jnp.float32

    def cast_compute(self, tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )


@flax.struct.dataclass
class DynamicLossScale:
    """Loss-scale state carried in the train-state pytree (it must ride
    the donated step and the checkpoint manifest like any other state).

    ``adjust(grads_finite)`` implements the standard grow/backoff
    schedule: ``growth_interval`` consecutive finite-grad steps double
    the scale (capped at ``max_scale``); any non-finite grad halves it
    (floored at ``min_scale``) and resets the streak. The caller skips
    the parameter update on the non-finite step —
    :meth:`train.state.TrainState.apply_gradients` owns that select.
    """

    scale: jax.Array  # f32 scalar
    good_steps: jax.Array  # i32 scalar, finite-grad streak length
    # 1.0/0.0 verdict of the LAST adjust() — carried in the state so
    # step metrics can report the skip/backoff without a second grad
    # reduction (and without mis-reading scale transitions at the
    # min/max-scale clamps, where a backoff/growth leaves scale equal)
    last_finite: jax.Array = flax.struct.field(
        default_factory=lambda: jnp.float32(1.0))
    growth_interval: int = flax.struct.field(pytree_node=False,
                                             default=200)
    growth_factor: float = flax.struct.field(pytree_node=False,
                                             default=2.0)
    backoff_factor: float = flax.struct.field(pytree_node=False,
                                              default=0.5)
    min_scale: float = flax.struct.field(pytree_node=False, default=1.0)
    max_scale: float = flax.struct.field(pytree_node=False,
                                         default=float(2 ** 24))

    @classmethod
    def create(cls, init_scale: float = float(2 ** 15),
               **kw) -> "DynamicLossScale":
        return cls(scale=jnp.float32(init_scale),
                   good_steps=jnp.zeros((), jnp.int32), **kw)

    def scale_loss(self, loss: jax.Array) -> jax.Array:
        return loss * self.scale.astype(loss.dtype)

    def unscale(self, grads):
        """Grads divided by the scale AND cast up to f32 — the 'grads
        cast back up into the f32 update' half of the policy."""
        inv = (1.0 / self.scale).astype(jnp.float32)
        return jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * inv, grads)

    def adjust(self, grads_finite: jax.Array) -> "DynamicLossScale":
        grew = self.good_steps + 1 >= self.growth_interval
        new_scale = jnp.where(
            grads_finite,
            jnp.where(grew,
                      jnp.minimum(self.scale * self.growth_factor,
                                  self.max_scale),
                      self.scale),
            jnp.maximum(self.scale * self.backoff_factor,
                        self.min_scale),
        )
        new_good = jnp.where(grads_finite & ~grew,
                             self.good_steps + 1,
                             jnp.zeros((), jnp.int32))
        return self.replace(scale=new_scale, good_steps=new_good,
                            last_finite=grads_finite.astype(jnp.float32))


def compute_dot(x, w, dtype) -> jax.Array:
    """``x @ w`` with both operands in the compute ``dtype`` and float32
    accumulation; the caller casts the float32 result where it stores
    an activation."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def compute_einsum(spec: str, a, b, dtype) -> jax.Array:
    """:func:`compute_dot` for an einsum of two operands."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def float32_dot(x, w) -> jax.Array:
    """``x @ w`` in float32 at ``Precision.HIGHEST`` whatever the compute
    dtype: for the small products a discrete choice hangs on (router
    logits), where a bf16 operand would flip choices."""
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def all_finite(tree) -> jax.Array:
    """Scalar bool: every float leaf of ``tree`` is finite. ONE fused
    reduction over the grad pytree — the overflow check dynamic loss
    scaling keys the skip/backoff decision on. (Branch-free: an empty
    float tree sums zero non-finite counts and reads True.)"""
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)]
    nonfinite = sum(jnp.sum(~jnp.isfinite(l)) for l in leaves)
    return jnp.asarray(nonfinite) == 0


def tree_select(pred: jax.Array, on_true, on_false):
    """Leaf-wise ``where(pred, a, b)`` over two same-structure pytrees —
    the skipped-update select (non-finite grads leave masters alone)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false)


@dataclasses.dataclass(frozen=True)
class MixedPolicy(Precision):
    """The full numerics policy: :class:`Precision`'s dtype triple plus
    the loss-scaling configuration. Build one with :func:`get_policy`
    from a config/CLI precision name; thread it through
    ``create_train_state(policy=...)`` (which attaches the
    :class:`DynamicLossScale` to the state when scaling is on) — the
    compiled steps key their scaling behavior off the presence of
    ``state.loss_scale``, so one traced program serves both modes per
    configuration with zero retrace churn."""

    loss_scaling: bool = False
    init_scale: float = float(2 ** 15)
    growth_interval: int = 200
    growth_factor: float = 2.0
    backoff_factor: float = 0.5

    @property
    def name(self) -> str:
        if self.compute_dtype == jnp.float32:
            return "f32"
        return "bf16_scaled" if self.loss_scaling else "bf16"

    def cast_to_param(self, tree):
        """Cast a (grad) tree up to the master ``param_dtype``."""
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.param_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )

    def make_loss_scale(self) -> DynamicLossScale | None:
        if not self.loss_scaling:
            return None
        return DynamicLossScale.create(
            init_scale=self.init_scale,
            growth_interval=self.growth_interval,
            growth_factor=self.growth_factor,
            backoff_factor=self.backoff_factor,
        )


_F32 = MixedPolicy(compute_dtype=jnp.float32)
_BF16 = MixedPolicy()
_BF16_SCALED = MixedPolicy(loss_scaling=True)

_ALIASES = {
    "bf16": _BF16, "bfloat16": _BF16, "mixed": _BF16,
    "f32": _F32, "float32": _F32, "full": _F32,
    "bf16_scaled": _BF16_SCALED, "bfloat16_scaled": _BF16_SCALED,
    "mixed_scaled": _BF16_SCALED,
}

PRECISION_NAMES = ("bf16", "bf16_scaled", "f32")


def get_policy(name: str = "bf16") -> MixedPolicy:
    """``bf16`` (TPU default), ``bf16_scaled`` (bf16 + dynamic loss
    scaling) or ``f32`` (parity testing / precision-floor configs)."""
    try:
        return _ALIASES[name]
    except KeyError:
        raise ValueError(
            f"unknown precision policy {name!r} "
            f"(known: {sorted(set(_ALIASES))})") from None


def get_precision(name: str = "bf16") -> Precision:
    """Back-compat alias of :func:`get_policy` (pre-policy callers only
    consume the dtype triple)."""
    return get_policy(name)


def precision_metrics(new_state) -> dict:
    """The ``mp_*`` step metrics when loss scaling is active, ``{}``
    otherwise — read off the POST-update state. ``mp_grads_finite`` is
    the in-graph verdict ``adjust()`` recorded for this step — the
    PR 10 sentinel consumes it to treat a scale backoff as handled
    rather than as a trip."""
    ls_new = getattr(new_state, "loss_scale", None)
    if ls_new is None:
        return {}
    return {
        "mp_loss_scale": ls_new.scale,
        "mp_grads_finite": ls_new.last_finite,
    }
