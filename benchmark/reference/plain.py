"""Plain building blocks of the benchmark's references.

Straight ``jax.numpy`` / ``lax``: no Flax, no kernels, nothing imported
from the program. A reference is written against these, follows the
published description of its architecture, and is what decides
``correct``. Every multiply goes through :func:`conv` or :func:`dense`,
which also append ``2 x multiply-adds`` to ``tally`` when one is passed:
that count, taken under ``jax.eval_shape``, is the model-FLOP count the
``step_mfu`` metrics divide. It depends on the configuration's shapes
only.

``Numerics`` says how a reference multiplies and stores:

- ``HIGHEST``: float32 everywhere, every multiply at
  ``lax.Precision.HIGHEST``: the truth a bf16 training step is held to.
- ``DEFAULT``: float32 storage, every multiply at the backend's default
  precision: "float32 at XLA's default matmul precision" as the backend
  itself takes it (on a TPU the operands of a convolution are rounded to
  bfloat16, on a CPU they are not). The ``stated_numerics`` of a
  configuration served so: how far a sound computation at that
  precision lies from float32 (``precision_excess``).
- ``BF16_OPERANDS``: float32 storage and accumulation, the operands of
  each convolution rounded to bfloat16 by hand: what ``DEFAULT`` is on
  a TPU, on any backend. The tests' ``stated_numerics`` on the CPU.
- ``BF16``: storage and elementwise arithmetic in bfloat16: the control
  of a configuration that states float32.
- ``FP8_OPERANDS``: the operands of each multiply rounded to float8
  e4m3 with one scale a tensor, straight-through gradient, all else
  float32: the mildest fp8 step, the control of a configuration that
  states bfloat16.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

_DIMS = ("NHWC", "HWIO", "NHWC")


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str
    store: str = "float32"        # dtype activations are kept in
    operands: str = "float32"     # what a multiply's operands are rounded to
    highest: bool = False

    def round_operand(self, a):
        if self.operands == "float32":
            return a
        if self.operands == "bfloat16":
            return a.astype(jnp.bfloat16)
        if self.operands == "float8_e4m3":
            scale = jnp.max(jnp.abs(a)).astype(jnp.float32) / 448.0 + 1e-30
            q = (a.astype(jnp.float32) / scale).astype(
                jnp.float8_e4m3fn).astype(jnp.float32) * scale
            # straight-through: the backward sees the identity
            return a + lax.stop_gradient(q.astype(a.dtype) - a)
        raise ValueError(f"unknown operand type {self.operands!r}")

    @property
    def precision(self):
        return lax.Precision.HIGHEST if self.highest else None


HIGHEST = Numerics("highest", highest=True)
DEFAULT = Numerics("default")
BF16_OPERANDS = Numerics("bf16_operands", operands="bfloat16")
BF16 = Numerics("bf16", store="bfloat16", operands="bfloat16")
FP8_OPERANDS = Numerics("fp8_operands", operands="float8_e4m3", highest=True)
NUMERICS = {n.name: n for n in (HIGHEST, DEFAULT, BF16_OPERANDS, BF16,
                                 FP8_OPERANDS)}


def conv(x, w, stride: int, pad: int, nm: Numerics, tally=None):
    """NHWC convolution with symmetric padding ``pad`` on both axes."""
    kh, kw, cin, cout = w.shape
    ho = (x.shape[1] + 2 * pad - kh) // stride + 1
    wo = (x.shape[2] + 2 * pad - kw) // stride + 1
    if tally is not None:
        tally.append(2 * x.shape[0] * ho * wo * cout * kh * kw * cin)
    y = lax.conv_general_dilated(
        nm.round_operand(x), nm.round_operand(w.astype(x.dtype)),
        (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=_DIMS, precision=nm.precision,
        preferred_element_type=jnp.float32)
    return y.astype(nm.store)


def dense(x, w, b, nm: Numerics, tally=None):
    if tally is not None:
        tally.append(2 * x.shape[0] * w.shape[0] * w.shape[1])
    y = jnp.dot(nm.round_operand(x), nm.round_operand(w.astype(x.dtype)),
                precision=nm.precision,
                preferred_element_type=jnp.float32)
    return y + b.astype(jnp.float32)


def batchnorm_train(x, scale, bias, eps: float, nm: Numerics):
    """Normalise by this batch's own mean and (biased) variance."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(xf - mean), axis=(0, 1, 2))
    y = (xf - mean) * lax.rsqrt(var + eps) * scale + bias
    return y.astype(nm.store)


def batchnorm_eval(x, scale, bias, mean, var, eps: float, nm: Numerics):
    """Normalise by the stored running statistics."""
    dt = jnp.dtype(nm.store)
    mul = (scale * lax.rsqrt(var + eps))
    y = (x - mean.astype(dt)) * mul.astype(dt) + bias.astype(dt)
    return y.astype(nm.store)


def max_pool(x, window: int, stride: int, pad: int):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1),
        (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def softmax_cross_entropy(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def he_normal_fan_out(key, shape):
    """Normal with variance 2 / (receptive field x output channels)."""
    fan_out = math.prod(shape[:-2]) * shape[-1]
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(
        2.0 / fan_out)


def he_normal_fan_in(key, shape):
    """Normal with variance 2 / (receptive field x input channels)."""
    fan_in = math.prod(shape[:-1])
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(
        2.0 / fan_in)


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 (the
    driver's seeds do not fit 32 signed bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))


def nest(flat: dict) -> dict:
    """``{("a", "b"): leaf}`` back to nested dicts: the inverse of
    :func:`tree_paths`."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def tree_paths(tree, prefix=()):
    """Flatten nested dicts to ``{("a", "b"): leaf}``, keys sorted."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(tree_paths(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out
