"""A hyper-connected latent-attention decoder with one multi-token
prediction module (Xing4.0-29B-A4B): DeepSeek-V3's attention, router and
experts on a residual of several streams that manifold-constrained
hyper-connections (mHC) mix.

Built from ``models/latent_moe.py`` (``_LatentAttention`` with a
compressed query and a head share, ``_BiasedMoe``, ``_GatedMlp``,
``balance_router_bias``) and ``models/transformer.py`` (``rms_norm``,
``yarn_angles``, ``_stacked``, ``token_nll``). What is its own:

- **The residual is ``n`` streams** ``X [B, T, n, C]`` in the compute
  dtype, carried side by side as ``[B, T, n C]``. The input is the
  embedding copied into every stream; the readout is their sum. Around
  each sublayer ``F`` (attention; MLP or expert layer) the
  hyper-connection (DeepSeek's mHC, on the
  Hyper-Connections of Zhu et al.) computes, per token, from ``x~ =
  RMSNorm(vec X_t)`` (width ``n C``, no scale of its own):
  ``H_pre = sigmoid(a_pre (x~ phi_pre) + b_pre)``, ``H_post = 2
  sigmoid(a_post (x~ phi_post) + b_post)`` and ``H_res =
  Sinkhorn(exp(clip(a_res mat(x~ phi_res) + b_res, +-clamp)))``, rows and
  columns normalised in turn ``sinkhorn_iters`` times with ``hc_eps`` in
  each denominator (a doubly stochastic ``n x n`` matrix); then ``X' =
  H_res X + H_post^T F(H_pre X)``. The maps ``phi`` are one ``[n C, n (n
  + 2)]`` product in float32 at HIGHEST (as the router's), and the
  sigmoids, the clamp, the exponent and the iterations are float32; the
  mixes read and write the streams in the compute dtype. On one TPU
  chip the maps, the mixes and their backward run as the Pallas kernels
  of ``ops/mhc.py`` (:func:`mhc_engages`), whose stream cotangents stay
  in the compute dtype; elsewhere in XLA, the kernels' yardstick.
- **Multi-token prediction** (DeepSeek-V3 section 2.2, depth 1): ``h'_t
  = M [RMSNorm(readout_t); RMSNorm(Emb(tok_{t+1}))]``, one expert block
  with hyper-connections of its own, a norm and the shared head, which
  predicts ``tok_{t+2}``. The readout is taken before the main final
  norm; embedding and head are the main model's.
- **Yarn rotary** and its softmax scale ``mscale^2 / sqrt(nope + rope)``.

Named scopes: those of ``latent_moe`` inside the sublayers, ``lm/mhc``
(the maps, the Sinkhorn iterations, the pre- and post-mixes and the
readouts, forward and backward), ``lm/mtp`` (``M``, its block, its head
and loss) and ``lm/head``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepvision_tpu.core.precision import compute_dot, float32_dot
from deepvision_tpu.models import transformer
from deepvision_tpu.models.latent_moe import (
    LatentConfig,
    _BiasedMoe,
    _GatedMlp,
    _LatentAttention,
    causal_pairs,
)
from deepvision_tpu.models.registry import register
from deepvision_tpu.models.transformer import (
    RMSNorm,
    _stacked,
    normal,
    token_nll,
    yarn_angles,
    yarn_mscale,
)

Dtype = Any
F32 = jnp.float32


# ------------------------------------------------------- hyper-connections


@dataclasses.dataclass(frozen=True)
class HyperConfig:
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    res_clamp: float
    rms_eps: float


def sinkhorn(m, iters: int, eps: float):
    """Rows, then columns, of the positive ``m [..., n, n]`` normalised
    to sum 1, ``iters`` times, ``eps`` in each denominator."""
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def sinkhorn_error(m):
    """The largest ``|row or column sum - 1|`` of each ``[n, n]``."""
    rows = jnp.abs(jnp.sum(m, -1) - 1.0)
    cols = jnp.abs(jnp.sum(m, -2) - 1.0)
    return jnp.maximum(jnp.max(rows, -1), jnp.max(cols, -1))


class HyperMaps(nn.Module):
    """``X [B, T, n, C]`` -> (``H_pre [B, T, n]``, ``H_post [B, T, n]``,
    ``H_res [B, T, n, n]``, float32; the Sinkhorn error's largest value
    of each sample ``[B]``); ``weights_only`` -> (``phi``, ``alpha``,
    ``b``), for the kernels of ``ops/mhc.py``."""

    hc: HyperConfig

    @nn.compact
    def __call__(self, x, weights_only: bool = False):
        b, t, n, d = x.shape
        phi = self.param("phi", normal, (n * d, n * (n + 2)))
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,))
        bias = self.param("b", nn.initializers.zeros, (n * (n + 2),))
        if weights_only:
            return phi, alpha, bias
        hc = self.hc
        with jax.named_scope("lm/mhc"):
            flat = x.reshape(b, t, n * d)
            inv = lax.rsqrt(jnp.mean(jnp.square(flat.astype(F32)), -1,
                                     keepdims=True) + hc.rms_eps)
            proj = float32_dot(flat, phi) * inv            # x~ phi
            pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
            post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                        + bias[n:2 * n])
            res = (alpha[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(
                b, t, n, n)
            res = sinkhorn(jnp.exp(jnp.clip(res, -hc.res_clamp, hc.res_clamp)),
                           hc.sinkhorn_iters, hc.hc_eps)
            err = jnp.max(sinkhorn_error(res), -1)
        return pre, post, res, err


def mhc_engages(tokens: int, streams: int, width: int,
                dtype=jnp.bfloat16) -> bool:
    """The kernels of ``ops/mhc.py`` take 4 streams in bf16 whose width
    fills lane rows, on one TPU chip, whatever the token count (a count
    the tile does not divide is padded); everything else is the XLA form
    of :class:`HyperMaps` and :func:`hyper_sublayer`."""
    return (tokens > 0 and streams == 4 and width % 128 == 0
            and jnp.dtype(dtype) == jnp.bfloat16
            and transformer._on_one_tpu())


def hyper_sublayer(maps, x, sublayer, dtype):
    """``X' = H_res X + H_post^T F(H_pre X)``; ``sublayer`` returns
    (``F``, what else it has to say). ``x`` is ``[B, T, n, C]`` or, as
    the model carries it, the streams side by side ``[B, T, n C]``. ->
    (``X'`` in ``x``'s layout, the Sinkhorn error, that). Through the
    kernels where :func:`mhc_engages`, else in XLA; a registry counter
    says which."""
    from deepvision_tpu.obs.metrics import record_mhc_site

    n = maps.hc.streams
    b, t = x.shape[:2]
    d = x.shape[-1] // n if x.ndim == 3 else x.shape[-1]
    x4 = x.reshape(b, t, n, d)
    by_kernel = mhc_engages(b * t, n, d, x.dtype)
    if by_kernel:
        phi, alpha, bias = maps(x4, weights_only=True)
    else:
        pre, post, res, err = maps(x4)
    if not maps.is_initializing():      # a shape trace runs nowhere
        record_mhc_site(by_kernel)
    if by_kernel:
        out, err, aux = _kernel_sublayer(x.reshape(b * t, n * d), b, phi,
                                         alpha, bias, maps.hc, sublayer)
        return out.reshape(x.shape), err, aux
    with jax.named_scope("lm/mhc"):
        xf = x4.astype(F32)
        u = jnp.sum(pre[..., None] * xf, 2).astype(dtype)
    y, aux = sublayer(u)
    with jax.named_scope("lm/mhc"):
        mixed = jnp.sum(res[..., None] * xf[:, :, None], 3)
        out = (mixed + post[..., None] * y.astype(F32)[:, :, None])
    return out.astype(dtype).reshape(x.shape), err, aux


def _kernel_sublayer(x, b: int, phi, alpha, bias, hc, sublayer):
    """:func:`hyper_sublayer` through ``ops/mhc.py`` on the streams side
    by side, ``x [B T, n C]`` in bf16 both ways; ``sublayer`` takes ``[B,
    T, C]``."""
    from deepvision_tpu.ops import mhc

    tokens = x.shape[0]
    with jax.named_scope("lm/mhc"):
        u, h, xs = mhc.premix(x, phi, alpha, bias, hc)
    y, aux = sublayer(u.reshape(b, -1, u.shape[-1]))
    with jax.named_scope("lm/mhc"):
        out = mhc.postmix(xs, y.reshape(tokens, -1), h)
        err = jnp.max(mhc.sinkhorn_error(h).reshape(b, -1), -1)
    return out, err, aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def streams_of(h, n: int):
    """``[B, T, C]`` copied into ``n`` streams side by side: ``[B, T, n
    C]``; the way back sums the streams' cotangents in float32."""
    return jnp.concatenate([h] * n, -1)


def _streams_of_fwd(h, n):
    return streams_of(h, n), None


def _streams_of_bwd(n, _, dx):
    return (readout(dx, n, dx.dtype),)


streams_of.defvjp(_streams_of_fwd, _streams_of_bwd)


def readout(x, n: int, dtype):
    """The sum of the ``n`` streams of ``[B, T, n C]``, in float32 one
    stream at a time."""
    d = x.shape[-1] // n
    with jax.named_scope("lm/mhc"):
        total = x[..., :d].astype(F32)
        for s in range(1, n):
            total = total + x[..., s * d:(s + 1) * d].astype(F32)
        return total.astype(dtype)


# ----------------------------------------------------------------- blocks


def _attention(c, u, angles):
    """The attention sublayer of a block: its norm, then latent
    attention (modules of the calling block)."""
    return _LatentAttention(c, name="attn")(
        RMSNorm(c.rms_eps, name="attn_norm")(u), angles)


class HyperDenseBlock(nn.Module):
    """A scan's body: -> (``X``, ``{"mhc_sinkhorn_err": [B]}``)."""

    cfg: LatentConfig
    hc: HyperConfig

    @nn.compact
    def __call__(self, x, angles):
        c = self.cfg
        x, e1, _ = hyper_sublayer(
            HyperMaps(self.hc, name="attn_hc"), x,
            lambda u: (_attention(c, u, angles), None), c.dtype)

        def mlp(u):
            h = RMSNorm(c.rms_eps, name="mlp_norm")(u)
            with jax.named_scope("lm/dense_mlp"):
                return _GatedMlp(c.dense_width, c.dtype, name="mlp")(h), None

        x, e2, _ = hyper_sublayer(HyperMaps(self.hc, name="mlp_hc"), x, mlp,
                                  c.dtype)
        return x, {"mhc_sinkhorn_err": jnp.maximum(e1, e2)}


class HyperExpertBlock(nn.Module):
    """A scan's body: -> (``X``, the block's routing statistics and
    Sinkhorn error)."""

    cfg: LatentConfig
    hc: HyperConfig

    @nn.compact
    def __call__(self, x, angles):
        c = self.cfg
        b, t = x.shape[:2]
        d = x.shape[-1] // self.hc.streams
        x, e1, _ = hyper_sublayer(
            HyperMaps(self.hc, name="attn_hc"), x,
            lambda u: (_attention(c, u, angles), None), c.dtype)

        def moe(u):
            h = RMSNorm(c.rms_eps, name="moe_norm")(u)
            y, experts, dropped = _BiasedMoe(c, name="moe")(
                h.reshape(b * t, d))
            return y.reshape(b, t, d), (experts, dropped)

        x, e2, (experts, dropped) = hyper_sublayer(
            HyperMaps(self.hc, name="moe_hc"), x, moe, c.dtype)
        experts = experts.reshape(b, t, -1)
        # every expert is counted, absent ones too (as latent_moe's)
        stats = {
            "expert_counts": jnp.sum(jax.nn.one_hot(
                experts, c.num_experts, dtype=jnp.int32), (1, 2)),
            "moe_dropped": jnp.broadcast_to(dropped, (b,)),
            "mhc_sinkhorn_err": jnp.maximum(e1, e2),
        }
        if c.capture:
            stats["experts"] = experts
        return x, stats


class MtpModule(nn.Module):
    """DeepSeek-V3's multi-token prediction at depth 1: the main
    readout and the next token's embedding ``[B, T, C]`` -> (the hidden
    state the shared head reads ``[B, T, C]``, the block's statistics).
    ``block`` is the expert block's class (recomputed or not)."""

    cfg: LatentConfig
    hc: HyperConfig
    block: Any

    @nn.compact
    def __call__(self, hidden, embedded, angles):
        c = self.cfg
        d = hidden.shape[-1]
        proj = self.param("proj", normal, (2 * d, d))
        h = jnp.concatenate([RMSNorm(c.rms_eps, name="hnorm")(hidden),
                             RMSNorm(c.rms_eps, name="enorm")(embedded)], -1)
        h = compute_dot(h, proj, c.dtype).astype(c.dtype)
        x, stats = self.block(c, self.hc, name="block")(
            streams_of(h, self.hc.streams), angles)
        return RMSNorm(c.rms_eps, name="final_norm")(
            readout(x, self.hc.streams, c.dtype)), stats


# ------------------------------------------------------------------ model


class HyperLatentLM(nn.Module):
    """``{"tokens": [B, L]}`` -> per-sample results: ``nll [B, L - 1]``
    (position ``i`` predicts token ``i + 1``), ``mtp_nll [B, L - 2]``
    (the module's prediction of token ``i + 2`` at position ``i``),
    ``expert_counts [B, expert layers + 1, all experts]`` (the MTP
    block last), ``expert_tokens`` (those of the experts held here),
    ``moe_dropped [B]``, ``mhc_sinkhorn_err [B]`` (the largest
    ``|row or column sum - 1|`` of any ``H_res``) and ``causal_pairs
    [B]``. ``logits=True`` adds the main head's ``logits``; ``capture``
    each expert layer's routing choice, the MTP block's last.

    ``vocab_size``, the experts held (``num_experts`` over
    ``expert_share[1]``) and the heads held (``heads`` over
    ``head_share[1]``) may be one chip's share of a deployment.
    ``num_layers`` counts the ``dense_layers`` at its head and not the
    MTP block."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    dense_layers: int = 2
    heads: int = 32
    head_share: tuple = (0, 1)
    q_rank: int = 768
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    dense_width: int = 9216
    num_experts: int = 64
    experts_per_token: int = 4
    expert_share: tuple = (0, 1)
    moe_width: int = 1024
    shared_experts: int = 1
    norm_topk: bool = True
    gate_scale: float = 2.0
    streams: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    res_clamp: float = 30.0
    sample_text_len: int = 13
    key_block: int = 2048
    q_chunk: int = 512
    remat: str | None = "layer"
    capture: bool = False
    dtype: Dtype = jnp.bfloat16

    def sample_input(self) -> dict:
        return {"tokens": np.zeros((1, self.sample_text_len), np.int32)}

    @nn.compact
    def __call__(self, inputs, train: bool = False, logits: bool = False):
        del train                                # no dropout, no statistics
        dt, n = self.dtype, self.streams
        tokens = inputs["tokens"]
        t = tokens.shape[1] - 1
        embed = self.param("embed", nn.initializers.normal(0.1),
                           (self.vocab_size, self.hidden_size))
        angles = yarn_angles(t, self.rope_dim // 2, self.rope_theta,
                             self.rope_factor, self.rope_original,
                             self.beta_fast, self.beta_slow)
        scale = yarn_mscale(self.rope_factor, self.mscale_all_dim) ** 2 \
            / math.sqrt(self.nope_dim + self.rope_dim)
        cfg = LatentConfig(
            self.heads, self.nope_dim, self.rope_dim, self.v_dim,
            self.kv_rank, self.dense_width, self.num_experts,
            self.experts_per_token, tuple(self.expert_share),
            self.moe_width, self.shared_experts, self.norm_topk,
            self.gate_scale, self.rms_eps, self.key_block, self.q_chunk,
            self.capture, dt, q_rank=self.q_rank,
            head_share=tuple(self.head_share), softmax_scale=scale)
        hc = HyperConfig(n, self.sinkhorn_iters, self.hc_eps,
                         self.res_clamp, self.rms_eps)
        dense, layer = HyperDenseBlock, HyperExpertBlock
        if self.remat is not None:
            keep = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mla_lse")
            dense = nn.remat(HyperDenseBlock, prevent_cse=False, policy=keep)
            layer = nn.remat(HyperExpertBlock, prevent_cse=False,
                             policy=keep)
        x = streams_of(embed[tokens[:, :-1]].astype(dt), n)
        x, dense_stats = _stacked(dense, self.dense_layers)(
            cfg, hc, name="dense")(x, angles)
        # every expert block's statistic, stacked [layers, B, ...]
        x, stats = _stacked(layer, self.num_layers - self.dense_layers)(
            cfg, hc, name="layers")(x, angles)
        main = readout(x, n, dt)
        hidden = RMSNorm(self.rms_eps, name="final_norm")(main)
        head = self.param("lm_head", normal,
                          (self.hidden_size, self.vocab_size))
        with jax.named_scope("lm/mtp"):
            # position i reads token i + 1 and predicts token i + 2; the
            # last position has no target and is left out of the loss
            mtp_hidden, mtp = MtpModule(cfg, hc, layer, name="mtp")(
                main, embed[tokens[:, 1:]].astype(dt), angles)
            mtp_nll = token_nll(mtp_hidden[:, :-1], head, tokens[:, 2:], dt)
        per_sample = lambda a: jnp.moveaxis(a, 0, 1)
        counts = jnp.concatenate([per_sample(stats["expert_counts"]),
                                  mtp["expert_counts"][:, None]], 1)
        held = self.num_experts // self.expert_share[1]
        lo = self.expert_share[0] * held
        err = jnp.max(jnp.stack([
            jnp.max(dense_stats["mhc_sinkhorn_err"], 0),
            jnp.max(stats["mhc_sinkhorn_err"], 0),
            mtp["mhc_sinkhorn_err"]]), 0)
        blocks = self.num_layers + 1
        out = {
            "nll": token_nll(hidden, head, tokens[:, 1:], dt),
            "mtp_nll": mtp_nll,
            "expert_counts": counts,
            "expert_tokens": counts[..., lo:lo + held],
            "moe_dropped": jnp.sum(stats["moe_dropped"], 0)
            + mtp["moe_dropped"],
            "mhc_sinkhorn_err": err,
            "causal_pairs": jnp.full((tokens.shape[0],),
                                     blocks * causal_pairs(t), jnp.int32),
        }
        if logits:
            out["logits"] = compute_dot(hidden, head, dt)
        if self.capture:
            out["experts"] = jnp.concatenate(
                [per_sample(stats["experts"]), mtp["experts"][:, None]], 1)
        return out


# --------------------------------------------------------------- registry

# One chip's share of an 8-chip tensor- and expert-parallel group over
# the same tokens (benchmark/configs/xing4_29b_a4b.json): every width as
# published; the depth (one dense and 4 expert blocks, the MTP module),
# the heads (4 of 32), the experts (8 of 64) and the vocabulary rows
# (16,384 of 131,072) are the chip's.
_SHARE_OF_8 = dict(num_layers=5, dense_layers=1, vocab_size=16384,
                   head_share=(0, 8), expert_share=(0, 8))
# CPU-sized preset for tests and ``train.py -m xing4_tiny``; the yarn
# ramp lies inside its 4 rotary pairs.
_TINY = dict(
    vocab_size=128, hidden_size=64, num_layers=3, dense_layers=1, heads=4,
    head_share=(0, 2), q_rank=24, nope_dim=16, rope_dim=8, v_dim=16,
    kv_rank=32, rope_factor=4.0, rope_original=16, beta_fast=2.0,
    dense_width=96, num_experts=8, experts_per_token=2, expert_share=(0, 2),
    moe_width=32, key_block=32, q_chunk=8)


def _factory(defaults: dict):
    def make(dtype=jnp.bfloat16, **kwargs):
        kw = {**defaults, **kwargs}
        for key in ("expert_share", "head_share"):
            if key in kw:
                kw[key] = tuple(kw[key])
        return HyperLatentLM(dtype=dtype, **kw)
    return make


register("xing4", remat="layer")(_factory({}))
register("xing4_ep8tp8", remat="layer")(_factory(_SHARE_OF_8))
register("xing4_tiny", remat="layer")(_factory(_TINY))
