"""A text decoder in DeepSeek-V3's layout (kanana-2-30b-a3b): latent
attention, a sigmoid router behind a balancing bias, shared experts and
a leading dense layer.

The second token model, beside ``models/transformer.py``, whose parts it
is built from (``rms_norm``, ``rotate``, ``_stacked``, :func:`moe_layer`
with its sort, windows and grouped products, ``token_nll``). What is its
own:

- **Latent attention (MLA), no query compression.** ``q = h Wq`` is
  ``heads`` heads of ``[q_nope | q_rope]``; ``c = h Wkva`` is
  ``[c_kv | k_rope]``, a latent of ``kv_rank`` and one rotary key that
  every head shares; ``RMSNorm(c_kv) Wkvb`` is ``heads`` heads of
  ``[k_nope | v]``. Rotary (by the token's index) on ``q_rope`` of every
  head and on the one ``k_rope``; ``k_h = [k_nope_h | k_rope]``. Query
  and key heads (``nope_dim + rope_dim``) and value heads (``v_dim``)
  differ in width. Training materialises ``k_nope`` and ``v`` for every
  head, as the published forward does; the absorbed form is a decode
  matter. The attention is plain causal softmax attention
  (:func:`causal_attention`), blocked like the sparse one: queries in
  blocks of ``key_block`` against the keys up to the block's end, in
  chunks of ``q_chunk``, each chunk recomputed on the way back.
  ``models/hyper_latent.py`` builds its attention from the same module
  with a compressed query (``q_rank``), the heads of a ``head_share``
  and a softmax scale of its own (``LatentConfig``).
- **The expert layer** is :func:`transformer.moe_layer`, told the
  scoring rule (``sigmoid``), the selection bias and the gate scale;
  beside it one SiLU-gated MLP that every token passes (the shared
  experts, side by side). The bias is a parameter leaf
  (``params/layers/moe/bias``) that no gradient reaches: it enters only
  the discrete choice. :func:`balance_router_bias` is the rule that
  moves it (``b_e += rate * sign(mean load - load_e)``, DeepSeek-V3's
  auxiliary-loss-free balancing); the train step applies it after the
  optimiser's update.
- **The decoder**: layer 0 is dense (its own parameters,
  ``params/dense``), the expert layers behind it are one scanned body
  (``params/layers``, stacked). Text only: the input is ``{"tokens": [B,
  L]}``, position ``i`` predicts token ``i + 1``.

Numerics as the sibling's: parameters float32, every multiply takes
``dtype`` operands and accumulates in float32, norms, router scores (a
float32 product at HIGHEST), the exponent and the sum of the softmax and
the loss are float32. Named scopes: ``lm/mla/proj`` (the four
projections, the latent's norm, the rotary), ``lm/mla/attn`` (either
form, forward and backward),
``lm/dense_mlp``, ``lm/moe/route|experts`` (the shared function's),
``lm/moe/shared``, ``lm/head``.
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
from jax.ad_checkpoint import checkpoint_name

from deepvision_tpu.core.precision import compute_dot, compute_einsum
from deepvision_tpu.models import transformer
from deepvision_tpu.models.registry import register
from deepvision_tpu.models.transformer import (
    RMSNorm,
    _blocks,
    _causal,
    _map_chunks,
    _stacked,
    moe_layer,
    normal,
    rope_angles,
    rotate,
    token_nll,
)

Dtype = Any


# ------------------------------------------------------- causal attention


def _attend(q, k, v, mask, dtype, scale=None):
    """Softmax attention of ``q [Tq, heads, dq]`` over the keys ``mask
    [Tq, Tk]`` keeps; ``k [Tk, heads, dq]``, ``v [Tk, heads, dv]``;
    logits times ``scale`` (by default ``1 / sqrt(dq)``).
    -> ``[Tq, heads x dv]``. The softmax is shifted by a bound known
    before the logits, as ``transformer._attend``'s is, and for its
    reasons: the exponentials leave the first product's fusion in the
    compute dtype, the second product takes them unnormalised."""
    tq, heads, dq = q.shape
    scale = 1.0 / math.sqrt(dq) if scale is None else scale
    with jax.named_scope("lm/mla/attn"):
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)), -1))
        bound = norm(q).T * jnp.max(norm(k), 0)[:, None] * scale   # [H, Tq]
        logits = compute_einsum("thd,shd->hts", q, k, dtype) * scale
        weights = jnp.where(mask, jnp.exp(jnp.maximum(
            logits - lax.stop_gradient(bound)[..., None], -80.0)), 0.0)
        # materialised once, in the compute dtype (see _attend there)
        weights = lax.optimization_barrier(weights.astype(dtype))
        total = jnp.sum(weights.astype(jnp.float32), -1)           # [H, Tq]
        out = compute_einsum("hts,shd->thd", weights, v, dtype)
        out = out / total.T[..., None]
    return out.reshape(tq, -1).astype(dtype)


def causal_attention(q, k, v, *, key_block: int, q_chunk: int, dtype,
                     scale=None):
    """One sequence: ``q``, ``k`` ``[T, heads, dq]`` (rotated), ``v [T,
    heads, dv]`` -> ``[T, heads x dv]``; ``scale`` as :func:`_attend`'s."""
    t = q.shape[0]
    block, chunk = _blocks(t, key_block, q_chunk)
    outs = []
    for b0 in range(0, t, block):
        end = b0 + block

        @jax.checkpoint
        def one(args, end=end):
            (qc,), t0 = args
            return _attend(qc, k[:end], v[:end], _causal(t0, chunk, end),
                           dtype, scale)

        outs.append(_map_chunks(one, (q,), b0, block, chunk).reshape(
            block, -1))
    return jnp.concatenate(outs)


def causal_pairs(t: int) -> int:
    """Query-key pairs of one causal sequence of ``t`` positions."""
    return t * (t + 1) // 2


# The same attention through the latent kernels of ops/dsa_attention.py,
# which keep the [heads, Tq, Tk] tiles on the chip and take the score as
# q_nope . k_nope + q_rope . k_rope, the one rotary key never broadcast.
# Which of the two a call site takes is read from the backend and the
# shapes (:func:`mla_engages`), and two registry counters say which it
# was.


def mla_engages(t: int, nope_dim: int, rope_dim: int, v_dim: int,
                key_block: int, q_chunk: int) -> bool:
    """The kernels take score heads whose own part and value heads that
    fill lane rows, a rotary part of half a lane row or whole ones and
    chunks of queries that fill lane rows, on one TPU chip; everything
    else is :func:`causal_attention`'s."""
    chunk = _blocks(t, key_block, q_chunk)[1]
    return (transformer._on_one_tpu() and nope_dim % 128 == 0
            and v_dim % 128 == 0 and rope_dim % 64 == 0 and chunk % 128 == 0)


def _kernel_forward(q, q_rope, kv, k_rope, key_block, q_chunk, scale):
    """:func:`causal_attention` of every sequence in one kernel call. ->
    (``[B, T, heads x dv]``, log-sum-exp ``[B, heads, T]``)"""
    from deepvision_tpu.ops import dsa_attention as dsa

    t = q.shape[1]
    heads = q_rope.shape[-1] // k_rope.shape[-1]
    block, chunk = _blocks(t, key_block, q_chunk)
    with jax.named_scope("lm/mla/attn"):
        kmax = dsa.latent_key_norms(kv, k_rope, heads, q.shape[-1] // heads,
                                    block)
        return dsa.latent_forward(q, q_rope, kv, k_rope, kmax, q_chunk=chunk,
                                  scale=scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def kernel_attention(q, q_rope, kv, k_rope, key_block, q_chunk, scale=None):
    """Causal latent attention of a batch through the kernels, heads side
    by side as the projections write them: ``q [B, T, heads x dn]``,
    ``q_rope [B, T, heads x dr]`` and the one rotary key ``k_rope [B, T,
    dr]`` (both rotated), ``kv [B, T, heads x (dn + dv)]`` (each head's
    key, then its values) -> ``[B, T, heads x dv]``; the logits' ``scale``
    is by default ``1 / sqrt(dn + dr)``.

    The backward is written out (``custom_vjp``), as
    ``transformer.kernel_attention``'s: what the forward keeps is the
    output and each row's log-sum-exp, named ``attn_out`` and
    ``mla_lse`` so that a recomputed layer keeps them too and never
    runs the forward kernel twice; one backward kernel call writes
    ``dq``, ``dq_rope``, ``dk_rope`` and the cotangent of ``kv`` in its
    layout."""
    return _kernel_forward(q, q_rope, kv, k_rope, key_block, q_chunk,
                           scale)[0]


def _kernel_attention_fwd(q, q_rope, kv, k_rope, key_block, q_chunk, scale):
    o, lse = _kernel_forward(q, q_rope, kv, k_rope, key_block, q_chunk,
                             scale)
    o, lse = checkpoint_name(o, "attn_out"), checkpoint_name(lse, "mla_lse")
    return o, (q, q_rope, kv, k_rope, o, lse)


def _kernel_attention_bwd(key_block, q_chunk, scale, kept, do):
    from deepvision_tpu.ops import dsa_attention as dsa

    q, q_rope, kv, k_rope, o, lse = kept
    b, t, heads = q.shape[0], q.shape[1], lse.shape[1]
    f32 = jnp.float32
    with jax.named_scope("lm/mla/attn"):
        di = jnp.sum((o.astype(f32) * do.astype(f32)).reshape(
            b, t, heads, -1), -1)
        return dsa.latent_backward(
            q, q_rope, kv, k_rope, lse, jnp.swapaxes(di, 1, 2), do,
            q_chunk=_blocks(t, key_block, q_chunk)[1], scale=scale)


kernel_attention.defvjp(_kernel_attention_fwd, _kernel_attention_bwd)


# ----------------------------------------------------------------- layers


def gated_mlp(h, gate, up, down, dtype):
    """``(silu(h gate) * (h up)) down``."""
    mid = (jax.nn.silu(compute_dot(h, gate, dtype))
           * compute_dot(h, up, dtype)).astype(dtype)
    return compute_dot(mid, down, dtype).astype(dtype)


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """What a layer is built from (hashable: it rides module fields and
    the jit cache key)."""

    heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    dense_width: int
    num_experts: int
    experts_per_token: int
    expert_share: tuple
    moe_width: int
    shared_experts: int
    norm_topk: bool
    gate_scale: float
    rms_eps: float
    key_block: int
    q_chunk: int
    capture: bool = False
    dtype: Dtype = jnp.bfloat16
    # the query through a latent of this rank (``q_a``, its norm,
    # ``q_b``), or one product (``q``) where None
    q_rank: int | None = None
    # (index, of): the heads this chip holds of ``heads``, and so the
    # part of the output they give
    head_share: tuple = (0, 1)
    # the logits' scale, by default 1 / sqrt(nope_dim + rope_dim)
    softmax_scale: float | None = None


class _LatentAttention(nn.Module):
    cfg: LatentConfig

    @nn.compact
    def __call__(self, h, angles):
        from deepvision_tpu.obs.metrics import record_latent_site

        c, dt = self.cfg, self.cfg.dtype
        b, t, d = h.shape
        heads = c.heads // c.head_share[1]                  # held here
        dn, dr, dv = c.nope_dim, c.rope_dim, c.v_dim
        if c.q_rank is None:
            wq = self.param("q", normal, (d, heads * (dn + dr)))
        else:
            wqa = self.param("q_a", normal, (d, c.q_rank))
            q_norm = RMSNorm(c.rms_eps, name="q_norm")
            wqb = self.param("q_b", normal, (c.q_rank, heads * (dn + dr)))
        wkva = self.param("kv_a", normal, (d, c.kv_rank + dr))
        wkvb = self.param("kv_b", normal, (c.kv_rank, heads * (dn + dv)))
        wo = self.param("o", normal, (heads * dv, d))
        by_kernel = mla_engages(t, dn, dr, dv, c.key_block, c.q_chunk)
        if not self.is_initializing():   # a shape trace runs nowhere
            record_latent_site(by_kernel)
        with jax.named_scope("lm/mla/proj"):
            if c.q_rank is None:
                q = compute_dot(h, wq, dt).astype(dt)
            else:
                q = compute_dot(q_norm(compute_dot(h, wqa, dt).astype(dt)),
                                wqb, dt).astype(dt)
            q = q.reshape(b, t, heads, dn + dr)
            q, q_rope = q[..., :dn], rotate(q[..., dn:], angles)
            latent = compute_dot(h, wkva, dt).astype(dt)
            c_kv = RMSNorm(c.rms_eps, name="kv_norm")(
                latent[..., :c.kv_rank])
            k_rope = rotate(latent[..., None, c.kv_rank:], angles)
            kv = compute_dot(c_kv, wkvb, dt).astype(dt)
            if by_kernel:
                # the kernels read heads side by side in lane rows
                q, q_rope = q.reshape(b, t, -1), q_rope.reshape(b, t, -1)
            else:
                kv = kv.reshape(b, t, heads, dn + dv)
                k, v = kv[..., :dn], kv[..., dn:]
                # the one rotary key stands in every head's
                q = jnp.concatenate([q, q_rope], -1)
                k = jnp.concatenate([k, jnp.broadcast_to(
                    k_rope, (b, t, heads, dr))], -1)
        if by_kernel:
            o = kernel_attention(q, q_rope, kv, k_rope[:, :, 0], c.key_block,
                                 c.q_chunk, c.softmax_scale)
        else:
            o = lax.map(lambda a: causal_attention(
                *a, key_block=c.key_block, q_chunk=c.q_chunk, dtype=dt,
                scale=c.softmax_scale), (q, k, v))
            # kept across the layer's recomputation: the way back then
            # recomputes each chunk once, not twice
            o = checkpoint_name(o, "attn_out")
        with jax.named_scope("lm/mla/proj"):
            return compute_dot(o, wo, dt).astype(dt)


class _GatedMlp(nn.Module):
    width: int
    dtype: Dtype

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        gate = self.param("gate", normal, (d, self.width))
        up = self.param("up", normal, (d, self.width))
        down = self.param("down", normal, (self.width, d))
        return gated_mlp(h, gate, up, down, self.dtype)


class _BiasedMoe(nn.Module):
    """The routed experts held here and the shared expert: -> (``[N,
    hidden]``, chosen experts ``[N, k]``, dropped assignments)."""

    cfg: LatentConfig

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        d, held = h.shape[-1], c.num_experts // c.expert_share[1]
        router = self.param("router", normal, (d, c.num_experts))
        bias = self.param("bias", nn.initializers.zeros, (c.num_experts,))
        gate = self.param("gate", normal, (held, d, c.moe_width))
        up = self.param("up", normal, (held, d, c.moe_width))
        down = self.param("down", normal, (held, c.moe_width, d))
        y, experts, dropped = moe_layer(
            h, router, gate, up, down,
            experts_per_token=c.experts_per_token, norm_topk=c.norm_topk,
            expert_share=tuple(c.expert_share), dtype=c.dtype,
            scoring="sigmoid", bias=bias, gate_scale=c.gate_scale)
        with jax.named_scope("lm/moe/shared"):
            y = y + _GatedMlp(c.shared_experts * c.moe_width, c.dtype,
                              name="shared")(h)
        return y, experts, dropped


class DenseLayer(nn.Module):
    cfg: LatentConfig

    @nn.compact
    def __call__(self, x, angles):
        c = self.cfg
        h = RMSNorm(c.rms_eps, name="attn_norm")(x)
        x = x + _LatentAttention(c, name="attn")(h, angles)
        h = RMSNorm(c.rms_eps, name="mlp_norm")(x)
        with jax.named_scope("lm/dense_mlp"):
            return x + _GatedMlp(c.dense_width, c.dtype, name="mlp")(h)


class ExpertLayer(nn.Module):
    """A scan's body: -> (``x``, the layer's routing statistics)."""

    cfg: LatentConfig

    @nn.compact
    def __call__(self, x, angles):
        c = self.cfg
        b, t, d = x.shape
        h = RMSNorm(c.rms_eps, name="attn_norm")(x)
        x = x + _LatentAttention(c, name="attn")(h, angles)
        h = RMSNorm(c.rms_eps, name="moe_norm")(x)
        y, experts, dropped = _BiasedMoe(c, name="moe")(h.reshape(b * t, d))
        experts = experts.reshape(b, t, -1)
        # every expert is counted, absent ones too: the router is whole
        # on every chip, and the balancing rule reads all of its outputs
        stats = {
            "expert_counts": jnp.sum(jax.nn.one_hot(
                experts, c.num_experts, dtype=jnp.int32), (1, 2)),
            # no sample owns a dropped assignment: every sample carries
            # the layer's count
            "moe_dropped": jnp.broadcast_to(dropped, (b,)),
        }
        if c.capture:
            stats["experts"] = experts
        return x + y.reshape(b, t, d).astype(c.dtype), stats


class LatentMoeLM(nn.Module):
    """``{"tokens": [B, L]}`` -> per-sample results of the next-token
    task: ``nll [B, L - 1]`` (position ``i`` predicts token ``i + 1``),
    ``expert_counts [B, expert layers, all experts]`` (tokens that chose
    each expert, whoever holds it), ``expert_tokens [B, expert layers,
    held experts]`` (those of the experts held here), ``moe_dropped
    [B]`` and ``causal_pairs [B]`` (query-key pairs attention ran over,
    all layers). ``logits=True`` adds ``logits [B, L - 1, vocab]`` (short
    sequences); ``capture`` adds each expert layer's routing choice.

    ``vocab_size`` and the experts held (``num_experts`` over
    ``expert_share[1]``) may be one chip's share of a deployment; the
    router keeps its ``num_experts`` outputs and its bias. ``num_layers``
    counts the leading dense layer."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    heads: int = 32
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    dense_width: int = 6144
    num_experts: int = 128
    experts_per_token: int = 6
    expert_share: tuple = (0, 1)
    moe_width: int = 768
    shared_experts: int = 2
    norm_topk: bool = True
    gate_scale: float = 2.448
    sample_text_len: int = 13
    key_block: int = 2048
    q_chunk: int = 512
    remat: str | None = "layer"
    capture: bool = False
    dtype: Dtype = jnp.bfloat16

    def sample_input(self) -> dict:
        """What ``init`` traces: parameter shapes do not depend on the
        text's length."""
        return {"tokens": np.zeros((1, self.sample_text_len), np.int32)}

    @nn.compact
    def __call__(self, inputs, train: bool = False, logits: bool = False):
        del train                                # no dropout, no statistics
        dt = self.dtype
        tokens = inputs["tokens"]
        t = tokens.shape[1] - 1
        embed = self.param("embed", nn.initializers.normal(0.1),
                           (self.vocab_size, self.hidden_size))
        x = embed[tokens[:, :-1]].astype(dt)
        angles = rope_angles(t, self.rope_dim // 2, self.rope_theta)
        cfg = LatentConfig(
            self.heads, self.nope_dim, self.rope_dim, self.v_dim,
            self.kv_rank, self.dense_width, self.num_experts,
            self.experts_per_token, tuple(self.expert_share),
            self.moe_width, self.shared_experts, self.norm_topk,
            self.gate_scale, self.rms_eps, self.key_block, self.q_chunk,
            self.capture, dt)
        dense, layer = DenseLayer, ExpertLayer
        if self.remat is not None:
            keep = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mla_lse")
            dense = nn.remat(DenseLayer, policy=keep)
            layer = nn.remat(ExpertLayer, prevent_cse=False, policy=keep)
        x = dense(cfg, name="dense")(x, angles)
        # every expert layer's statistic, stacked [layers, B, ...]
        x, stats = _stacked(layer, self.num_layers - 1)(cfg, name="layers")(
            x, angles)
        per_sample = lambda a: jnp.moveaxis(a, 0, 1)
        hidden = RMSNorm(self.rms_eps, name="final_norm")(x)
        head = self.param("lm_head", normal,
                          (self.hidden_size, self.vocab_size))
        counts = per_sample(stats["expert_counts"])
        held = self.num_experts // self.expert_share[1]
        lo = self.expert_share[0] * held
        out = {
            "nll": token_nll(hidden, head, tokens[:, 1:], dt),
            "expert_counts": counts,
            "expert_tokens": counts[..., lo:lo + held],
            "moe_dropped": jnp.sum(stats["moe_dropped"], 0),
            "causal_pairs": jnp.full(
                (tokens.shape[0],), self.num_layers * causal_pairs(t),
                jnp.int32),
        }
        if logits:
            out["logits"] = compute_dot(hidden, head, dt)
        if self.capture:
            out["experts"] = per_sample(stats["experts"])
        return out


# ---------------------------------------------------------- the bias rule


def is_router_bias(path) -> bool:
    """Whether a parameter leaf's key path names an expert layer's
    selection bias (``.../moe/bias``)."""
    names = [getattr(k, "key", None) for k in path[-2:]]
    return names == ["moe", "bias"]


def balance_router_bias(params, expert_counts, rate: float):
    """``b_e += rate * sign(mean_e'(c_e') - c_e)`` on every expert
    layer's selection bias: ``expert_counts [expert layers, all
    experts]`` are the tokens of the step's batch that chose each
    expert. An overloaded expert's entry falls by ``rate``, an
    underloaded one's rises; every other leaf is returned as it is.
    Where several leaves hold biases (a stack of layers ``[layers,
    experts]``, a lone layer's ``[experts]``), they take the rows of
    ``expert_counts`` one after another in the tree's order."""
    c = expert_counts.astype(jnp.float32)
    step = rate * jnp.sign(jnp.mean(c, -1, keepdims=True) - c)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves, row = [], 0
    for path, leaf in flat:
        if is_router_bias(path):
            n = leaf.shape[0] if leaf.ndim == 2 else 1
            leaf = leaf + step[row:row + n].reshape(leaf.shape).astype(
                leaf.dtype)
            row += n
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves)


def router_bias_abs_mean(params):
    """Mean of ``|b|`` over every selection bias entry."""
    leaves = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(params)[0]
              if is_router_bias(path)]
    return jnp.mean(jnp.abs(jnp.concatenate([a.reshape(-1) for a in leaves])))


# --------------------------------------------------------------- registry

# One chip's share of the 8-chip expert-parallel deployment the benchmark
# measures (benchmark/configs/kanana2_30b_a3b.json): every width as
# published; depth (the dense layer and 5 expert layers), experts held
# and vocabulary rows are the chip's.
_SHARE_OF_8 = dict(num_layers=6, vocab_size=16032, expert_share=(0, 8))
# CPU-sized preset for tests and ``train.py -m kanana2_tiny``.
_TINY = dict(
    vocab_size=128, hidden_size=64, num_layers=3, heads=4, nope_dim=16,
    rope_dim=8, v_dim=16, kv_rank=32, dense_width=96, num_experts=8,
    experts_per_token=2, expert_share=(0, 2), moe_width=32,
    shared_experts=1, key_block=32, q_chunk=8)


def _factory(defaults: dict):
    def make(dtype=jnp.bfloat16, **kwargs):
        kw = {**defaults, **kwargs}
        if "expert_share" in kw:
            kw["expert_share"] = tuple(kw["expert_share"])
        return LatentMoeLM(dtype=dtype, **kw)
    return make


register("kanana2", remat="layer")(_factory({}))
register("kanana2_ep8", remat="layer")(_factory(_SHARE_OF_8))
register("kanana2_tiny", remat="layer")(_factory(_TINY))
