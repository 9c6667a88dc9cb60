"""A hybrid decoder of gated-delta-rule linear attention beside full
attention (Olmo-Hybrid-7B), with the two entry points generation needs:
a chunked prefill of one request into its cache slot and one recurrent
decode step over every slot.

One period is ``[linear, linear, linear, full]``; ``num_layers`` counts
every layer and is a multiple of 4. The block is Olmo-3's reordered
norm: ``h = x + RMSNorm(Mixer(x))``, ``out = h + RMSNorm(SwiGLU(h))``.

- **Linear attention** (Gated DeltaNet, Yang et al., arXiv:2412.06464, in
  FLA's layer form): ``[q, k, v] = SiLU(causal_depthwise_conv([x Wq, x
  Wk, x Wv]))`` with no bias; per head ``q <- q / |q| / sqrt(dk)``, ``k
  <- k / |k|``; ``beta = 2 sigmoid(x Wb)`` (negative eigenvalues
  allowed, Grazzi et al., arXiv:2411.12537); ``g = -exp(A_log)
  softplus(x Wa + dt_bias)``, ``alpha = exp(g)``; ``S_t = alpha_t (I -
  beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T`` with ``S`` of ``[dk,
  dv]`` a head; ``o_t = S_t^T q_t``; ``y = RMSNorm_dv(o) w * SiLU(x
  Wz)``; ``out = y Wo``. Prefill runs the chunk form (the WY/UT
  transform of sub-chunks of ``chunk`` tokens, their states passed on
  by a scan), decode the recurrence.
- **Full attention**: ``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` over
  the whole width, rotary on both (pairs ``(i, i + head_dim / 2)``),
  causal softmax at ``head_dim^-1/2`` over the keys and values of the
  slot's cache.

Parameters (``params``): ``embed [V, D]``, ``final_norm``, ``lm_head [D,
V]``, ``linear/*`` stacked ``[periods, 3, ...]`` and ``full/*`` stacked
``[periods, ...]``; the periods are one scanned body, its three linear
layers another.

The cache (:func:`cache_shapes`) holds, per slot: ``conv [periods, 3,
kernel - 1, conv_dim]`` and ``delta [periods, 3, heads, dk, dv]`` for
the linear layers, ``k`` and ``v [periods, max_len, heads, head_dim]``
for the full ones, and ``length``, all under one slot index. A prefill
call at ``start == 0`` zeroes the slot's recurrent state first, so a
reused slot carries nothing of its last request; keys and values are
written before they are read, and only positions below the length are
read.

Numerics: weights and every matrix product in ``dtype`` (bf16) with
float32 accumulation; the residual stream, the norms, the gates
``alpha`` and ``beta``, the softmax and the delta rule (its state, the
chunk form's products at HIGHEST) in float32; the conv state and the
keys and values are stored in ``dtype``, the delta state in float32.

Named scopes: ``lm/gdn`` (conv, gates, delta rule, gated norm),
``lm/linear_proj`` (the linear layers' five wide projections),
``lm/attn`` (the full layers: projections, norms, rotary, cache write,
softmax), ``lm/mlp`` and ``lm/head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepvision_tpu.core.precision import compute_dot, compute_einsum
from deepvision_tpu.models.latent_moe import gated_mlp
from deepvision_tpu.models.registry import register
from deepvision_tpu.models.transformer import rms_norm, rotate

Dtype = Any
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
PERIOD = ("linear_attention",) * 3 + ("full_attention",)
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Sizes and numerics (hashable: a static argument of the programs)."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    heads: int
    linear_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    mlp_width: int
    rms_eps: float
    rope_theta: float
    allow_neg_eigval: bool
    chunk: int          # the delta rule's sub-chunk
    key_block: int      # keys a step of the blocked softmax
    dtype: Dtype = jnp.bfloat16

    @property
    def periods(self) -> int:
        return self.num_layers // len(PERIOD)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.heads

    @property
    def conv_dim(self) -> int:
        return self.linear_heads * (2 * self.key_dim + self.value_dim)


# ------------------------------------------------------------ parameters


def param_shapes(cfg: HybridConfig) -> dict:
    """``{group: {leaf: shape}}`` of the parameters, stacks included."""
    d, f, p = cfg.hidden_size, cfg.mlp_width, cfg.periods
    hk = cfg.linear_heads * cfg.key_dim
    hv = cfg.linear_heads * cfg.value_dim
    mlp = {"gate": (d, f), "up": (d, f), "down": (f, d), "mlp_norm": (d,)}
    linear = {"wq": (d, hk), "wk": (d, hk), "wv": (d, hv), "wz": (d, hv),
              "wa": (d, cfg.linear_heads), "wb": (d, cfg.linear_heads),
              "conv": (cfg.conv_kernel, cfg.conv_dim),
              "A_log": (cfg.linear_heads,), "dt_bias": (cfg.linear_heads,),
              "o_norm": (cfg.value_dim,), "wo": (hv, d),
              "mixer_norm": (d,), **mlp}
    full = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "q_norm": (d,), "k_norm": (d,), "mixer_norm": (d,), **mlp}
    return {
        "embed": {"embed": (cfg.vocab_size, d)},
        "final_norm": {"final_norm": (d,)},
        "lm_head": {"lm_head": (d, cfg.vocab_size)},
        "linear": {k: (p, 3, *s) for k, s in linear.items()},
        "full": {k: (p, *s) for k, s in full.items()},
    }


# leaves a serving copy keeps in float32 (norm scales and the decay's
# two vectors); every other leaf is a weight stored in the compute dtype
FLOAT32_LEAVES = frozenset({"A_log", "dt_bias", "o_norm", "mixer_norm",
                            "mlp_norm", "q_norm", "k_norm", "final_norm"})


def _init_group(key, shapes: dict) -> dict:
    """The program's own initialisation (serve.py without weights, the
    CPU preset's training): normal(0.02) matrices, unit norm scales, the
    conv uniform over +-1/sqrt(kernel), ``A`` uniform on [1, 16] and the
    softplus of ``dt_bias`` log-uniform on [1e-3, 0.1] (FLA's)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name in FLOAT32_LEAVES - {"A_log", "dt_bias"}:
            out[name] = jnp.ones(shape, F32)
        elif name == "A_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, F32, math.log(1e-3),
                                            math.log(0.1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "conv":
            bound = 1.0 / math.sqrt(shape[-2])
            out[name] = jax.random.uniform(k, shape, F32, -bound, bound)
        else:
            out[name] = 0.02 * jax.random.normal(k, shape, F32)
    return out


def serving_params(params, dtype=jnp.bfloat16):
    """The weights as served: every leaf in ``dtype`` but the float32
    ones (:data:`FLOAT32_LEAVES`)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a.astype(F32 if path[-1].key in FLOAT32_LEAVES else dtype)
        for path, a in flat])


# ------------------------------------------------------------ the cache


def cache_shapes(cfg: HybridConfig, slots: int, max_len: int) -> dict:
    """``{leaf: ShapeDtypeStruct}`` of a cache of ``slots`` slots."""
    sds = jax.ShapeDtypeStruct
    p, h = cfg.periods, cfg.linear_heads
    kv = (p, slots, cfg.heads, max_len, cfg.head_dim)
    return {
        "conv": sds((p, 3, slots, cfg.conv_kernel - 1, cfg.conv_dim),
                    cfg.dtype),
        "delta": sds((p, 3, slots, h, cfg.key_dim, cfg.value_dim),
                     F32),
        "k": sds(kv, cfg.dtype),
        "v": sds(kv, cfg.dtype),
        "length": sds((slots,), jnp.int32),
    }


def empty_cache(cfg: HybridConfig, slots: int, max_len: int) -> dict:
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in
            cache_shapes(cfg, slots, max_len).items()}


# ---------------------------------------------------------- delta rule


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def chunk_delta_rule(q, k, v, beta, g, state, chunk: int):
    """The gated delta rule over ``T`` tokens of one sequence in its chunk
    form: ``q``, ``k [T, H, dk]``, ``v [T, H, dv]``, ``beta``, ``g [T,
    H]`` (float32; a position with ``beta = 0`` and ``g = 0`` leaves the
    state as it is), ``state [H, dk, dv]`` -> ``(o [T, H, dv], state)``.

    In each sub-chunk of ``chunk`` tokens, with ``G`` the cumulative sum
    of ``g`` and ``D_ij = exp(G_i - G_j)`` for ``i >= j``: ``A =
    strict_lower(beta k k^T * D)``, ``[u | w] = (I + A)^-1 [beta v |
    beta k exp(G)]`` (the UT transform, a unit triangular solve); then
    in order over the sub-chunks ``v' = u - w S``, ``o = (q exp(G)) S +
    lower(q k^T * D) v'`` and ``S <- exp(G_last) S + (k exp(G_last -
    G))^T v'``."""
    t, h, dk = k.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        zeros = lambda a: jnp.zeros((pad, *a.shape[1:]), a.dtype)
        q, k, v, beta, g = (jnp.concatenate([a, zeros(a)])
                            for a in (q, k, v, beta, g))
    n = (t + pad) // chunk
    # [n, H, C, ...]
    heads_first = lambda a: jnp.moveaxis(
        a.reshape(n, chunk, h, *a.shape[2:]), 2, 1)
    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    beta, g = heads_first(beta), heads_first(g)
    ein = lambda s, a, b: jnp.einsum(s, a, b, precision=HIGHEST,
                                     preferred_element_type=F32)
    decay = jnp.cumsum(g, -1)                                   # [n, H, C]
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    gap = jnp.where(lower, decay[..., :, None] - decay[..., None, :], -jnp.inf)
    d = jnp.exp(gap)                                            # [n, H, C, C]
    kb = k * beta[..., None]
    a = jnp.where(i[:, None] > i[None, :],
                  ein("nhid,nhjd->nhij", kb, k) * d, 0.0)
    rhs = jnp.concatenate([v * beta[..., None],
                           kb * jnp.exp(decay)[..., None]], -1)
    solved = lax.linalg.triangular_solve(
        jnp.eye(chunk, dtype=F32) + a, rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    local = ein("nhid,nhjd->nhij", q, k) * d
    qd = q * jnp.exp(decay)[..., None]
    last = decay[..., -1]                                       # [n, H]
    kd = k * jnp.exp(last[..., None] - decay)[..., None]

    def step(s, xs):
        u_c, w_c, local_c, qd_c, kd_c, last_c = xs
        v_new = u_c - ein("hcd,hde->hce", w_c, s)
        o = ein("hcd,hde->hce", qd_c, s) + ein("hij,hje->hie", local_c,
                                               v_new)
        s = s * jnp.exp(last_c)[:, None, None] + ein("hcd,hce->hde", kd_c,
                                                     v_new)
        return s, o

    state, o = lax.scan(step, state.astype(F32), (u, w, local, qd, kd, last))
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, h, dv)
    return o[:t], state


def recurrent_delta_step(q, k, v, beta, g, state):
    """One token of the rule for a batch: ``q``, ``k [B, H, dk]``, ``v
    [B, H, dv]``, ``beta``, ``g [B, H]``, ``state [B, H, dk, dv]`` ->
    ``(o [B, H, dv], state)``: ``S <- alpha S + beta k (v - alpha S^T
    k)^T``, ``o = S^T q``."""
    ein = lambda s, a, b: jnp.einsum(s, a, b, precision=HIGHEST,
                                     preferred_element_type=F32)
    alpha = jnp.exp(g)[..., None, None]
    s = alpha * state.astype(F32)
    delta = (v - ein("bhde,bhd->bhe", s, k)) * beta[..., None]
    s = s + k[..., :, None] * delta[..., None, :]
    return ein("bhde,bhd->bhe", s, q), s


# ------------------------------------------------------------- layers


def _gates(x, lp, cfg: HybridConfig):
    """``beta`` and ``g`` ``[..., H]`` in float32."""
    dt = cfg.dtype
    scale = 2.0 if cfg.allow_neg_eigval else 1.0
    beta = scale * jax.nn.sigmoid(compute_dot(x, lp["wb"], dt))
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
        compute_dot(x, lp["wa"], dt) + lp["dt_bias"])
    return beta, g


def _split_heads(conved, cfg: HybridConfig):
    """The conv's float32 output ``[..., conv_dim]`` -> normalised and
    scaled ``q``, normalised ``k`` ``[..., H, dk]`` and ``v [..., H,
    dv]``."""
    h, dk = cfg.linear_heads, cfg.key_dim
    q, k, v = jnp.split(conved, [h * dk, 2 * h * dk], -1)
    q = _l2norm(q.reshape(*q.shape[:-1], h, dk)) / math.sqrt(dk)
    k = _l2norm(k.reshape(*k.shape[:-1], h, dk))
    return q, k, v.reshape(*v.shape[:-1], h, cfg.value_dim)


def _gated_out(o, x, lp, cfg: HybridConfig):
    """``RMSNorm_dv(o) w * SiLU(x Wz)`` ``[..., H dv]`` in the compute
    dtype; ``o [..., H, dv]`` float32."""
    z = compute_dot(x, lp["wz"], cfg.dtype)
    z = z.reshape(*z.shape[:-1], cfg.linear_heads, cfg.value_dim)
    y = rms_norm(o, lp["o_norm"], cfg.rms_eps) * jax.nn.silu(z)
    return y.reshape(*y.shape[:-2], -1).astype(cfg.dtype)


def _mlp_half(h, lp, cfg: HybridConfig):
    """``h + RMSNorm(SwiGLU(h))``."""
    with jax.named_scope("lm/mlp"):
        m = gated_mlp(h, lp["gate"], lp["up"], lp["down"], cfg.dtype)
        return h + rms_norm(m.astype(F32), lp["mlp_norm"], cfg.rms_eps)


def _qkv_in(x, lp, cfg: HybridConfig):
    with jax.named_scope("lm/linear_proj"):
        return jnp.concatenate(
            [compute_dot(x, lp[n], cfg.dtype) for n in ("wq", "wk", "wv")],
            -1).astype(cfg.dtype)


def _mixer_out(y, x, lp, cfg: HybridConfig):
    """``x + RMSNorm(y Wo)``."""
    with jax.named_scope("lm/linear_proj"):
        out = compute_dot(y, lp["wo"], cfg.dtype)
    return x + rms_norm(out, lp["mixer_norm"], cfg.rms_eps)


def linear_chunk(x, lp, state, conv_state, n_valid, cfg: HybridConfig):
    """A linear layer over one sequence's chunk ``x [T, D]`` (float32)
    whose first ``n_valid`` positions are real: -> ``(x, state,
    conv_state)``. The padded positions are no-ops (``beta = 0``,
    ``alpha = 1``) and the conv state is taken at the true end."""
    t = x.shape[0]
    qkv = _qkv_in(x, lp, cfg)
    with jax.named_scope("lm/gdn"):
        k1 = cfg.conv_kernel - 1
        seq = jnp.concatenate([conv_state.astype(cfg.dtype), qkv])
        w = lp["conv"].astype(F32)
        conved = jax.nn.silu(sum(w[j] * seq[j:j + t].astype(F32)
                                 for j in range(cfg.conv_kernel)))
        conv_state = lax.dynamic_slice_in_dim(seq, n_valid, k1)
        q, k, v = _split_heads(conved, cfg)
        beta, g = _gates(x, lp, cfg)
        real = (jnp.arange(t) < n_valid)[:, None]
        beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        o, state = chunk_delta_rule(q, k, v, beta, g, state, cfg.chunk)
        y = _gated_out(o, x, lp, cfg)
    return _mlp_half(_mixer_out(y, x, lp, cfg), lp, cfg), state, conv_state


def linear_step(x, lp, state, conv_state, cfg: HybridConfig):
    """A linear layer for one token of every slot, ``x [S, D]``: ->
    ``(x, state [S, H, dk, dv], conv_state [S, kernel - 1, conv_dim])``."""
    qkv = _qkv_in(x, lp, cfg)
    with jax.named_scope("lm/gdn"):
        window = jnp.concatenate([conv_state.astype(cfg.dtype),
                                  qkv[:, None]], 1)
        conved = jax.nn.silu(jnp.einsum(
            "skc,kc->sc", window.astype(F32), lp["conv"].astype(F32),
            precision=HIGHEST))
        q, k, v = _split_heads(conved, cfg)
        beta, g = _gates(x, lp, cfg)
        o, state = recurrent_delta_step(q, k, v, beta, g, state)
        y = _gated_out(o, x, lp, cfg)
    return (_mlp_half(_mixer_out(y, x, lp, cfg), lp, cfg), state,
            window[:, 1:])


def _angles(positions, cfg: HybridConfig):
    """``[len(positions), head_dim / 2]`` rotary angles at ``positions``."""
    pairs = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-np.arange(pairs) / pairs)
    return positions.astype(F32)[:, None] * jnp.asarray(inv, F32)


def _full_qkv(x, fp, positions, cfg: HybridConfig):
    """Rotated ``q``, ``k`` and ``v`` ``[T, heads, head_dim]`` in the
    compute dtype."""
    dt, shape = cfg.dtype, (x.shape[0], cfg.heads, cfg.head_dim)
    q = rms_norm(compute_dot(x, fp["wq"], dt), fp["q_norm"], cfg.rms_eps)
    k = rms_norm(compute_dot(x, fp["wk"], dt), fp["k_norm"], cfg.rms_eps)
    v = compute_dot(x, fp["wv"], dt).astype(dt).reshape(shape)
    angles = _angles(positions, cfg)
    return (rotate(q.reshape(shape), angles).astype(dt),
            rotate(k.reshape(shape), angles).astype(dt), v)


def _softmax_update(carry, s, vals, spec: str, cfg: HybridConfig):
    """One block of a softmax carried as a running maximum ``m``, sum
    and unnormalised output (float32): logits ``s`` (masked with
    ``-inf``) against values ``vals``."""
    m, total, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, -1))
    e = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    return (m_new, total * corr + jnp.sum(e, -1),
            acc * corr[..., None] + compute_einsum(spec, e, vals, cfg.dtype))


def chunk_attention(q, k, v, keys, values, p, slot, start,
                    cfg: HybridConfig):
    """Causal softmax of a chunk's queries ``q [C, H, hd]`` at positions
    ``start ...`` over the keys before it, read from the cache's
    ``keys``/``values`` ``[periods, slots, H, L, hd]`` at ``(p, slot)``
    block by block (``key_block`` positions a block, only those below
    ``start``, which may be traced), and over the chunk's own ``k``,
    ``v``: -> ``[C, H, hd]``."""
    c, h, hd = q.shape
    kb = cfg.key_block
    scale = 1.0 / math.sqrt(hd)

    def cached(j, carry):
        at, size = (p, slot, 0, j * kb, 0), (1, 1, h, kb, hd)
        kj = lax.dynamic_slice(keys, at, size)[0, 0]
        vj = lax.dynamic_slice(values, at, size)[0, 0]
        s = compute_einsum("thd,hkd->htk", q, kj, cfg.dtype) * scale
        s = jnp.where((j * kb + jnp.arange(kb) < start)[None, None], s,
                      -jnp.inf)
        return _softmax_update(carry, s, vj, "htk,hkd->htd", cfg)

    init = (jnp.full((h, c), -jnp.inf, F32), jnp.zeros((h, c), F32),
            jnp.zeros((h, c, hd), F32))
    carry = lax.fori_loop(0, (start + kb - 1) // kb, cached, init)
    s = compute_einsum("thd,khd->htk", q, k, cfg.dtype) * scale
    i = jnp.arange(c)
    s = jnp.where((i[None, :] <= i[:, None])[None], s, -jnp.inf)
    _, total, acc = _softmax_update(carry, s, v, "htk,khd->htd", cfg)
    return jnp.moveaxis(acc / total[..., None], 0, 1)


def step_attention(q, k, v, keys, values, pos, cfg: HybridConfig):
    """Each slot's query ``q [S, H, hd]`` at position ``pos [S]`` over its
    cached keys and values ``[S, H, L, hd]`` below that position (every
    position read, the rest masked) and its own ``k``, ``v``: -> ``[S,
    H, hd]``."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = compute_einsum("shd,shld->shl", q, keys, cfg.dtype) * scale
    s = jnp.where(jnp.arange(keys.shape[2])[None, None, :]
                  < pos[:, None, None], s, -jnp.inf)
    own = compute_einsum("shd,shd->sh", q, k, cfg.dtype) * scale
    m = jnp.maximum(jnp.max(s, -1), own)
    e, e_own = jnp.exp(s - m[..., None]), jnp.exp(own - m)
    out = compute_einsum("shl,shld->shd", e, values, cfg.dtype)
    out = out + e_own[..., None] * v.astype(F32)
    return out / (jnp.sum(e, -1) + e_own)[..., None]


def _attn_out(o, x, fp, cfg: HybridConfig):
    """``x + RMSNorm(o Wo)``; ``o [..., heads, head_dim]``."""
    o = o.reshape(*o.shape[:-2], -1).astype(cfg.dtype)
    out = compute_dot(o, fp["wo"], cfg.dtype)
    return x + rms_norm(out, fp["mixer_norm"], cfg.rms_eps)


# -------------------------------------------------------- entry points
#
# The periods are one scanned body that reads the keys and values of the
# cache and writes only the linear layers' state: the full layers' new
# keys and values come out of the scan and are written after it, one
# dynamic_update_slice a period (and a slot). A scan that also wrote
# them made XLA give the carried buffers another layout, and so copy
# both whole (two 4 GB temporaries at the benchmark's shapes, more than
# the chip holds).


def chunk_hidden(params, cache, slot, tokens, start, n_valid,
                 cfg: HybridConfig):
    """Run ``tokens [C]`` (the first ``n_valid`` real) of the request in
    ``slot`` at positions ``start ...`` through every layer, reading and
    writing the slot's state: -> ``(hidden [C, D] float32, cache)``.
    ``start == 0`` zeroes the slot's recurrent state first. ``start +
    C`` is at most the cache's length."""
    c = tokens.shape[0]
    first = start == 0
    positions = start + jnp.arange(c)
    x = params["embed"]["embed"][tokens].astype(F32)

    def period(carry, xs):
        x, rec = carry
        lin, fp, p = xs

        def layer(carry, xs):
            x, rec = carry
            lp, i = xs
            idx = (p, i, slot)
            state = jnp.where(first, 0, rec["delta"][idx])
            conv = jnp.where(first, 0, rec["conv"][idx])
            x, state, conv = linear_chunk(x, lp, state, conv, n_valid, cfg)
            return (x, {"delta": rec["delta"].at[idx].set(
                state.astype(rec["delta"].dtype)),
                "conv": rec["conv"].at[idx].set(conv)}), None

        (x, rec), _ = lax.scan(layer, (x, rec), (lin, jnp.arange(3)))
        with jax.named_scope("lm/attn"):
            q, k, v = _full_qkv(x, fp, positions, cfg)
            o = chunk_attention(q, k, v, cache["k"], cache["v"], p, slot,
                                start, cfg)
            x = _attn_out(o, x, fp, cfg)
        x = _mlp_half(x, fp, cfg)
        return (x, rec), (k, v)

    rec = {"delta": cache["delta"], "conv": cache["conv"]}
    (x, rec), (ks, vs) = lax.scan(
        period, (x, rec), (params["linear"], params["full"],
                           jnp.arange(cfg.periods)))
    out = {**cache, **rec}
    with jax.named_scope("lm/attn"):
        for name, new in (("k", ks), ("v", vs)):
            for p in range(cfg.periods):
                out[name] = lax.dynamic_update_slice(
                    out[name], jnp.swapaxes(new[p], 0, 1)[None, None],
                    (p, slot, 0, start, 0))
    out["length"] = cache["length"].at[slot].set(start + n_valid)
    return x, out


def _logits(params, h, cfg: HybridConfig):
    with jax.named_scope("lm/head"):
        h = rms_norm(h, params["final_norm"]["final_norm"], cfg.rms_eps)
        return compute_dot(h, params["lm_head"]["lm_head"], cfg.dtype)


def prefill(params, cache, slot, tokens, start, n_valid, cfg: HybridConfig):
    """One prefill call (:func:`chunk_hidden`): -> ``(cache, next token,
    logits [V] float32 of the last real position)``."""
    h, cache = chunk_hidden(params, cache, slot, tokens, start, n_valid,
                            cfg)
    logits = _logits(params, lax.dynamic_index_in_dim(h, n_valid - 1, 0,
                                                      keepdims=False), cfg)
    return cache, jnp.argmax(logits).astype(jnp.int32), logits


def decode(params, cache, tokens, active, cfg: HybridConfig):
    """One step over every slot: ``tokens [S]`` at each slot's length,
    the rows where ``active [S]`` is false left as they are: -> ``(cache,
    next tokens [S], logits [S, V] float32)``. An inactive row's key and
    value land at its length, which is written again before it is read."""
    pos = cache["length"]
    x = params["embed"]["embed"][tokens].astype(F32)
    keep = lambda new, old: jnp.where(
        active.reshape(-1, *([1] * (old.ndim - 1))), new.astype(old.dtype),
        old)

    def period(carry, xs):
        x, rec = carry
        lin, fp, p = xs

        def layer(carry, xs):
            x, rec = carry
            lp, i = xs
            state, conv = rec["delta"][p, i], rec["conv"][p, i]
            x, new_state, new_conv = linear_step(x, lp, state, conv, cfg)
            return (x, {"delta": rec["delta"].at[p, i].set(
                keep(new_state, state)),
                "conv": rec["conv"].at[p, i].set(keep(new_conv, conv))}), None

        (x, rec), _ = lax.scan(layer, (x, rec), (lin, jnp.arange(3)))
        with jax.named_scope("lm/attn"):
            q, k, v = _full_qkv(x, fp, pos, cfg)
            o = step_attention(q, k, v, cache["k"][p], cache["v"][p], pos,
                               cfg)
            x = _attn_out(o, x, fp, cfg)
        x = _mlp_half(x, fp, cfg)
        return (x, rec), (k, v)

    rec = {"delta": cache["delta"], "conv": cache["conv"]}
    (x, rec), (ks, vs) = lax.scan(
        period, (x, rec), (params["linear"], params["full"],
                           jnp.arange(cfg.periods)))
    out = {**cache, **rec}
    with jax.named_scope("lm/attn"):
        for name, new in (("k", ks), ("v", vs)):
            for p in range(cfg.periods):
                for slot in range(tokens.shape[0]):
                    out[name] = lax.dynamic_update_slice(
                        out[name], new[p, slot][None, None, :, None],
                        (p, slot, 0, pos[slot], 0))
    out["length"] = pos + active.astype(jnp.int32)
    logits = _logits(params, x, cfg)
    return out, jnp.argmax(logits, -1).astype(jnp.int32), logits


def forward_hidden(params, tokens, cfg: HybridConfig):
    """The whole of one sequence ``tokens [T]`` from an empty state, one
    chunk: -> ``hidden [T, D]`` float32 (training and scoring)."""
    t = tokens.shape[0]
    length = -(-t // cfg.key_block) * cfg.key_block
    padded = jnp.zeros((length,), tokens.dtype).at[:t].set(tokens)
    h, _ = chunk_hidden(params, empty_cache(cfg, 1, length), 0, padded, 0,
                        t, cfg)
    return h[:t]


# ------------------------------------------------------- Flax and registry


class OlmoHybridLM(nn.Module):
    """The parameters as a Flax module, for the registry, ``train.py``
    and the lint gates: ``{"tokens": [B, L]}`` -> ``nll [B, L - 1]``
    (position ``i`` predicts token ``i + 1``), plus ``logits`` with
    ``logits=True``. Serving calls :func:`prefill` and :func:`decode`
    on ``variables["params"]`` (``serve/generator.py``)."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    num_layers: int = 32
    heads: int = 30
    linear_heads: int = 30
    key_dim: int = 96
    value_dim: int = 192
    conv_kernel: int = 4
    mlp_width: int = 11008
    rms_eps: float = 1e-6
    rope_theta: float = 500000.0
    allow_neg_eigval: bool = True
    chunk: int = 64
    key_block: int = 384
    sample_text_len: int = 13
    dtype: Dtype = jnp.bfloat16

    @property
    def config(self) -> HybridConfig:
        return HybridConfig(
            self.vocab_size, self.hidden_size, self.num_layers, self.heads,
            self.linear_heads, self.key_dim, self.value_dim,
            self.conv_kernel, self.mlp_width, self.rms_eps, self.rope_theta,
            self.allow_neg_eigval, self.chunk, self.key_block, self.dtype)

    def sample_input(self) -> dict:
        return {"tokens": np.zeros((1, self.sample_text_len), np.int32)}

    def setup(self):
        cfg = self.config
        if cfg.num_layers % len(PERIOD):
            raise ValueError(f"num_layers {cfg.num_layers} is not a whole "
                             f"number of {len(PERIOD)}-layer periods")
        self.groups = {name: self.param(
            name, lambda key, s=shapes: _init_group(key, s))
            for name, shapes in param_shapes(cfg).items()}

    def __call__(self, inputs, train: bool = False, logits: bool = False):
        del train                              # no dropout, no statistics
        cfg, params = self.config, self.groups
        tokens = inputs["tokens"]
        hidden = jax.vmap(lambda t: forward_hidden(params, t, cfg))(
            tokens[:, :-1])
        out_logits = _logits(params, hidden, cfg)
        logz = jax.nn.logsumexp(out_logits, -1)
        picked = jnp.take_along_axis(out_logits, tokens[:, 1:, None],
                                     -1)[..., 0]
        out = {"nll": logz - picked}
        if logits:
            out["logits"] = out_logits
        return out


# One pipeline stage of the 4-stage deployment the benchmark measures
# (benchmark/configs/olmo_hybrid_7b.json): two of the eight periods, all
# 30 heads of every layer, the whole vocabulary.
_STAGE_OF_4 = dict(num_layers=8)
# CPU-sized preset for tests and ``serve.py -m olmo_hybrid_tiny``.
_TINY = dict(vocab_size=256, hidden_size=128, num_layers=8, heads=2,
             linear_heads=6, key_dim=32, value_dim=64, mlp_width=192,
             chunk=8, key_block=16)


def _factory(defaults: dict):
    def make(dtype=jnp.bfloat16, **kwargs):
        return OlmoHybridLM(dtype=dtype, **{**defaults, **kwargs})
    return make


register("olmo_hybrid")(_factory({}))
register("olmo_hybrid_pp4")(_factory(_STAGE_OF_4))
register("olmo_hybrid_tiny")(_factory(_TINY))
