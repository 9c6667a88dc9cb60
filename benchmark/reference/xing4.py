"""Plain reference of the ``xing4_29b_a4b`` configuration.

Xing4.0-29B-A4B as its public ``config.json`` states it
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json,
``model_type`` ``xing4_0``): DeepSeek-V3's latent attention, router and
experts (arXiv:2412.19437, sections 2.1-2.2) on a residual of
``hc_mult`` streams that manifold-constrained hyper-connections mix
("mHC: Manifold-Constrained Hyper-Connections", DeepSeek, on the
Hyper-Connections of Zhu et al.), with one multi-token-prediction
module. Straight ``jax.numpy`` in float32 at ``lax.Precision.HIGHEST``,
one document at a time and one chunk of queries at a time; nothing is
imported from the program. The latent attention's, the router's and the
experts' mathematics is ``kanana2``'s, whose helpers are used here.

The residual of one document is ``X [T, n, C]``: the embedding copied
into the ``n`` streams. Around each sublayer ``F`` (attention; the dense
MLP or the expert layer), per token: ``x~ = RMSNorm(vec X_t)`` (width
``n C``, no scale), ``p = x~ phi`` (``phi [n C, n (n + 2)]``, float32 at
HIGHEST), ``H_pre = sigmoid(a_pre p[:n] + b_pre)``, ``H_post = 2
sigmoid(a_post p[n:2n] + b_post)``, ``H_res = Sinkhorn(exp(clip(a_res
mat(p[2n:]) + b_res, -30, 30)))`` (rows, then columns, divided by their
sum + ``hc_eps``, ``hc_sinkhorn_iters`` times), and ``X' = H_res X +
H_post^T F(RMSNorm(H_pre X))``. The readout is the sum of the streams.

- Attention (``q_lora_rank`` 768): ``q = RMSNorm(h Wqa) Wqb``, the held
  heads of ``[q_nope | q_rope]``; ``c = h Wkva`` = ``[c_kv | k_rope]``;
  ``RMSNorm(c_kv) Wkvb`` = the held heads of ``[k_nope | v]``. Yarn
  rotary (``rope_scaling``: DeepSeek-V3's correction range and linear
  ramp over the pairs, ``mscale`` = ``mscale_all_dim``, so the rotary is
  not rescaled) on ``q_rope`` and the one shared ``k_rope``; logits times
  ``yarn_mscale(factor, mscale_all_dim)^2 / sqrt(qk_head_dim)``, causal
  softmax; ``out = concat(o) Wo`` over the held heads: the part of the
  output they give.
- Dense MLP (the leading layers) and expert layer (sigmoid router over
  all published experts behind the selection bias, top
  ``num_experts_per_tok``, gates renormalised and times
  ``routed_scaling_factor``, the held experts' part plus the shared
  expert): ``kanana2.gated_mlp`` and ``kanana2.moe``.
- Multi-token prediction, depth 1: ``h'_t = M [RMSNorm(readout_t);
  RMSNorm(Emb(tok_{t+1}))]`` over all ``T`` positions, one expert block
  with hyper-connections of its own on ``h'`` copied into the streams, a
  final norm and the shared head, which predicts ``tok_{t+2}`` at the
  first ``T - 1`` positions.
- Loss: the mean next-token cross-entropy plus ``mtp_loss_weight`` times
  the mean MTP cross-entropy, both over the vocabulary slice.

Training: ``kanana2``'s Adam, then the balancing rule on every expert
block's selection bias, the MTP block's among them (its counts are the
last row). Everything the public config does not state is listed in the
configuration file's ``assumed``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import kanana2, plain
from benchmark.reference.kanana2 import _ein, _mm, gated_mlp, rms_norm, rotate

NEG = -jnp.inf


# ------------------------------------------------------------------ sizes


def sizes(cfg) -> dict:
    return {
        "seq": cfg["seq_len"],
        "streams": cfg["hc_mult"],
        "heads": cfg["num_attention_heads"],
        "experts_all": cfg["router_width"],
        "experts_here": cfg["router_width"] // cfg["expert_share"][1],
        "dense_layers": cfg["first_k_dense_replace"],
        "expert_layers": cfg["num_hidden_layers"]
        - cfg["first_k_dense_replace"],
        "mtp_layers": cfg["num_nextn_predict_layers"],
        "qk_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
    }


def _stack(n, tree):
    return jax.tree.map(lambda shape: (n, *shape), tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def param_shapes(cfg) -> dict:
    """Nested ``{name: shape}`` of every parameter leaf. The dense
    blocks and the expert blocks are each a stack (the block its
    leading axis); the MTP module's one block stands alone."""
    sz = sizes(cfg)
    if sz["mtp_layers"] != 1:
        raise ValueError("one multi-token-prediction module is written here")
    d, heads, n = cfg["hidden_size"], sz["heads"], sz["streams"]
    rank, dr, qr = (cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
                    cfg["q_lora_rank"])
    norm = lambda w: {"scale": (w,)}
    mlp = lambda width: {"gate": (d, width), "up": (d, width),
                         "down": (width, d)}
    hc = {"phi": (n * d, n * (n + 2)), "alpha": (3,), "b": (n * (n + 2),)}
    attn = {"q_a": (d, qr), "q_norm": norm(qr),
            "q_b": (qr, heads * sz["qk_dim"]),
            "kv_a": (d, rank + dr), "kv_norm": norm(rank),
            "kv_b": (rank, heads * (cfg["qk_nope_head_dim"]
                                    + cfg["v_head_dim"])),
            "o": (heads * cfg["v_head_dim"], d)}
    e, f = sz["experts_here"], cfg["moe_intermediate_size"]
    expert_block = {
        "attn_hc": hc, "attn_norm": norm(d), "attn": attn,
        "moe_hc": hc, "moe_norm": norm(d),
        "moe": {"router": (d, sz["experts_all"]),
                "bias": (sz["experts_all"],),
                "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d),
                "shared": mlp(cfg["n_shared_experts"] * f)}}
    return {
        "embed": (cfg["vocab_size"], d),
        "final_norm": norm(d),
        "lm_head": (d, cfg["vocab_size"]),
        "dense": _stack(sz["dense_layers"], {
            "attn_hc": hc, "attn_norm": norm(d), "attn": attn,
            "mlp_hc": hc, "mlp_norm": norm(d),
            "mlp": mlp(cfg["intermediate_size"])}),
        "layers": _stack(sz["expert_layers"], expert_block),
        "mtp": {"hnorm": norm(d), "enorm": norm(d), "proj": (2 * d, d),
                "block": expert_block, "final_norm": norm(d)},
    }


def param_count(cfg) -> dict:
    """Parameters held here, by part."""
    parts = {"dense_layers": 0, "expert_layers": 0, "mtp": 0,
             "embed_head": 0}
    names = {"dense": "dense_layers", "layers": "expert_layers",
             "mtp": "mtp"}
    for path, shape in plain.tree_paths(param_shapes(cfg)).items():
        parts[names.get(path[0], "embed_head")] += math.prod(shape)
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------- weights

STD = 0.02
EMBED_STD = 1.0         # see keye_vl2.EMBED_STD: a token stays itself
NORM_SCALE = (0.8, 1.2)
HC_ALPHA = 0.01
HC_RES_BIAS_STD = 1.0


def make_weights(cfg, key) -> dict:
    """All parameters from ``key`` in one traced function (jit it), as
    ``kanana2.make_weights`` draws them (a block's last matrix ``o``,
    ``down`` divided by sqrt(2 x blocks), the MTP block and ``M``
    counted among them); of each hyper-connection ``phi`` normal with
    std 0.02, ``alpha`` 0.01 each, ``b_pre`` and ``b_post`` 0 and
    ``b_res`` normal with std 1, so that ``H_res`` starts away from the
    uniform matrix and the Sinkhorn iterations have work. float32."""
    flat = plain.tree_paths(param_shapes(cfg))
    keys = jax.random.split(key, len(flat))
    n = cfg["hc_mult"]
    blocks = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    last = 1.0 / math.sqrt(2 * blocks)
    leaves = {}
    for k, (path, shape) in zip(keys, flat.items()):
        name = path[-1]
        if name == "scale":
            leaf = jax.random.uniform(k, shape, jnp.float32, *NORM_SCALE)
        elif name == "bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif name == "alpha":
            leaf = jnp.full(shape, HC_ALPHA, jnp.float32)
        elif name == "b":
            leaf = HC_RES_BIAS_STD * jax.random.normal(k, shape, jnp.float32)
            leaf = leaf.at[..., :2 * n].set(0.0)
        elif path == ("embed",):
            leaf = EMBED_STD * jax.random.normal(k, shape, jnp.float32)
        else:
            std = STD * (last if name in ("o", "down") else 1.0)
            leaf = std * jax.random.normal(k, shape, jnp.float32)
        leaves[path] = leaf
    return plain.nest(leaves)


def make_batch(cfg, key, rows: int) -> dict:
    """``rows`` documents of ``seq_len + 1`` ids drawn uniformly from the
    slice: the first ``seq_len`` are the input positions, ids ``1 ..``
    the next-token labels and ids ``2 ..`` the MTP module's."""
    return kanana2.make_batch(cfg, key, rows)


# ----------------------------------------------------------------- rotary


def yarn_inverse_frequencies(cfg) -> np.ndarray:
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding``: pair ``i`` of
    ``qk_rope_head_dim`` turns at ``theta^(-2i / dim)`` above the
    correction range, at that over ``factor`` below it, a linear ramp
    between."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    original = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (rs["factor"] * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def rope_angles(cfg, length: int):
    return (jnp.arange(length, dtype=jnp.float32)[:, None]
            * jnp.asarray(yarn_inverse_frequencies(cfg), jnp.float32))


def softmax_scale(cfg) -> float:
    """``mscale^2 / sqrt(qk_head_dim)``, ``mscale = 0.1 mscale_all_dim
    ln(factor) + 1``; the rotary's own factor ``mscale /
    mscale_all_dim`` must be 1."""
    rs = cfg["rope_scaling"]
    if rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("a rotary rescaled by mscale is not written here")
    factor = rs["factor"]
    m = 1.0 if factor <= 1 else 0.1 * rs["mscale_all_dim"] * math.log(
        factor) + 1.0
    return m * m / math.sqrt(sizes(cfg)["qk_dim"])


# ---------------------------------------------------------------- decoder


def latent_qkv(cfg, p, h, angles, nm):
    """``h [T, hidden]`` (normed) -> ``q``, ``k`` ``[T, heads, qk_dim]``
    (rotated) and ``v [T, heads, v_head_dim]`` of the held heads."""
    heads, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"])
    rank, t, eps = cfg["kv_lora_rank"], h.shape[0], cfg["rms_norm_eps"]
    c_q = rms_norm(_mm(h, p["q_a"], nm), p["q_norm"]["scale"], eps)
    q = _mm(c_q, p["q_b"], nm).reshape(t, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], angles)], -1)
    c = _mm(h, p["kv_a"], nm)
    k_rope = rotate(c[:, None, rank:], angles)                  # one head
    c_kv = rms_norm(c[:, :rank], p["kv_norm"]["scale"], eps)
    kv = _mm(c_kv, p["kv_b"], nm).reshape(t, heads, -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (t, heads, dr))], -1)
    return q, k, kv[..., dn:]


def attention_chunk(q, k, v, t0, scale, nm):
    """Queries ``t0 ..`` against keys ``0 ..``, causal: -> ``[Tq, heads,
    v_head_dim]``."""
    tq, tk = q.shape[0], k.shape[0]
    causal = jnp.arange(tk)[None, :] <= t0 + jnp.arange(tq)[:, None]
    logits = _ein("thd,shd->hts", q, k, nm).astype(jnp.float32)
    probs = jax.nn.softmax(jnp.where(causal, logits * scale, NEG), -1)
    return _ein("hts,shd->thd", probs.astype(nm.store), v, nm)


def attention(cfg, p, h, angles, nm):
    """``h [T, hidden]`` (normed) -> the held heads' part of ``[T,
    hidden]``, in blocks of ``reference_key_block`` and chunks of
    ``reference_q_chunk`` queries."""
    t = h.shape[0]
    q, k, v = latent_qkv(cfg, p, h, angles, nm)
    scale = softmax_scale(cfg)
    block = min(cfg.get("reference_key_block", t), t)
    chunk = min(cfg.get("reference_q_chunk", block), block)
    assert t % block == 0 and block % chunk == 0
    outs = []
    for b0 in range(0, t, block):
        end = b0 + block

        @jax.checkpoint
        def one(args, end=end):
            qc, t0 = args
            return attention_chunk(qc, k[:end], v[:end], t0, scale, nm)

        n = block // chunk
        o = lax.map(one, (q[b0:end].reshape(n, chunk, *q.shape[1:]),
                          b0 + chunk * jnp.arange(n)))
        outs.append(o.reshape(block, -1))
    return _mm(jnp.concatenate(outs), p["o"], nm)


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hyper_maps(cfg, p, x, nm):
    """``X [T, n, C]`` -> ``H_pre [T, n]``, ``H_post [T, n]``, ``H_res
    [T, n, n]``, float32."""
    t, n, d = x.shape
    flat = x.reshape(t, n * d).astype(jnp.float32)
    xt = flat * lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    proj = jnp.dot(nm.round_operand(xt), nm.round_operand(p["phi"]),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    a, b = p["alpha"], p["b"]
    pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    clamp_lo, clamp_hi = (cfg["mhc_h_res_clamp_min"],
                          cfg["mhc_h_res_clamp_max"])
    res = sinkhorn(jnp.exp(jnp.clip(res, clamp_lo, clamp_hi)),
                   cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    return pre, post, res


def hyper(cfg, p_hc, x, sublayer, nm):
    """``X' = H_res X + H_post^T F(H_pre X)``; ``sublayer`` returns
    (``F``, what else it has to say). -> (``X'``, that)."""
    pre, post, res = hyper_maps(cfg, p_hc, x, nm)
    u = jnp.einsum("tn,tnc->tc", pre, x.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST).astype(nm.store)
    y, aux = sublayer(u)
    mixed = jnp.einsum("tnm,tmc->tnc", res, x.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)
    return (mixed + post[..., None] * y.astype(jnp.float32)[:, None]
            ).astype(nm.store), aux


def _attention_sublayer(cfg, p, angles, nm):
    return lambda u: (attention(cfg, p["attn"], rms_norm(
        u, p["attn_norm"]["scale"], cfg["rms_norm_eps"]), angles, nm), None)


def dense_block(cfg, p, x, angles, nm):
    eps = cfg["rms_norm_eps"]
    x, _ = hyper(cfg, p["attn_hc"], x,
                 _attention_sublayer(cfg, p, angles, nm), nm)
    return hyper(cfg, p["mlp_hc"], x, lambda u: (gated_mlp(
        p["mlp"], rms_norm(u, p["mlp_norm"]["scale"], eps), nm), None),
        nm)[0]


def expert_block(cfg, p, x, angles, nm):
    """-> (``X``, tokens that chose each of all experts, choices)."""
    eps = cfg["rms_norm_eps"]
    x, _ = hyper(cfg, p["attn_hc"], x,
                 _attention_sublayer(cfg, p, angles, nm), nm)

    def moe(u):
        y, counts, experts = kanana2.moe(
            cfg, p["moe"], rms_norm(u, p["moe_norm"]["scale"], eps), nm)
        return y, (counts, experts)

    x, (counts, experts) = hyper(cfg, p["moe_hc"], x, moe, nm)
    return x, counts, experts


def _nll(hidden, head, labels, nm):
    logits = _mm(hidden, head, nm).astype(jnp.float32)
    return (jax.nn.logsumexp(logits, -1)
            - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]), logits


def forward_sample(cfg, params, tokens, nm=plain.HIGHEST, capture=False,
                   remat=True):
    """One document ``[seq_len + 1]`` -> ``{"nll" [seq_len], "mtp_nll"
    [seq_len - 1], "expert_counts" [expert layers + 1, all experts]}``
    (the MTP block's counts last; with ``capture`` the main logits and
    each expert block's routing choice)."""
    eps, n = cfg["rms_norm_eps"], cfg["hc_mult"]
    t = tokens.shape[0] - 1
    streams = lambda h: jnp.broadcast_to(h[:, None], (t, n, h.shape[-1]))
    angles = rope_angles(cfg, t)

    def dense(x, p):
        return dense_block(cfg, p, x, angles, nm), None

    def expert(x, p):
        x, counts, experts = expert_block(cfg, p, x, angles, nm)
        return x, {"expert_counts": counts,
                   **({"experts": experts} if capture else {})}

    if remat:
        dense, expert = jax.checkpoint(dense), jax.checkpoint(expert)
    x = streams(params["embed"][tokens[:-1]].astype(nm.store))
    x, _ = lax.scan(dense, x, params["dense"])
    x, stats = lax.scan(expert, x, params["layers"])
    main = jnp.sum(x.astype(jnp.float32), 1).astype(nm.store)
    hidden = rms_norm(main, params["final_norm"]["scale"], eps)
    nll, logits = _nll(hidden, params["lm_head"], tokens[1:], nm)

    m = params["mtp"]
    h = jnp.concatenate([
        rms_norm(main, m["hnorm"]["scale"], eps),
        rms_norm(params["embed"][tokens[1:]].astype(nm.store),
                 m["enorm"]["scale"], eps)], -1)
    xm, mtp = expert(streams(_mm(h, m["proj"], nm)), m["block"])
    mh = rms_norm(jnp.sum(xm.astype(jnp.float32), 1).astype(nm.store),
                  m["final_norm"]["scale"], eps)
    mtp_nll, _ = _nll(mh[:-1], params["lm_head"], tokens[2:], nm)

    out = {"nll": nll, "mtp_nll": mtp_nll,
           "expert_counts": jnp.concatenate(
               [stats["expert_counts"], mtp["expert_counts"][None]])}
    if capture:
        out.update(logits=logits, experts=jnp.concatenate(
            [stats["experts"], mtp["experts"][None]]))
    return out


def loss(cfg, params, batch, nm=plain.HIGHEST, kept=None):
    """-> (loss, {"expert_counts" [expert layers + 1, all experts]})
    over the batch, one document after another. ``kept [rows]`` (1 or
    0) leaves documents out of the mean and of the counts."""
    one = jax.checkpoint(lambda tokens: forward_sample(
        cfg, params, tokens, nm))
    out = lax.map(one, batch["tokens"])
    if kept is None:
        kept = jnp.ones(out["nll"].shape[:1], jnp.float32)
    per_doc = (jnp.mean(out["nll"], -1)
               + cfg["mtp_loss_weight"] * jnp.mean(out["mtp_nll"], -1))
    value = jnp.sum(per_doc * kept) / jnp.sum(kept)
    counts = jnp.sum(out["expert_counts"]
                     * kept.astype(jnp.int32)[:, None, None], 0)
    return value, {"expert_counts": counts}


# ------------------------------------------------------------------ FLOPs


def forward_flops_parts(cfg) -> dict:
    """2 x multiply-adds of one sample's forward at this chip's share,
    by part, whatever the program computes: each block's projections
    and attention over the causal pairs (the dense blocks, the expert
    blocks and the MTP block), the hyper-connections (the maps' product
    and the three mixes of the streams, two a block), the dense MLPs,
    the router, the shared expert and the expected local routed experts
    of every expert block, ``M``, and the shared head twice (the main
    head over ``T`` positions, the MTP's over ``T - 1``)."""
    sz = sizes(cfg)
    d, t, heads, n = (cfg["hidden_size"], sz["seq"], sz["heads"],
                      sz["streams"])
    rank, dr, qr = (cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
                    cfg["q_lora_rank"])
    dn, dv, f = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                 cfg["moe_intermediate_size"])
    local = cfg["num_experts_per_tok"] * sz["experts_here"] \
        / sz["experts_all"]
    blocks = sz["dense_layers"] + sz["expert_layers"] + sz["mtp_layers"]
    expert_blocks = sz["expert_layers"] + sz["mtp_layers"]
    every_block = {
        "projections": 2 * t * (d * qr + qr * heads * sz["qk_dim"]
                                + d * (rank + dr)
                                + rank * heads * (dn + dv)
                                + heads * dv * d),
        "attention": 2 * kanana2.causal_pairs(t) * heads
        * (sz["qk_dim"] + dv),
        "hyper_connections": 2 * 2 * t * (n * d * n * (n + 2)
                                          + n * d + n * n * d + n * d),
    }
    expert_block = {
        "router": 2 * t * d * sz["experts_all"],
        "shared": 2 * t * 3 * d * cfg["n_shared_experts"] * f,
        "experts": int(2 * t * local * 3 * d * f),
    }
    parts = {k: blocks * x for k, x in every_block.items()}
    parts.update({k: expert_blocks * x for k, x in expert_block.items()})
    parts.update(
        dense_mlp=sz["dense_layers"] * 2 * t * 3 * d
        * cfg["intermediate_size"],
        mtp_proj=2 * t * 2 * d * d,
        head=2 * (2 * t - 1) * d * cfg["vocab_size"])
    return parts


def forward_flops_per_image(cfg) -> int:
    return int(sum(forward_flops_parts(cfg).values()))


def train_flops_per_image(cfg) -> int:
    """Forward and backward: three times the forward count. A sample,
    one document, counts as one image. Recomputation is not counted."""
    return 3 * forward_flops_per_image(cfg)


# --------------------------------------------------------------- training

_STEP_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "intermediate_size",
    "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "scoring_func",
    "router_width", "expert_share", "num_hidden_layers",
    "first_k_dense_replace", "num_nextn_predict_layers", "vocab_size",
    "seq_len", "rope_theta", "rope_scaling", "rms_norm_eps", "hc_mult",
    "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max", "mtp_loss_weight", "bias_update_rate",
    "optimizer", "reference_key_block", "reference_q_chunk")


def make_step(cfg, nm: plain.Numerics = plain.HIGHEST):
    """One pair of jitted functions for each distinct set of arguments."""
    return _make_step(json.dumps({k: cfg[k] for k in _STEP_KEYS if k in cfg},
                                 sort_keys=True), nm)


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, nm):
    """``kanana2._make_step``'s two programs; the balancing rule moves
    the stacked expert blocks' biases from the first rows of the counts
    and the MTP block's from the last."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    lr, b1, b2, eps = opt["lr"], opt["beta1"], opt["beta2"], opt["eps"]
    warmup, gamma = opt["warmup_steps"], cfg["bias_update_rate"]

    @jax.jit
    def grads(p, b, kept):
        return jax.value_and_grad(
            lambda q: loss(cfg, q, b, nm, kept), has_aux=True)(p)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, mu, nu, g, counts, count):
        mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, mu, g)
        nu = jax.tree.map(lambda n, gi: b2 * n + (1 - b2) * gi * gi, nu, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        rate = lr * jnp.minimum(count / warmup, 1.0)
        p = jax.tree.map(
            lambda pi, m, n: pi - rate * (m / c1) / (jnp.sqrt(n / c2) + eps),
            p, mu, nu)
        moved = kanana2.balance(
            jnp.concatenate([p["layers"]["moe"]["bias"],
                             p["mtp"]["block"]["moe"]["bias"][None]]),
            counts, gamma)
        p["layers"]["moe"]["bias"] = moved[:-1]
        p["mtp"]["block"]["moe"]["bias"] = moved[-1]
        return p, mu, nu

    return grads, update


def train_steps(cfg, params, batch, n_steps: int,
                nm: plain.Numerics = plain.HIGHEST,
                rows: tuple | None = None):
    """``kanana2.train_steps`` with this configuration's step: ->
    (losses [n], Adam's first moment after step 1 (on the host),
    parameters after the last step)."""
    grads, update = make_step(cfg, nm)
    n_rows = batch["tokens"].shape[0]
    start, stop = rows or (0, n_rows)
    kept = ((np.arange(n_rows) >= start)
            & (np.arange(n_rows) < stop)).astype(np.float32)
    chip = next(iter(batch["tokens"].devices()))
    place = lambda t: jax.device_put(t, chip)
    host = lambda t: jax.tree.map(np.asarray, t)
    p = place(params)
    mu = nu = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), params)
    losses, first = [], None
    for i in range(n_steps):
        (value, stats), g = grads(p, batch, kept)
        losses.append(value)
        p, mu, nu = update(p, place(mu), place(nu), g,
                           stats["expert_counts"], jnp.float32(i + 1))
        del g
        if i == 0:
            first = host(mu)
        if i + 1 < n_steps:
            mu, nu = first if i == 0 else host(mu), host(nu)
    return jnp.stack(losses), first, p
