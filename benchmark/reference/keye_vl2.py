"""Plain reference of the ``keye_vl2_30b_a3b`` configuration.

Keye-VL-2.0-30B-A3B as its public ``config.json`` states it
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json):
a vision tower, a projector and a pre-norm decoder whose every layer is
grouped-query attention over the keys a learned indexer selects,
followed by a mixture of experts. Straight ``jax.numpy`` in float32 at
``lax.Precision.HIGHEST``, one sequence at a time and one chunk of
queries at a time so that it fits beside its own optimiser state;
nothing is imported from the program.

One decoder layer, input ``x`` (T x hidden): ``x += Attn(RMSNorm(x))``,
``x += MoE(RMSNorm(x))``.

- Attention: ``q = h Wq`` (heads x head_dim), ``k = h Wk``, ``v = h Wv``
  (key/value heads x head_dim), RMSNorm over each head of ``q`` and
  ``k``, M-RoPE on both (head_dim/2 frequency pairs from ``rope_theta``,
  the pairs split by ``mrope_section`` over the temporal, row and column
  position). Indexer: ``qI = h WqI`` (indexer heads x indexer dim),
  ``kI = LayerNorm(h WkI)`` (one shared key), rotary on the first half
  of the indexer dim by the token's index, ``w = h Ww``; score
  ``I[t, s] = scale * sum_j w[t, j] relu(qI[t, j] . kI[s])`` for
  ``s <= t``, ``scale = dim^-1/2 heads^-1/2``; ``S_t`` = the ``topk``
  largest ``I[t, :t+1]`` (all while ``t < topk``);
  ``o_t = softmax_{s in S_t}(q_t . k_s / sqrt(head_dim)) v_s``;
  ``out = o Wo``. The mask is ``I >= (the topk-th largest of the row)``.
- MoE: ``p = softmax(h Wr)`` over all published experts, the
  ``num_experts_per_tok`` largest renormalised to sum 1,
  ``y = sum_e g_e Wdown_e(silu(Wgate_e h) * (Wup_e h))`` over the experts
  this chip holds (``expert_share``); what the absent experts would add
  is left out.
- Loss: mean next-token cross-entropy over the vocabulary slice on the
  text positions, plus ``index_loss_weight`` times the indexer's
  alignment loss ``sum_layers sum_t KL(p_t || softmax(I[t, S_t]))``
  averaged over the sequences, ``p_t`` the main attention's
  probabilities summed over the heads and normalised over ``S_t``; the
  indexer's input and ``p_t`` are detached.

The tower is SigLIP-so400m's layout (pre-LayerNorm blocks with biases,
tanh GELU, learned positions, a last LayerNorm), the projector merges
2 x 2 patches, LayerNorm, Linear, GELU, Linear. Everything the public
config does not state is listed in the configuration file's ``assumed``.

Training is Adam as ``optax.adam`` has it (bias-corrected moments,
epsilon outside the root), on the loss above, from float32 parameters;
update ``n`` (from 1) takes the rate ``lr * min(n / warmup_steps, 1)``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import plain

LN_EPS = 1e-6
NEG = -jnp.inf


# ------------------------------------------------------------------ sizes


def sizes(cfg) -> dict:
    v = cfg["vision_config"]
    grid = v["image_size"] // v["patch_size"]
    merged = grid // v["spatial_merge_size"]
    n_img = merged * merged
    of = cfg["expert_share"][1]
    return {
        "grid": grid, "merged": merged, "n_img": n_img,
        "seq": n_img + cfg["text_len"],
        "experts_all": cfg["num_local_experts"],
        "experts_here": cfg["num_local_experts"] // of,
    }


def param_shapes(cfg) -> dict:
    """Nested ``{name: shape}`` of every parameter leaf. The layers of
    the decoder, and of the tower, are stacked: each of their leaves has
    the layer as its leading axis."""
    v, sa, sz = cfg["vision_config"], cfg["sa_config"], sizes(cfg)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    dv, fv = v["hidden_size"], v["intermediate_size"]
    ln = lambda n: {"scale": (n,), "bias": (n,)}
    lin = lambda i, o: {"kernel": (i, o), "bias": (o,)}
    vision = {
        "patch_embed": lin(v["patch_size"] ** 2 * 3, dv),
        "pos_embed": (sz["grid"] ** 2, dv),
        "post_norm": ln(dv),
    }
    stack = lambda n, tree: jax.tree.map(
        lambda shape: (n, *shape), tree,
        is_leaf=lambda x: isinstance(x, tuple))
    vision["layers"] = stack(cfg["vision_num_hidden_layers"], {
        "norm1": ln(dv), "norm2": ln(dv),
        "attn": {n: lin(dv, dv) for n in "qkvo"},
        "mlp": {"fc1": lin(dv, fv), "fc2": lin(fv, dv)},
    })
    dm = dv * v["spatial_merge_size"] ** 2
    shapes = {
        "vision": vision,
        "projector": {"norm": ln(dm), "fc1": lin(dm, d), "fc2": lin(d, d)},
        "embed": (cfg["vocab_size"], d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, cfg["vocab_size"]),
    }
    e, f = sz["experts_here"], cfg["moe_intermediate_size"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    shapes["layers"] = stack(cfg["num_hidden_layers"], {
        "attn_norm": {"scale": (d,)},
        "attn": {"q": (d, cfg["num_attention_heads"] * hd),
                 "k": (d, cfg["num_key_value_heads"] * hd),
                 "v": (d, cfg["num_key_value_heads"] * hd),
                 "o": (cfg["num_attention_heads"] * hd, d),
                 "q_norm": {"scale": (hd,)},
                 "k_norm": {"scale": (hd,)}},
        "indexer": {"wq": (d, hi * di), "wk": (d, di), "ww": (d, hi),
                    "k_norm": ln(di)},
        "moe_norm": {"scale": (d,)},
        "moe": {"router": (d, sz["experts_all"]),
                "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d)},
    })
    return shapes


def param_count(cfg) -> dict:
    """Parameters held here, by part: what the configuration's table of
    the cut gives."""
    flat = plain.tree_paths(param_shapes(cfg))
    parts = {"decoder": 0, "embed_head": 0, "vision": 0}
    for path, shape in flat.items():
        part = ("vision" if path[0] in ("vision", "projector")
                else "embed_head" if path[0] in ("embed", "lm_head")
                else "decoder")
        parts[part] += math.prod(shape)
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------- weights

STD = 0.02
# Unit variance, the scale of the image's tokens as the projector gives
# them (0.84): a token then stays itself in the residual stream beside
# what attention adds, which is nearly the same for every query (a mean
# over thousands of values), and the seeded router spreads the tokens
# over the experts as a trained one does. At 0.1 that common part was
# three quarters of the stream after two layers and one expert took
# three quarters of a layer's tokens (PERF.md, PR 28).
EMBED_STD = 1.0
NORM_SCALE = (0.8, 1.2)


def make_weights(cfg, key) -> dict:
    """All parameters from ``key`` in one traced function (jit it).
    Matrices are normal with std 0.02 (the family's initialiser range),
    the embedding with std 1 (see ``EMBED_STD``), a block's last matrix (``o``, ``down``, ``fc2``) divided by
    sqrt(2 x layers) as residual stacks are initialised, norm scales
    drawn in 0.8-1.2 and biases drawn small, so that no leaf is constant
    and none has a gradient of exactly 0. float32."""
    flat = plain.tree_paths(param_shapes(cfg))
    keys = jax.random.split(key, len(flat))
    last = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    last_v = 1.0 / math.sqrt(2 * cfg["vision_num_hidden_layers"])
    leaves = {}
    for k, (path, shape) in zip(keys, flat.items()):
        name = path[-1]
        if name == "scale":
            leaf = jax.random.uniform(k, shape, jnp.float32, *NORM_SCALE)
        elif name == "bias":
            leaf = STD * jax.random.normal(k, shape, jnp.float32)
        elif path == ("embed",):
            leaf = EMBED_STD * jax.random.normal(k, shape, jnp.float32)
        else:
            std = STD
            if path[0] == "vision" and path[-2] in ("o", "fc2"):
                std *= last_v
            elif name in ("o", "down"):
                std *= last
            leaf = std * jax.random.normal(k, shape, jnp.float32)
        leaves[path] = leaf
    return plain.nest(leaves)


def make_batch(cfg, key, rows: int) -> dict:
    """``rows`` samples, each one image (unit-normal float32 pixels) at
    the head of ``text_len`` tokens drawn uniformly from the slice."""
    k_img, k_tok = jax.random.split(jax.random.fold_in(key, 7919))
    size = cfg["vision_config"]["image_size"]
    return {
        "image": jax.random.normal(k_img, (rows, size, size, 3),
                                   jnp.float32),
        "tokens": jax.random.randint(k_tok, (rows, cfg["text_len"]), 0,
                                     cfg["vocab_size"], jnp.int32),
    }


# ----------------------------------------------------------- building blocks


def _mm(x, w, nm):
    y = jnp.dot(nm.round_operand(x), nm.round_operand(w.astype(x.dtype)),
                precision=nm.precision, preferred_element_type=jnp.float32)
    return y.astype(nm.store)


def _ein(spec, a, b, nm):
    y = jnp.einsum(spec, nm.round_operand(a),
                   nm.round_operand(b.astype(a.dtype)),
                   precision=nm.precision,
                   preferred_element_type=jnp.float32)
    return y.astype(nm.store)


def _linear(p, x, nm):
    return _mm(x, p["kernel"], nm) + p["bias"].astype(nm.store)


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def layer_norm(x, p, eps=LN_EPS):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


def positions(cfg):
    """M-RoPE positions ``[3, T]`` (temporal, row, column) of one sample,
    Qwen2-VL's rule: the image's tokens share temporal index 0 and take
    the row and column of the merged grid; text carries one number three
    times and resumes at the largest position + 1."""
    sz = sizes(cfg)
    m = sz["merged"]
    rows = np.repeat(np.arange(m), m)
    cols = np.tile(np.arange(m), m)
    text = m + np.arange(cfg["text_len"])
    return np.stack([np.concatenate([np.zeros(m * m, np.int64), text]),
                     np.concatenate([rows, text]),
                     np.concatenate([cols, text])]).astype(np.int32)


def mrope_angles(cfg, pos3):
    """``[T, head_dim / 2]`` rotation angles: pair ``i`` turns by the
    position of its section times ``theta^(-2i / head_dim)``."""
    half = cfg["head_dim"] // 2
    sections = cfg["rope_scaling"]["mrope_section"]
    assert sum(sections) == half
    which = np.repeat(np.arange(3), sections)               # [half]
    inv = cfg["rope_theta"] ** (-np.arange(half) / half)
    pos = jnp.asarray(pos3, jnp.float32)[which]             # [half, T]
    return (pos * jnp.asarray(inv, jnp.float32)[:, None]).T


def rope_angles_1d(theta, pairs: int, span: int, length: int):
    """``[length, pairs]`` angles of a 1-D rotary over ``span`` dims."""
    inv = theta ** (-np.arange(pairs) / (span // 2))
    return (jnp.arange(length, dtype=jnp.float32)[:, None]
            * jnp.asarray(inv, jnp.float32))


def rotate(x, angles):
    """Rotary embedding of the leading ``2 x angles.shape[-1]`` dims of
    ``x [T, heads, dim]``, pair ``i`` being dims ``(i, i + pairs)``."""
    pairs = angles.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :pairs], xf[..., pairs:2 * pairs], \
        xf[..., 2 * pairs:]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                          -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ tower


def vision_tower(cfg, p, image, nm):
    """One image ``[S, S, 3]`` -> ``[n_img, hidden]`` projected tokens."""
    v, sz = cfg["vision_config"], sizes(cfg)
    g, ps, heads = sz["grid"], v["patch_size"], v["num_attention_heads"]
    x = image.astype(nm.store).reshape(g, ps, g, ps, 3)
    x = x.transpose(0, 2, 1, 3, 4).reshape(g * g, ps * ps * 3)
    pv = p["vision"]
    x = _linear(pv["patch_embed"], x, nm) + pv["pos_embed"].astype(nm.store)
    dh = v["hidden_size"] // heads

    def block(x, lp):
        h = layer_norm(x, lp["norm1"])
        q, k, val = (_linear(lp["attn"][n], h, nm).reshape(-1, heads, dh)
                     for n in "qkv")
        logits = _ein("qhd,khd->hqk", q, k, nm).astype(jnp.float32)
        probs = jax.nn.softmax(logits / math.sqrt(dh), -1).astype(nm.store)
        o = _ein("hqk,khd->qhd", probs, val, nm).reshape(-1, heads * dh)
        x = x + _linear(lp["attn"]["o"], o, nm)
        h = layer_norm(x, lp["norm2"])
        return x + _linear(lp["mlp"]["fc2"],
                           gelu_tanh(_linear(lp["mlp"]["fc1"], h, nm)),
                           nm), None

    x, _ = lax.scan(block, x, pv["layers"])
    x = layer_norm(x, pv["post_norm"])
    m, s = sz["merged"], v["spatial_merge_size"]
    x = x.reshape(m, s, m, s, -1).transpose(0, 2, 1, 3, 4)
    x = x.reshape(m * m, -1)
    pp = p["projector"]
    x = layer_norm(x, pp["norm"])
    return _linear(pp["fc2"], gelu_tanh(_linear(pp["fc1"], x, nm)), nm)


# ---------------------------------------------------------------- decoder


def indexer_inputs(cfg, p, h, nm):
    """``qI [T, heads, dim]``, ``kI [T, dim]``, ``w [T, heads]`` (the
    score's scale folded into ``w``) from the detached ``h``."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h = lax.stop_gradient(h)
    t = h.shape[0]
    angles = rope_angles_1d(cfg["rope_theta"], di // 4, di // 2, t)
    qi = rotate(_mm(h, p["wq"], nm).reshape(t, hi, di), angles)
    ki = layer_norm(_mm(h, p["wk"], nm), p["k_norm"])
    ki = rotate(ki[:, None, :], angles)[:, 0]
    w = _mm(h, p["ww"], nm).astype(jnp.float32) * (di ** -0.5 * hi ** -0.5)
    return qi, ki, w


def index_scores(qi, ki, w, nm):
    """``I [Tq, Tk]``, float32."""
    dots = _ein("tjd,sd->tjs", qi, ki, nm).astype(jnp.float32)
    return jnp.sum(w[:, :, None] * jnp.maximum(dots, 0.0), axis=1)


def select(scores, t0, topk: int):
    """The selected set as a mask ``[Tq, Tk]``: query ``t0 + i`` keeps
    the ``topk`` largest scores among keys ``s <= t0 + i``."""
    tq, tk = scores.shape
    causal = (jnp.arange(tk)[None, :] <= t0 + jnp.arange(tq)[:, None])
    masked = jnp.where(causal, scores, NEG)
    if tk <= topk:
        return causal
    kth = lax.top_k(lax.stop_gradient(masked), topk)[0][:, -1]
    return causal & (masked >= kth[:, None])


def attention_chunk(cfg, q, k, v, qi, ki, w, t0, nm):
    """Queries ``t0 ..`` against keys ``0 ..``: -> (outputs ``[Tq, heads,
    dim]``, the alignment loss summed over these queries, the number of
    selected pairs, the mask)."""
    heads, kvh, hd = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    scores = index_scores(qi, ki, w, nm)
    sel = select(scores, t0, cfg["sa_config"]["topk"])
    qg = q.reshape(q.shape[0], kvh, heads // kvh, hd)
    logits = _ein("tgrd,sgd->grts", qg, k, nm).astype(jnp.float32)
    logits = jnp.where(sel, logits / math.sqrt(hd), NEG)
    probs = jax.nn.softmax(logits, -1)
    out = _ein("grts,sgd->tgrd", probs.astype(nm.store), v, nm)
    # the indexer's alignment loss against the detached attention
    target = lax.stop_gradient(jnp.sum(probs, (0, 1))) / heads
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, NEG), -1)
    kl = jnp.where(target > 0, target * (jnp.log(jnp.where(
        target > 0, target, 1.0)) - jnp.where(sel, log_q, 0.0)), 0.0)
    return (out.reshape(q.shape[0], heads, hd), jnp.sum(kl),
            jnp.sum(sel), sel)


def attention(cfg, p, pi, h, angles, nm, capture=False):
    """``h [T, hidden]`` (normed) -> (``[T, hidden]``, alignment loss,
    selected pairs, masks or None). Queries go in blocks of
    ``reference_key_block`` with the keys up to the block's end, and in
    chunks of ``reference_q_chunk`` inside a block."""
    heads, kvh, hd = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    t = h.shape[0]
    q = rms_norm(_mm(h, p["q"], nm).reshape(t, heads, hd),
                 p["q_norm"]["scale"], cfg["rms_norm_eps"])
    k = rms_norm(_mm(h, p["k"], nm).reshape(t, kvh, hd),
                 p["k_norm"]["scale"], cfg["rms_norm_eps"])
    v = _mm(h, p["v"], nm).reshape(t, kvh, hd)
    q, k = rotate(q, angles), rotate(k, angles)
    qi, ki, w = indexer_inputs(cfg, pi, h, nm)

    block = min(cfg.get("reference_key_block", t), t)
    chunk = min(cfg.get("reference_q_chunk", block), block)
    assert t % block == 0 and block % chunk == 0
    outs, kl, pairs, masks = [], 0.0, 0, []
    for b0 in range(0, t, block):
        end = b0 + block

        @jax.checkpoint
        def one(args, end=end):
            qc, qic, wc, t0 = args
            o, kl_c, n_c, sel = attention_chunk(
                cfg, qc, k[:end], v[:end], qic, ki[:end], wc, t0, nm)
            return o, kl_c, n_c, (sel if capture else None)

        n = block // chunk
        split = lambda a: a[b0:end].reshape(n, chunk, *a.shape[1:])
        o, kl_b, n_b, sel = lax.map(one, (
            split(q), split(qi), split(w), b0 + chunk * jnp.arange(n)))
        outs.append(o.reshape(block, heads * hd))
        kl, pairs = kl + jnp.sum(kl_b), pairs + jnp.sum(n_b)
        if capture:
            masks.append(jnp.pad(sel.reshape(block, end),
                                 ((0, 0), (0, t - end))))
    out = _mm(jnp.concatenate(outs), p["o"], nm)
    return out, kl, pairs, (jnp.concatenate(masks) if capture else None)


def route(cfg, router, h, nm):
    """-> (chosen experts ``[T, k]``, their gates renormalised to sum 1).
    The router's logits and softmax are float32 in every numerics."""
    logits = jnp.dot(nm.round_operand(h.astype(jnp.float32)),
                     nm.round_operand(router), precision=lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top, experts = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return experts, top


def moe(cfg, p, h, nm, share=None):
    """The part of the layer's result that the experts of ``share`` =
    (index, of) give, the router deciding over all of them; -> (``[T,
    hidden]``, tokens each held expert got, chosen experts)."""
    index, of = share or cfg["expert_share"]
    here = cfg["num_local_experts"] // of
    experts, gates = route(cfg, p["router"], h, nm)

    def one(carry, ew):
        e, gate_w, up_w, down_w = ew
        g = jnp.sum(jnp.where(experts == e, gates, 0.0), -1)
        y = _mm(jax.nn.silu(_mm(h, gate_w, nm)) * _mm(h, up_w, nm),
                down_w, nm)
        return carry + g[:, None].astype(nm.store) * y, jnp.sum(
            experts == e)

    y, tokens = lax.scan(
        jax.checkpoint(one), jnp.zeros_like(h),
        (index * here + jnp.arange(here), p["gate"], p["up"], p["down"]))
    return y, tokens, experts


def decoder_layer(cfg, p, x, angles, nm, capture=False):
    eps = cfg["rms_norm_eps"]
    a, kl, pairs, mask = attention(
        cfg, p["attn"], p["indexer"],
        rms_norm(x, p["attn_norm"]["scale"], eps), angles, nm, capture)
    x = x + a
    y, tokens, experts = moe(cfg, p["moe"],
                             rms_norm(x, p["moe_norm"]["scale"], eps), nm)
    return x + y, {"index_kl": kl, "selected_pairs": pairs,
                   "expert_tokens": tokens,
                   **({"mask": mask, "experts": experts} if capture
                      else {})}


def forward_sample(cfg, params, image, tokens, nm=plain.HIGHEST,
                   capture=False, remat=True):
    """One sample -> ``{"nll" [text_len], "index_kl", "selected_pairs",
    "expert_tokens" [layers, held experts]}`` (and with ``capture`` the
    logits, each layer's selection mask and routing choice)."""
    sz = sizes(cfg)
    img = vision_tower(cfg, params, image, nm)
    x = jnp.concatenate([img, params["embed"][tokens].astype(nm.store)])
    angles = mrope_angles(cfg, positions(cfg))
    layer = lambda x, p: decoder_layer(cfg, p, x, angles, nm, capture)
    if remat:
        layer = jax.checkpoint(layer)
    # one layer after another; each statistic comes stacked [layers, ...]
    x, stats = lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    # position n_img - 1 (the image's last token) predicts the first text
    # token, the last text token predicts nothing
    hidden = x[sz["n_img"] - 1: sz["seq"] - 1]
    logits = _mm(hidden, params["lm_head"], nm).astype(jnp.float32)
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0])
    out = {"nll": nll,
           "index_kl": jnp.sum(stats["index_kl"]),
           "selected_pairs": jnp.sum(stats["selected_pairs"]),
           "expert_tokens": stats["expert_tokens"]}
    if capture:
        out.update(logits=logits, masks=stats["mask"],
                   experts=stats["experts"])
    return out


def loss(cfg, params, batch, nm=plain.HIGHEST, kept=None):
    """-> (loss, {"lm_loss", "index_loss", "selected_pairs",
    "expert_tokens"}) over the batch, one sample after another.
    ``kept [rows]`` (1 or 0) leaves samples out of both means."""
    one = jax.checkpoint(lambda s: forward_sample(
        cfg, params, s["image"], s["tokens"], nm))
    out = lax.map(one, batch)
    if kept is None:
        kept = jnp.ones(out["index_kl"].shape, jnp.float32)
    mean = lambda per_sample: jnp.sum(per_sample * kept) / jnp.sum(kept)
    lm = mean(jnp.mean(out["nll"], -1))
    index = mean(out["index_kl"])
    return lm + cfg["index_loss_weight"] * index, {
        "lm_loss": lm, "index_loss": index,
        "selected_pairs": jnp.sum(out["selected_pairs"]),
        "expert_tokens": jnp.sum(out["expert_tokens"], 0)}


# ------------------------------------------------------------------ FLOPs


def selected_pairs(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the pairs attention is taken over."""
    return sum(min(t + 1, topk) for t in range(seq))


def forward_flops_parts(cfg) -> dict:
    """2 x multiply-adds of one sample's forward at this chip's share,
    by part: every matrix product of tower, projector, projections and
    router, the expected local experts a token (``experts per token x
    held / all``), the head over the slice on the labelled positions,
    the indexer's scores over the causal pairs, attention over the
    selected pairs only, whatever the program computes."""
    v, sa, sz = cfg["vision_config"], cfg["sa_config"], sizes(cfg)
    d, hd, t = cfg["hidden_size"], cfg["head_dim"], sz["seq"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, dv, fv = sz["grid"] ** 2, v["hidden_size"], v["intermediate_size"]
    dm = dv * v["spatial_merge_size"] ** 2
    tower = cfg["vision_num_hidden_layers"] * (
        2 * n * (4 * dv * dv + 2 * dv * fv) + 2 * 2 * n * n * dv)
    tower += 2 * n * (v["patch_size"] ** 2 * 3) * dv
    projector = 2 * sz["n_img"] * (dm * d + d * d)
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    causal = t * (t + 1) // 2
    sel = selected_pairs(t, sa["topk"])
    local = cfg["num_experts_per_tok"] * sz["experts_here"] \
        / sz["experts_all"]
    layer = {
        "projections": 2 * t * d * hd * (2 * heads + 2 * kvh),
        "indexer": 2 * t * d * (hi * di + di + hi) + 2 * causal * hi * di,
        "attention": 2 * 2 * sel * heads * hd,
        "router": 2 * t * d * sz["experts_all"],
        "experts": int(2 * t * local * 3 * d * cfg["moe_intermediate_size"]),
    }
    parts = {k: cfg["num_hidden_layers"] * x for k, x in layer.items()}
    parts.update(tower=tower, projector=projector,
                 head=2 * cfg["text_len"] * d * cfg["vocab_size"])
    return parts


def forward_flops_per_image(cfg) -> int:
    return int(sum(forward_flops_parts(cfg).values()))


def train_flops_per_image(cfg) -> int:
    """Forward and backward: three times the forward count. A sample is
    one image with its sequence. Recomputation is not counted."""
    return 3 * forward_flops_per_image(cfg)


# --------------------------------------------------------------- training

_STEP_KEYS = (
    "vision_config", "sa_config", "rope_scaling", "hidden_size", "head_dim",
    "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
    "vision_num_hidden_layers", "num_local_experts", "num_experts_per_tok",
    "norm_topk_prob", "moe_intermediate_size", "expert_share", "vocab_size",
    "text_len", "rope_theta", "rms_norm_eps", "index_loss_weight",
    "optimizer", "reference_key_block", "reference_q_chunk")


def make_step(cfg, nm: plain.Numerics = plain.HIGHEST):
    """One pair of jitted functions for each distinct set of arguments."""
    return _make_step(json.dumps({k: cfg[k] for k in _STEP_KEYS if k in cfg},
                                 sort_keys=True), nm)


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, nm):
    """One Adam step in two programs: ``grads(params, batch, kept) ->
    ((loss, stats), gradient)`` and ``update(params, mu, nu, gradient,
    count) -> (params, mu, nu)``, count from 1. ``kept [rows]`` says
    which samples count (the planted fault "half of the batch left out"
    is the same program with zeros in it). Two programs, so that Adam's
    moments need not be on the device while the gradient is taken: the
    gradient's pass holds the parameters, the gradient summed so far and
    one sample's, and with the moments beside them the control's
    numerics passed the chip's memory (16.04 GB of 15.75 GiB). ``update``
    is donated the parameters and the moments."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    lr, b1, b2, eps = opt["lr"], opt["beta1"], opt["beta2"], opt["eps"]
    warmup = opt["warmup_steps"]

    @jax.jit
    def grads(p, b, kept):
        return jax.value_and_grad(
            lambda q: loss(cfg, q, b, nm, kept), has_aux=True)(p)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, mu, nu, g, count):
        mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, mu, g)
        nu = jax.tree.map(lambda n, gi: b2 * n + (1 - b2) * gi * gi, nu, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        rate = lr * jnp.minimum(count / warmup, 1.0)
        p = jax.tree.map(
            lambda pi, m, n: pi - rate * (m / c1) / (jnp.sqrt(n / c2) + eps),
            p, mu, nu)
        return p, mu, nu

    return grads, update


def train_steps(cfg, params, batch, n_steps: int,
                nm: plain.Numerics = plain.HIGHEST,
                rows: tuple | None = None):
    """``n_steps`` of that step on one batch, from ``params`` (which it
    consumes); ``rows`` = (start, stop) keeps only those samples. Adam's
    moments wait on the host while a gradient is taken. ->
    (losses [n], Adam's first moment after step 1 (on the host),
    parameters after the last step)."""
    grads, update = make_step(cfg, nm)
    n_rows = batch["tokens"].shape[0]
    start, stop = rows or (0, n_rows)
    kept = ((np.arange(n_rows) >= start)
            & (np.arange(n_rows) < stop)).astype(np.float32)
    # placed as the update's outputs will be, so that the first call and
    # the later ones are one program (a compile of minutes)
    chip = next(iter(batch["tokens"].devices()))
    place = lambda t: jax.device_put(t, chip)
    host = lambda t: jax.tree.map(np.asarray, t)
    p = place(params)
    mu = nu = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), params)
    losses, first = [], None
    for i in range(n_steps):
        (value, _stats), g = grads(p, batch, kept)
        losses.append(value)
        p, mu, nu = update(p, place(mu), place(nu), g, jnp.float32(i + 1))
        del g
        if i == 0:
            first = host(mu)
        if i + 1 < n_steps:
            mu, nu = first if i == 0 else host(mu), host(nu)
    return jnp.stack(losses), first, p
