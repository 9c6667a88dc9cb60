"""Plain reference of the ``kanana2_30b_a3b`` configuration.

kanana-2-30b-a3b-instruct-2601 as its public ``config.json`` states it
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json,
``model_type`` ``deepseek_v3``): a pre-norm text decoder whose every
layer is latent attention (MLA) followed by a feed-forward network, the
first layer's dense, the others' a mixture of experts with a shared
expert. Straight ``jax.numpy`` in float32 at ``lax.Precision.HIGHEST``,
one sequence at a time and one chunk of queries at a time so that it
fits beside its own optimiser state; nothing is imported from the
program.

One layer, input ``x`` (T x hidden): ``x += Attn(RMSNorm(x))``,
``x += FFN(RMSNorm(x))``; no bias anywhere.

- Attention (``q_lora_rank`` null: no query compression): ``q = h Wq``,
  ``heads`` heads of ``[q_nope (qk_nope_head_dim) | q_rope
  (qk_rope_head_dim)]``; ``c = h Wkva`` = ``[c_kv (kv_lora_rank) |
  k_rope]``; ``RMSNorm(c_kv) Wkvb`` = ``heads`` heads of ``[k_nope |
  v (v_head_dim)]``. Rotary (``rope_theta``, positions 0..T-1, no
  scaling) on ``q_rope`` of every head and on the one ``k_rope``, which
  all heads share: ``k_h = [k_nope_h | k_rope]``.
  ``o_h = softmax_{s <= t}(q_h . k_h / sqrt(qk_head_dim)) v_h``,
  ``out = concat(o) Wo``. The rotary pairs dims ``(i, i + pairs)`` of
  the rotary part; the published weights pair ``(2i, 2i + 1)``
  (``rope_interleave``), which is this under a fixed permutation of the
  rotary columns of ``Wq`` and ``Wkva``: with seeded weights the same
  model.
- Dense FFN (layer 0): ``Wdown(silu(Wgate h) * (Wup h))``.
- Expert layer (DeepSeek-V3's, ``n_group`` 1 and ``topk_group`` 1, so no
  group limit): ``s = sigmoid(h Wr)`` over all published experts,
  float32; the ``num_experts_per_tok`` largest of ``s + b`` are chosen
  (``b`` the selection bias: it moves the choice and never the gate);
  gates ``g_e = routed_scaling_factor * s_e / (sum of the chosen s +
  1e-20)``; ``y = sum_e g_e Expert_e(h) + Shared(h)`` over the experts
  this chip holds (``expert_share``), each a SiLU-gated MLP of
  ``moe_intermediate_size``; ``Shared`` one SiLU-gated MLP of
  ``n_shared_experts`` times that width. What the absent experts would
  add is left out; the shared expert is whole.
- Loss: mean next-token cross-entropy over the vocabulary slice. No
  auxiliary loss.

Training is Adam as ``optax.adam`` has it (bias-corrected moments,
epsilon outside the root) from float32 parameters, update ``n`` (from 1)
at the rate ``lr * min(n / warmup_steps, 1)``; then the balancing rule
of DeepSeek-V3 (arXiv:2412.19437, section 2.1.2; arXiv:2408.15664): for
each expert layer ``b_e += gamma * sign(mean_e'(c_e') - c_e)``, ``c_e``
the tokens of the step's batch that chose expert ``e`` (all published
experts are counted). ``b`` has a gradient of 0 by construction and Adam
leaves it where it is. Everything the public config does not state is
listed in the configuration file's ``assumed``.
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

NEG = -jnp.inf


# ------------------------------------------------------------------ sizes


def sizes(cfg) -> dict:
    of = cfg["expert_share"][1]
    return {
        "seq": cfg["seq_len"],
        "experts_all": cfg["router_width"],
        "experts_here": cfg["router_width"] // of,
        "expert_layers": cfg["num_hidden_layers"]
        - cfg["first_k_dense_replace"],
        "qk_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
    }


def param_shapes(cfg) -> dict:
    """Nested ``{name: shape}`` of every parameter leaf. The expert
    layers are stacked: each of their leaves has the layer as its
    leading axis; the leading dense layer stands alone."""
    sz = sizes(cfg)
    if cfg["first_k_dense_replace"] != 1:
        raise ValueError("one leading dense layer is what is written here")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    mlp = lambda width: {"gate": (d, width), "up": (d, width),
                         "down": (width, d)}
    attn = {"q": (d, heads * sz["qk_dim"]),
            "kv_a": (d, rank + dr),
            "kv_norm": {"scale": (rank,)},
            "kv_b": (rank, heads * (cfg["qk_nope_head_dim"]
                                    + cfg["v_head_dim"])),
            "o": (heads * cfg["v_head_dim"], d)}
    e, f = sz["experts_here"], cfg["moe_intermediate_size"]
    stack = lambda n, tree: jax.tree.map(
        lambda shape: (n, *shape), tree,
        is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": (cfg["vocab_size"], d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, cfg["vocab_size"]),
        "dense": {"attn_norm": {"scale": (d,)}, "attn": attn,
                  "mlp_norm": {"scale": (d,)},
                  "mlp": mlp(cfg["intermediate_size"])},
        "layers": stack(sz["expert_layers"], {
            "attn_norm": {"scale": (d,)}, "attn": attn,
            "moe_norm": {"scale": (d,)},
            "moe": {"router": (d, sz["experts_all"]),
                    "bias": (sz["experts_all"],),
                    "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d),
                    "shared": mlp(cfg["n_shared_experts"] * f)},
        }),
    }


def param_count(cfg) -> dict:
    """Parameters held here, by part: what the configuration's table of
    the cut gives."""
    flat = plain.tree_paths(param_shapes(cfg))
    parts = {"dense_layer": 0, "expert_layers": 0, "embed_head": 0}
    for path, shape in flat.items():
        part = ("dense_layer" if path[0] == "dense"
                else "expert_layers" if path[0] == "layers"
                else "embed_head")
        parts[part] += math.prod(shape)
    parts["total"] = sum(parts.values())
    return parts


# ---------------------------------------------------------------- weights

STD = 0.02
EMBED_STD = 1.0         # see keye_vl2.EMBED_STD: a token stays itself
NORM_SCALE = (0.8, 1.2)


def make_weights(cfg, key) -> dict:
    """All parameters from ``key`` in one traced function (jit it).
    Matrices are normal with std 0.02, the embedding with std 1, a
    block's last matrix (``o``, ``down``) divided by sqrt(2 x layers) as
    residual stacks are initialised, norm scales drawn in 0.8-1.2; the
    selection bias starts at 0. float32."""
    flat = plain.tree_paths(param_shapes(cfg))
    keys = jax.random.split(key, len(flat))
    last = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    leaves = {}
    for k, (path, shape) in zip(keys, flat.items()):
        name = path[-1]
        if name == "scale":
            leaf = jax.random.uniform(k, shape, jnp.float32, *NORM_SCALE)
        elif name == "bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif path == ("embed",):
            leaf = EMBED_STD * jax.random.normal(k, shape, jnp.float32)
        else:
            std = STD * (last if name in ("o", "down") else 1.0)
            leaf = std * jax.random.normal(k, shape, jnp.float32)
        leaves[path] = leaf
    return plain.nest(leaves)


def make_batch(cfg, key, rows: int) -> dict:
    """``rows`` documents of ``seq_len + 1`` ids drawn uniformly from the
    slice: the first ``seq_len`` are the input positions, the last
    ``seq_len`` their labels."""
    k_tok = jax.random.fold_in(key, 7919)
    return {"tokens": jax.random.randint(
        k_tok, (rows, cfg["seq_len"] + 1), 0, cfg["vocab_size"], jnp.int32)}


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


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope_angles(cfg, length: int):
    """``[length, qk_rope_head_dim / 2]`` angles: pair ``i`` turns by the
    position times ``theta^(-2i / qk_rope_head_dim)``."""
    pairs = cfg["qk_rope_head_dim"] // 2
    inv = cfg["rope_theta"] ** (-np.arange(pairs) / pairs)
    return (jnp.arange(length, dtype=jnp.float32)[:, None]
            * jnp.asarray(inv, jnp.float32))


def rotate(x, angles):
    """Rotary embedding of ``x [T, heads, 2 x pairs]``, pair ``i`` being
    dims ``(i, i + pairs)``."""
    pairs = angles.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :pairs], xf[..., pairs:]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def gated_mlp(p, h, nm):
    return _mm(jax.nn.silu(_mm(h, p["gate"], nm)) * _mm(h, p["up"], nm),
               p["down"], nm)


# ---------------------------------------------------------------- decoder


def latent_qkv(cfg, p, h, angles, nm):
    """``h [T, hidden]`` (normed) -> ``q``, ``k`` ``[T, heads, qk_dim]``
    (rotated) and ``v [T, heads, v_head_dim]``, materialised for every
    head."""
    heads, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"])
    rank, t = cfg["kv_lora_rank"], h.shape[0]
    q = _mm(h, p["q"], nm).reshape(t, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], angles)], -1)
    c = _mm(h, p["kv_a"], nm)
    k_rope = rotate(c[:, None, rank:], angles)                  # one head
    c_kv = rms_norm(c[:, :rank], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    kv = _mm(c_kv, p["kv_b"], nm).reshape(t, heads, -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (t, heads, dr))], -1)
    return q, k, kv[..., dn:]


def attention_chunk(q, k, v, t0, nm):
    """Queries ``t0 ..`` against keys ``0 ..``, causal: -> ``[Tq, heads,
    v_head_dim]``."""
    tq, tk = q.shape[0], k.shape[0]
    causal = jnp.arange(tk)[None, :] <= t0 + jnp.arange(tq)[:, None]
    logits = _ein("thd,shd->hts", q, k, nm).astype(jnp.float32)
    logits = jnp.where(causal, logits / math.sqrt(q.shape[-1]), NEG)
    probs = jax.nn.softmax(logits, -1)
    return _ein("hts,shd->thd", probs.astype(nm.store), v, nm)


def attention(cfg, p, h, angles, nm):
    """``h [T, hidden]`` (normed) -> ``[T, hidden]``. Queries go in
    blocks of ``reference_key_block`` with the keys up to the block's
    end, and in chunks of ``reference_q_chunk`` inside a block."""
    t = h.shape[0]
    q, k, v = latent_qkv(cfg, p, h, angles, nm)
    block = min(cfg.get("reference_key_block", t), t)
    chunk = min(cfg.get("reference_q_chunk", block), block)
    assert t % block == 0 and block % chunk == 0
    outs = []
    for b0 in range(0, t, block):
        end = b0 + block

        @jax.checkpoint
        def one(args, end=end):
            qc, t0 = args
            return attention_chunk(qc, k[:end], v[:end], t0, nm)

        n = block // chunk
        o = lax.map(one, (q[b0:end].reshape(n, chunk, *q.shape[1:]),
                          b0 + chunk * jnp.arange(n)))
        outs.append(o.reshape(block, -1))
    return _mm(jnp.concatenate(outs), p["o"], nm)


def route(cfg, router, bias, h, nm):
    """-> (chosen experts ``[T, k]``, their gates). The router's scores
    are float32 in every numerics."""
    logits = jnp.dot(nm.round_operand(h.astype(jnp.float32)),
                     nm.round_operand(router), precision=lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    if cfg["scoring_func"] != "sigmoid":
        raise ValueError("the sigmoid router is what is written here")
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, experts, -1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return experts, gates * cfg["routed_scaling_factor"]


def moe(cfg, p, h, nm, share=None, shared: bool = True):
    """The part of the layer's result that the experts of ``share`` =
    (index, of) give (``p`` holds those), the router deciding over all
    of them, plus (with ``shared``) the shared expert's; -> (``[T,
    hidden]``, tokens that chose each of all experts, chosen
    experts)."""
    index, of = share or cfg["expert_share"]
    every = cfg["router_width"]
    here = every // of
    experts, gates = route(cfg, p["router"], p["bias"], h, nm)

    def one(carry, ew):
        e, gate_w, up_w, down_w = ew
        g = jnp.sum(jnp.where(experts == e, gates, 0.0), -1)
        y = gated_mlp({"gate": gate_w, "up": up_w, "down": down_w}, h, nm)
        return carry + g[:, None].astype(nm.store) * y, None

    y, _ = lax.scan(
        jax.checkpoint(one), jnp.zeros_like(h),
        (index * here + jnp.arange(here), p["gate"], p["up"], p["down"]))
    if shared:
        y = y + gated_mlp(p["shared"], h, nm)
    counts = jnp.sum(experts[..., None] == jnp.arange(every), (0, 1))
    return y, counts, experts


def forward_sample(cfg, params, tokens, nm=plain.HIGHEST, capture=False,
                   remat=True):
    """One document ``[seq_len + 1]`` -> ``{"nll" [seq_len],
    "expert_counts" [expert layers, all experts]}`` (and with
    ``capture`` the logits and each expert layer's routing choice)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens[:-1]].astype(nm.store)
    angles = rope_angles(cfg, x.shape[0])

    def dense_layer(x, p):
        x = x + attention(cfg, p["attn"], rms_norm(
            x, p["attn_norm"]["scale"], eps), angles, nm)
        return x + gated_mlp(p["mlp"], rms_norm(
            x, p["mlp_norm"]["scale"], eps), nm)

    def expert_layer(x, p):
        x = x + attention(cfg, p["attn"], rms_norm(
            x, p["attn_norm"]["scale"], eps), angles, nm)
        y, counts, experts = moe(cfg, p["moe"], rms_norm(
            x, p["moe_norm"]["scale"], eps), nm)
        return x + y, {"expert_counts": counts,
                       **({"experts": experts} if capture else {})}

    if remat:
        dense_layer = jax.checkpoint(dense_layer)
        expert_layer = jax.checkpoint(expert_layer)
    x = dense_layer(x, params["dense"])
    # one layer after another; each statistic comes stacked [layers, ...]
    x, stats = lax.scan(expert_layer, x, params["layers"])
    hidden = rms_norm(x, params["final_norm"]["scale"], eps)
    logits = _mm(hidden, params["lm_head"], nm).astype(jnp.float32)
    labels = tokens[1:]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])
    out = {"nll": nll, "expert_counts": stats["expert_counts"]}
    if capture:
        out.update(logits=logits, experts=stats["experts"])
    return out


def loss(cfg, params, batch, nm=plain.HIGHEST, kept=None):
    """-> (loss, {"expert_counts" [expert layers, all experts]}) over
    the batch, one document after another. ``kept [rows]`` (1 or 0)
    leaves documents out of the mean and of the counts."""
    one = jax.checkpoint(lambda tokens: forward_sample(
        cfg, params, tokens, nm))
    out = lax.map(one, batch["tokens"])
    if kept is None:
        kept = jnp.ones(out["nll"].shape[:1], jnp.float32)
    value = jnp.sum(jnp.mean(out["nll"], -1) * kept) / jnp.sum(kept)
    counts = jnp.sum(out["expert_counts"]
                     * kept.astype(jnp.int32)[:, None, None], 0)
    return value, {"expert_counts": counts}


def balance(bias, counts, gamma: float):
    """The rule: ``b_e += gamma * sign(mean_e'(c_e') - c_e)``, a layer a
    row."""
    c = counts.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(c, -1, keepdims=True) - c)


# ------------------------------------------------------------------ FLOPs


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def forward_flops_parts(cfg) -> dict:
    """2 x multiply-adds of one sample's forward at this chip's share,
    by part: every matrix product of the projections and the router,
    attention over the causal pairs (at the query/key width for the
    scores, at the value width for the values), the dense MLP, the
    shared expert, the expected local routed experts a token (``experts
    per token x held / all``), the head over the slice, whatever the
    program computes."""
    sz = sizes(cfg)
    d, t, heads = cfg["hidden_size"], sz["seq"], cfg["num_attention_heads"]
    rank, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dn, dv, f = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                 cfg["moe_intermediate_size"])
    local = cfg["num_experts_per_tok"] * sz["experts_here"] \
        / sz["experts_all"]
    every_layer = {
        "projections": 2 * t * (d * heads * sz["qk_dim"] + d * (rank + dr)
                                + rank * heads * (dn + dv)
                                + heads * dv * d),
        "attention": 2 * causal_pairs(t) * heads * (sz["qk_dim"] + dv),
    }
    expert_layer = {
        "router": 2 * t * d * sz["experts_all"],
        "shared": 2 * t * 3 * d * cfg["n_shared_experts"] * f,
        "experts": int(2 * t * local * 3 * d * f),
    }
    parts = {k: cfg["num_hidden_layers"] * x for k, x in every_layer.items()}
    parts.update({k: sz["expert_layers"] * x
                  for k, x in expert_layer.items()})
    parts.update(
        dense_mlp=cfg["first_k_dense_replace"] * 2 * t * 3 * d
        * cfg["intermediate_size"],
        head=2 * t * d * cfg["vocab_size"])
    return parts


def forward_flops_per_image(cfg) -> int:
    return int(sum(forward_flops_parts(cfg).values()))


def train_flops_per_image(cfg) -> int:
    """Forward and backward: three times the forward count. A sample,
    one document, counts as one image. Recomputation is not counted."""
    return 3 * forward_flops_per_image(cfg)


# --------------------------------------------------------------- training

_STEP_KEYS = (
    "hidden_size", "num_attention_heads", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "intermediate_size",
    "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "scoring_func",
    "router_width", "expert_share", "num_hidden_layers",
    "first_k_dense_replace", "vocab_size", "seq_len", "rope_theta",
    "rms_norm_eps", "bias_update_rate", "optimizer", "reference_key_block",
    "reference_q_chunk")


def make_step(cfg, nm: plain.Numerics = plain.HIGHEST):
    """One pair of jitted functions for each distinct set of arguments."""
    return _make_step(json.dumps({k: cfg[k] for k in _STEP_KEYS if k in cfg},
                                 sort_keys=True), nm)


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, nm):
    """One step in two programs: ``grads(params, batch, kept) -> ((loss,
    stats), gradient)`` and ``update(params, mu, nu, gradient, counts,
    count) -> (params, mu, nu)``, count from 1: Adam on every leaf (the
    selection bias's gradient is 0, and Adam leaves it), then the
    balancing rule on the bias from ``counts``. ``kept [rows]`` says
    which documents count (the planted fault "half of the batch left
    out" is the same program with zeros in it). Two programs, so that
    Adam's moments need not be on the device while the gradient is taken
    (see ``keye_vl2._make_step``). ``update`` is donated the parameters
    and the moments."""
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
        p["layers"]["moe"]["bias"] = balance(
            p["layers"]["moe"]["bias"], counts, gamma)
        return p, mu, nu

    return grads, update


def train_steps(cfg, params, batch, n_steps: int,
                nm: plain.Numerics = plain.HIGHEST,
                rows: tuple | None = None):
    """``n_steps`` of that step on one batch, from ``params`` (which it
    consumes); ``rows`` = (start, stop) keeps only those documents.
    Adam's moments wait on the host while a gradient is taken. ->
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
