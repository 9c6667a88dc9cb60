"""Plain reference of the ``olmo_hybrid_7b`` configuration.

Olmo-Hybrid-7B as its public ``config.json`` states it
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
``model_type`` ``olmo_hybrid``): a text decoder whose ``layer_types``
repeat ``[linear_attention x 3, full_attention]``. Straight
``jax.numpy`` in float32 at ``lax.Precision.HIGHEST``, one sequence at a
time; nothing is imported from the program. The configuration's
``assumed`` lists what the config does not state.

One layer, input ``x`` (T x hidden), Olmo-3's reordered norm: ``h = x +
RMSNorm(Mixer(x))``, ``out = h + RMSNorm(Wdown(silu(Wgate h) * (Wup
h)))``; no bias anywhere; every RMSNorm has eps ``rms_norm_eps`` and a
scale.

- Linear attention (Gated DeltaNet in FLA's layer form), ``H`` =
  ``linear_num_key_heads`` heads, ``dk`` / ``dv`` the key and value
  head widths: ``[q | k | v] = silu(conv(x [Wq | Wk | Wv]))``, ``conv``
  causal and depthwise over ``linear_conv_kernel_dim`` positions
  (earlier positions zero); per head ``q <- q / sqrt(|q|^2 + 1e-6) /
  sqrt(dk)``, ``k <- k / sqrt(|k|^2 + 1e-6)``; ``beta = 2 sigmoid(x
  Wb)`` (``linear_allow_neg_eigval``); ``g = -exp(A_log) softplus(x Wa
  + dt_bias)``. The state ``S`` (``dk x dv`` a head, zero at the start)
  follows the recurrence token by token: ``S <- exp(g_t) S``, ``S <- S
  + beta_t k_t (v_t - S^T k_t)^T``, ``o_t = S^T q_t``; ``y =
  RMSNorm_dv(o) w * silu(x Wz)`` per head; ``out = y Wo``.
- Full attention, ``num_attention_heads`` heads of ``hidden /
  heads``: ``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` over the whole
  width, ``v = x Wv``; rotary on ``q`` and ``k`` at the token's index
  (``rope_theta``, pairs ``(i, i + half)``); causal softmax at ``1 /
  sqrt(head)``, in blocks of ``reference_q_block`` queries; ``out =
  concat(o) Wo``.
- ``logits = RMSNorm(x) Whead``.

:func:`logits_at` is the comparison's forward: the whole sequence (a
prompt and the program's own generated tokens, teacher-forced) and the
logits at the positions asked for. Its ``numerics`` (:data:`NUMERICS`)
gives the truth (``highest``, float32), the precision the configuration
states (``stated``: bfloat16 operands and storage, the delta rule in
float32), or one of the configuration's controls, each the stated
precision with one departure: ``bf16_state`` (the delta-rule state
rounded to bfloat16 after every token, a lower precision than stated)
and ``conv_reset`` (from the first position of the decode on, the conv
sees zeros where the prompt's last positions stood: decode without the
conv state carried from prefill).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import plain

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


# ------------------------------------------------------------------ sizes


def sizes(cfg) -> dict:
    h = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    types = cfg["layer_types"]
    return {
        "hidden": cfg["hidden_size"], "periods": len(types) // 4,
        "linear_layers": types.count("linear_attention"),
        "full_layers": types.count("full_attention"),
        "heads": cfg["num_attention_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "linear_heads": h, "dk": dk, "dv": dv,
        "conv_dim": h * (2 * dk + dv), "kernel": cfg["linear_conv_kernel_dim"],
    }


def param_shapes(cfg) -> dict:
    """The parameters as the program stacks them: ``linear/*`` ``[periods,
    3, ...]`` (layers ``4 p + i``), ``full/*`` ``[periods, ...]`` (layer
    ``4 p + 3``)."""
    sz = sizes(cfg)
    if cfg["layer_types"] != (["linear_attention"] * 3
                              + ["full_attention"]) * sz["periods"]:
        raise ValueError("layer_types is not whole periods of 3 linear "
                         "layers and a full one")
    d, f, p = cfg["hidden_size"], cfg["intermediate_size"], sz["periods"]
    h, dk, dv = sz["linear_heads"], sz["dk"], sz["dv"]
    mlp = {"gate": (d, f), "up": (d, f), "down": (f, d), "mlp_norm": (d,)}
    linear = {"wq": (d, h * dk), "wk": (d, h * dk), "wv": (d, h * dv),
              "wz": (d, h * dv), "wa": (d, h), "wb": (d, h),
              "conv": (sz["kernel"], sz["conv_dim"]), "A_log": (h,),
              "dt_bias": (h,), "o_norm": (dv,), "wo": (h * dv, d),
              "mixer_norm": (d,), **mlp}
    full = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "q_norm": (d,), "k_norm": (d,), "mixer_norm": (d,), **mlp}
    return {"embed": {"embed": (cfg["vocab_size"], d)},
            "final_norm": {"final_norm": (d,)},
            "lm_head": {"lm_head": (d, cfg["vocab_size"])},
            "linear": {k: (p, 3, *s) for k, s in linear.items()},
            "full": {k: (p, *s) for k, s in full.items()}}


def param_count(cfg) -> int:
    return sum(math.prod(s) for s in
               plain.tree_paths(param_shapes(cfg)).values())


# ---------------------------------------------------------------- weights

NORM_SCALE = (0.8, 1.2)
GATE_GAIN = 0.5         # x Wa, x Wb of std ~0.5 |x| / sqrt(hidden)
FLOAT32 = ("A_log", "dt_bias", "o_norm", "mixer_norm", "mlp_norm",
           "q_norm", "k_norm", "final_norm")


def make_weights(cfg, key) -> dict:
    """All parameters from ``key`` in one traced function (jit it), as
    the program stores them: matrices and the conv in bfloat16, norm
    scales and the decay's two vectors in float32. Matrices normal with
    std 1 / sqrt(fan-in), the embedding with std 1, ``Wa`` and ``Wb``
    with std 0.5 / sqrt(hidden) (so that ``beta`` spans (0, 2) and the
    decays span fast and slow heads), the conv uniform over +-1 /
    sqrt(kernel), ``A`` uniform on [1, 16], ``softplus(dt_bias)``
    log-uniform on [1e-3, 0.1] (FLA's), norm scales in 0.8-1.2."""
    flat = plain.tree_paths(param_shapes(cfg))
    keys = jax.random.split(key, len(flat))
    leaves = {}
    for k, (path, shape) in zip(keys, flat.items()):
        name = path[-1]
        if name.endswith("norm"):
            leaf = jax.random.uniform(k, shape, F32, *NORM_SCALE)
        elif name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, F32, math.log(1e-3),
                                            math.log(0.1)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "conv":
            bound = 1.0 / math.sqrt(shape[-2])
            leaf = jax.random.uniform(k, shape, F32, -bound, bound)
        elif name == "embed":
            leaf = jax.random.normal(k, shape, F32)
        else:
            gain = GATE_GAIN if name in ("wa", "wb") else 1.0
            leaf = gain / math.sqrt(shape[-2]) * jax.random.normal(
                k, shape, F32)
        leaves[path] = leaf if name in FLOAT32 else leaf.astype(jnp.bfloat16)
    return plain.nest(leaves)


def make_prompts(cfg, traffic, seed: int, count: int) -> list:
    """``count`` prompts: their lengths the quantiles of the log-uniform
    distribution on ``[prompt_min, prompt_max]`` in an order drawn from
    the traffic's ``trace_seed`` (one replayed trace: every run offers
    the same lengths in the same order), their ids uniform over the
    vocabulary from ``seed``."""
    lo, hi = traffic["prompt_min"], traffic["prompt_max"]
    q = (np.arange(count) + 0.5) / count
    lengths = np.round(np.exp(math.log(lo) + q * math.log(hi / lo)))
    lengths = lengths.astype(np.int64)
    np.random.default_rng([int(traffic["trace_seed"]), 4]).shuffle(lengths)
    rng = np.random.default_rng([int(seed), 4])
    return [rng.integers(0, cfg["vocab_size"], n, dtype=np.int32)
            for n in lengths]


# ---------------------------------------------------------------- forward


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the forward rounds. ``bf16_operands``: the activation operand
    of every product, and what the configuration stores in bfloat16 (the
    projections the conv reads, keys and values), rounded to bfloat16;
    the delta rule, the norms and the softmax stay float32.
    ``bf16_state``: the delta-rule state rounded to bfloat16 after every
    token. ``conv_reset``: from ``reset_at`` on, the conv sees zeros for
    the positions before it."""

    bf16_operands: bool = False
    bf16_state: bool = False
    conv_reset: bool = False


NUMERICS = {
    # the truth the program is held to
    "highest": Numerics(),
    # the precision the configuration states, computed independently:
    # how far a sound computation at that precision lies from the truth
    "stated": Numerics(bf16_operands=True),
    # the two controls, each the stated precision with one departure
    "bf16_state": Numerics(bf16_operands=True, bf16_state=True),
    "conv_reset": Numerics(bf16_operands=True, conv_reset=True),
}


def _bf16(x):
    """``x`` rounded to bfloat16's precision, kept in float32: an explicit
    rounding the compiler may not drop (a cast down and up again it may,
    where it allows excess precision, as XLA on a TPU does)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _r(x, nm: Numerics):
    return _bf16(x) if nm.bf16_operands else x


def _mm(x, w, nm: Numerics):
    return jnp.dot(_r(x, nm), w.astype(F32), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def mlp(p, h, nm):
    return _mm(jax.nn.silu(_mm(h, p["gate"], nm)) * _mm(h, p["up"], nm),
               p["down"], nm)


def conv(x, w, reset_at):
    """Causal depthwise conv of ``x [T, C]`` by ``w [K, C]``; from
    position ``reset_at`` on, inputs before it count as zero."""
    t, kk = x.shape[0], w.shape[0]
    pos = jnp.arange(t)
    out = jnp.zeros_like(x)
    for j in range(kk):
        shift = kk - 1 - j
        src = jnp.concatenate([jnp.zeros((shift, x.shape[1]), x.dtype),
                               x[:t - shift]])
        cut = (pos >= reset_at) & (pos - shift < reset_at)
        out = out + w[j].astype(F32) * jnp.where(cut[:, None], 0.0, src)
    return out


def delta_rule(q, k, v, beta, g, round_state: bool):
    """The recurrence, token by token: ``q``, ``k [T, H, dk]``, ``v [T,
    H, dv]``, ``beta``, ``g [T, H]`` -> ``o [T, H, dv]``."""
    h, dk, dv = k.shape[1], k.shape[2], v.shape[2]

    def step(s, xs):
        qt, kt, vt, bt, gt = xs
        s = s * jnp.exp(gt)[:, None, None]
        mem = jnp.einsum("hde,hd->he", s, kt, precision=HIGHEST)
        s = s + kt[:, :, None] * ((vt - mem) * bt[:, None])[:, None, :]
        if round_state:
            s = _bf16(s)
        return s, jnp.einsum("hde,hd->he", s, qt, precision=HIGHEST)

    _, o = lax.scan(step, jnp.zeros((h, dk, dv), F32), (q, k, v, beta, g))
    return o


def linear_attention(cfg, p, x, nm: Numerics, reset_at):
    sz, eps = sizes(cfg), cfg["rms_norm_eps"]
    h, dk, dv = sz["linear_heads"], sz["dk"], sz["dv"]
    t = x.shape[0]
    qkv = _r(jnp.concatenate([_mm(x, p[n], nm) for n in ("wq", "wk", "wv")],
                             -1), nm)
    qkv = jax.nn.silu(conv(qkv, p["conv"], reset_at))
    q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], -1)
    q = _l2(q.reshape(t, h, dk)) / math.sqrt(dk)
    k = _l2(k.reshape(t, h, dk))
    v = v.reshape(t, h, dv)
    scale = 2.0 if cfg["linear_allow_neg_eigval"] else 1.0
    beta = scale * jax.nn.sigmoid(_mm(x, p["wb"], nm))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(_mm(x, p["wa"], nm)
                                                + p["dt_bias"])
    o = delta_rule(q, k, v, beta, g, nm.bf16_state)
    z = _mm(x, p["wz"], nm).reshape(t, h, dv)
    y = rms_norm(o, p["o_norm"], eps) * jax.nn.silu(z)
    return _mm(y.reshape(t, h * dv), p["wo"], nm)


def rope(cfg, x):
    """Rotary on ``x [T, heads, head]`` at positions 0..T-1."""
    t, _, hd = x.shape
    half = hd // 2
    inv = cfg["rope_theta"] ** (-np.arange(half) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def full_attention(cfg, p, x, nm: Numerics):
    sz, eps = sizes(cfg), cfg["rms_norm_eps"]
    t, heads, hd = x.shape[0], sz["heads"], sz["head_dim"]
    q = _r(rope(cfg, rms_norm(_mm(x, p["wq"], nm), p["q_norm"], eps)
                .reshape(t, heads, hd)), nm)
    k = _r(rope(cfg, rms_norm(_mm(x, p["wk"], nm), p["k_norm"], eps)
                .reshape(t, heads, hd)), nm)
    v = _r(_mm(x, p["wv"], nm).reshape(t, heads, hd), nm)
    block = cfg["reference_q_block"]

    def one(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / math.sqrt(
            hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(jnp.arange(t)[None, None] <= qpos[None, :, None], s,
                      -jnp.inf)
        w = _r(jax.nn.softmax(s, -1), nm)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)

    o = lax.map(one, jnp.arange(t // block)).reshape(t, heads * hd)
    return _mm(o, p["wo"], nm)


def hidden(cfg, params, tokens, nm: Numerics, reset_at):
    """``tokens [T]`` (``T`` a multiple of ``reference_q_block``) ->
    the last layer's output ``[T, hidden]``."""
    eps = cfg["rms_norm_eps"]
    reset_at = reset_at if nm.conv_reset else tokens.shape[0]
    x = params["embed"]["embed"][tokens].astype(F32)
    as32 = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)

    def block(x, p, mixer):
        h = x + rms_norm(mixer(p, x), p["mixer_norm"], eps)
        return h + rms_norm(mlp(p, h, nm), p["mlp_norm"], eps)

    def period(x, layers):
        lin, full = layers

        def one_linear(x, p):
            return block(x, as32(p), lambda p, x: linear_attention(
                cfg, p, x, nm, reset_at)), None

        x, _ = lax.scan(one_linear, x, lin)
        return block(x, as32(full), lambda p, x: full_attention(
            cfg, p, x, nm)), None

    x, _ = lax.scan(period, x, (params["linear"], params["full"]))
    return x


def logits_at(cfg, params, tokens, positions, numerics: str = "highest",
              reset_at=None):
    """``tokens [T]`` (padded past the sequence's end: later positions
    change nothing before them) -> ``[len(positions), vocab]`` float32
    logits at ``positions`` in ``NUMERICS[numerics]``; ``reset_at`` is
    the first position of the decode, read by ``conv_reset`` alone."""
    fn = _logits_fn(json.dumps(cfg, sort_keys=True), numerics)
    return fn(params, jnp.asarray(tokens), jnp.asarray(positions),
              jnp.asarray(-1 if reset_at is None else reset_at))


@functools.lru_cache(maxsize=None)
def _logits_fn(cfg_json: str, numerics: str):
    cfg, nm = json.loads(cfg_json), NUMERICS[numerics]

    def fn(params, tokens, positions, reset_at):
        with jax.default_matmul_precision("highest"):
            x = hidden(cfg, params, tokens, nm, reset_at)[positions]
            x = rms_norm(x, params["final_norm"]["final_norm"].astype(F32),
                         cfg["rms_norm_eps"])
            return _mm(x, params["lm_head"]["lm_head"], nm)

    return jax.jit(fn)
