"""Token models: a vision-language decoder with sparse attention and a
mixture of experts (Keye-VL-2.0's layout).

The conv zoo is NHWC feature maps; this module is ``[batch, tokens,
hidden]``. One model, :class:`KeyeVL2`: a ViT tower (patch embedding,
pre-LayerNorm blocks, 2 x 2 merge, projector) whose tokens stand at the
head of the text's, and pre-norm decoder layers, each

- grouped-query attention over the keys a learned indexer selects
  (DeepSeek sparse attention): the indexer scores every causal pair,
  each query keeps its ``topk`` best keys, attention is a softmax over
  those; an alignment loss (KL from the detached attention to the
  indexer's softmax) is all that trains the indexer;
- a mixture of experts that is *told which experts it holds*
  (``expert_share = (index, of)``): it routes over all ``num_experts``,
  computes the part of the result its own experts give and drops no
  token whatever the imbalance. On one chip there is no exchange.

Numerics (``core/precision.py``): parameters float32; every multiply
takes ``dtype`` operands and accumulates in float32 (the indexer's
``[T, T]`` score product too: its scores are float32 from the
accumulation on); norms, the router's logits (a float32 product), the
exponent and the sum of every softmax, the selection and the loss are
float32; the attention's exponentials are stored in ``dtype`` for the
second product.

The decoder's layers, and the tower's, are one scanned body each
(:func:`_stacked`): their parameters are stacked on a leading axis
(``params/layers/...``), and the device program holds a layer once.

The heavy parts are pure functions of arrays (:func:`sparse_attention`,
:func:`moe_layer`, :func:`token_nll`), blocked so that an 8k sequence
fits: queries go in blocks of ``key_block`` against the keys up to the
block's end and in chunks of ``q_chunk`` inside a block, each chunk
recomputed on the way back; the selection (``lm/attn/select``) runs
once, ahead of the chunks, and is kept across the layer's
recomputation. On one TPU chip, where the shapes tile (heads of 128,
chunks that fill lane rows), the attention itself runs as the Pallas
kernels of ``ops/dsa_attention.py`` (:func:`kernel_attention`), which
keep the ``[heads, queries, keys]`` tiles on the chip, and a chunk's
indexer scores as those of ``ops/dsa_indexer.py``
(:func:`chunk_scores`), which do the same for the indexer's heads;
everywhere else as the XLA forms (:func:`sparse_attention`,
:func:`index_scores`), which the tests hold the kernels to. Named
scopes (``vlm/vision``, ``vlm/projector``,
``lm/attn/proj|indexer|select|sparse``, ``lm/index_loss``,
``lm/moe/route|experts``, ``lm/head``) put every device operation's
``op_name`` under the part it belongs to.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deepvision_tpu.core.precision import (
    compute_dot,
    compute_einsum,
    float32_dot,
)
from deepvision_tpu.models.registry import register

Dtype = Any
NEG = -jnp.inf
normal = nn.initializers.normal(0.02)
LN_EPS = 1e-6


# ------------------------------------------------------------------ norms


def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = LN_EPS):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps) * scale + bias
    return y.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps)


class LayerNorm(nn.Module):
    eps: float = LN_EPS

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        return layer_norm(x, scale, bias, self.eps)


class Linear(nn.Module):
    features: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", normal, (x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        return (compute_dot(x, kernel, self.dtype) + bias).astype(self.dtype)


# ----------------------------------------------------------------- rotary


def mrope_positions(merged: int, text_len: int) -> np.ndarray:
    """``[3, T]`` (temporal, row, column) positions of one sample, an
    image of ``merged x merged`` tokens at the head of ``text_len`` text
    tokens (Qwen2-VL's rule: the image shares one temporal index and
    takes its grid's rows and columns; text carries one number three
    times and resumes at the largest position + 1)."""
    rows = np.repeat(np.arange(merged), merged)
    cols = np.tile(np.arange(merged), merged)
    text = merged + np.arange(text_len)
    return np.stack([
        np.concatenate([np.zeros(merged * merged, np.int64), text]),
        np.concatenate([rows, text]),
        np.concatenate([cols, text])]).astype(np.int32)


def mrope_angles(pos3, head_dim: int, theta: float,
                 sections: Sequence[int]):
    """``[T, head_dim / 2]`` angles: frequency pair ``i`` turns by the
    position of its section (temporal, row, column) times
    ``theta^(-2i / head_dim)``. Text-only positions give 1-D RoPE."""
    half = head_dim // 2
    if sum(sections) != half:
        raise ValueError(f"mrope sections {sections} do not sum to {half}")
    which = np.repeat(np.arange(3), sections)
    inv = theta ** (-np.arange(half) / half)
    pos = jnp.asarray(pos3, jnp.float32)[which]             # [half, T]
    return (pos * jnp.asarray(inv, jnp.float32)[:, None]).T


def rope_angles(length: int, pairs: int, theta: float):
    """``[length, pairs]`` angles of a 1-D rotary by the token's index."""
    inv = theta ** (-np.arange(pairs) / pairs)
    return (jnp.arange(length, dtype=jnp.float32)[:, None]
            * jnp.asarray(inv, jnp.float32))


def yarn_angles(length: int, pairs: int, theta: float, factor: float,
                original: int, beta_fast: float, beta_slow: float):
    """``[length, pairs]`` angles of DeepSeek-V3's yarn rotary: pairs
    that turn more than ``beta_fast`` times over the ``original``
    length keep their frequency, pairs that turn fewer than
    ``beta_slow`` times have theirs divided by ``factor``, and a linear
    ramp over the pair's index joins the two (its
    ``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``)."""
    dim = 2 * pairs

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = theta ** (-np.arange(pairs) / pairs)
    keep = 1.0 - np.clip((np.arange(pairs) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * (1.0 - keep) + extra * keep
    return (jnp.arange(length, dtype=jnp.float32)[:, None]
            * jnp.asarray(inv, jnp.float32))


def yarn_mscale(factor: float, mscale: float) -> float:
    """yarn's attention factor ``0.1 mscale ln(factor) + 1`` (1 for a
    factor of 1 or less)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotate(x, angles):
    """Rotary embedding of the leading ``2 x angles.shape[-1]`` dims of
    ``x [..., T, heads, dim]``; pair ``i`` is dims ``(i, i + pairs)``."""
    pairs = angles.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :pairs], xf[..., pairs:2 * pairs]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., 2 * pairs:]], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------- sparse attention


def kth_largest(x, k: int):
    """The ``k``-th largest of every row of float32 ``x [rows, n]``
    (``-inf`` where a row has fewer than ``k`` entries above ``-inf``),
    exactly, by bisection on the bits: float32 ordered as int32, then 32
    passes of "how many are >= the midpoint". On the chip ``lax.top_k``
    with a ``k`` in the thousands is a full sort of every row: 1.9 ms
    for [512, 8192] against 0.26 ms here (v5e, PERF.md PR 28)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    flip = jnp.int32(0x7FFFFFFF)
    key = jnp.where(bits < 0, bits ^ flip, bits)     # order-preserving
    rows = x.shape[:1]
    lo = jnp.full(rows, jnp.iinfo(jnp.int32).min, jnp.int32)
    hi = jnp.full(rows, jnp.iinfo(jnp.int32).max, jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + ((lo & 1) | (hi & 1))   # upper middle
        enough = jnp.sum(key >= mid[:, None], -1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    lo, _ = lax.fori_loop(0, 33, halve, (lo, hi))
    return lax.bitcast_convert_type(jnp.where(lo < 0, lo ^ flip, lo),
                                    jnp.float32)


def index_scores(qi, ki, w, dtype):
    """``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])``, float32 from
    the accumulation on; ``w`` carries the score's scale."""
    with jax.named_scope("lm/attn/indexer"):
        dots = compute_einsum("tjd,sd->tjs", qi, ki, dtype)
        return jnp.sum(w[:, :, None] * jnp.maximum(dots, 0.0), axis=1)


def _causal(t0, tq: int, tk: int):
    return jnp.arange(tk)[None, :] <= t0 + jnp.arange(tq)[:, None]


def _blocks(t: int, key_block: int, q_chunk: int):
    """(block, chunk) that divide ``t``: a length the blocks do not
    divide is taken in one."""
    block = key_block if 0 < key_block <= t and t % key_block == 0 else t
    chunk = q_chunk if 0 < q_chunk <= block and block % q_chunk == 0 \
        else block
    return block, chunk


def _map_chunks(fn, arrays, b0: int, block: int, chunk: int):
    """``fn(chunk of each array, first query's index)`` over the chunks
    of rows ``b0 .. b0 + block``, one after another."""
    return lax.map(fn, _chunks_of(arrays, b0, block, chunk))


def _chunks_of(arrays, b0: int, block: int, chunk: int):
    """The chunks of rows ``b0 .. b0 + block`` of each array, stacked,
    and each chunk's first row's index."""
    n = block // chunk
    return (tuple(a[b0:b0 + block].reshape(n, chunk, *a.shape[1:])
                  for a in arrays), b0 + chunk * jnp.arange(n))


# One chunk's scores are computed three times a training step (the
# selection's thresholds, the attention's forward, its backward) and a
# threshold of the first is compared with ``>=`` in the other two: every
# call site takes :func:`chunk_scores`, so that the three are the same
# function of the same bits.


def _on_one_tpu() -> bool:
    # a bare pallas_call has no partitioning rule: under a sharded jit
    # it would force a gather (as ops/lrn.select_lrn_impl)
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def indexer_engages(chunk: int, heads: int, dim: int) -> bool:
    """The indexer's kernels (``ops/dsa_indexer.py``) take chunks of
    queries that fill lane rows and heads of half a lane row or of whole
    ones, side by side in whole lane rows, on one TPU chip; everything
    else is :func:`index_scores`'s."""
    return (_on_one_tpu() and chunk % 128 == 0 and dim % 64 == 0
            and (heads * dim) % 128 == 0)


def chunk_scores(qi, ki, w, t0, dtype):
    """:func:`index_scores` of one chunk of queries, the first at
    position ``t0`` among the keys: through the kernels where
    :func:`indexer_engages` (zeros then stand in the key tiles above the
    chunk's last query, which no caller takes for causal), through the
    XLA form elsewhere. A registry counter says which it was."""
    from deepvision_tpu.obs.metrics import record_indexer_site

    by_kernel = indexer_engages(*qi.shape)
    record_indexer_site(by_kernel)
    if not by_kernel:
        return index_scores(qi, ki, w, dtype)
    return kernel_scores(qi.astype(dtype), ki.astype(dtype), w, t0)


@jax.custom_vjp
def kernel_scores(qi, ki, w, t0):
    """``I [Tq, keys]`` by ``ops/dsa_indexer.py``'s forward kernel; its
    backward is the second kernel, which forms the per-head products
    again and keeps nothing but the inputs."""
    from deepvision_tpu.ops import dsa_indexer

    with jax.named_scope("lm/attn/indexer"):
        return dsa_indexer.forward(qi.reshape(qi.shape[0], -1), ki, w, t0)


def _kernel_scores_pull(qi, ki, w, t0, dscores, dki):
    """-> (``dqi``, ``dw``, float32 ``dki`` with this chunk's part added
    to its first rows in place)."""
    from deepvision_tpu.ops import dsa_indexer

    with jax.named_scope("lm/attn/indexer"):
        dqi, dw, dki = dsa_indexer.backward(
            qi.reshape(qi.shape[0], -1), ki, w, t0, dscores, dki)
    return dqi.reshape(qi.shape), dw.astype(w.dtype), dki


def _kernel_scores_bwd(kept, dscores):
    qi, ki, w, t0 = kept
    dqi, dw, dki = _kernel_scores_pull(
        qi, ki, w, t0, dscores, jnp.zeros(ki.shape, jnp.float32))
    return dqi, dki.astype(ki.dtype), dw, None


def _kernel_scores_fwd(qi, ki, w, t0):
    return kernel_scores(qi, ki, w, t0), (qi, ki, w, t0)


kernel_scores.defvjp(_kernel_scores_fwd, _kernel_scores_bwd)


def _scores_and_pull(qi, ki, w, t0, dtype):
    """:func:`chunk_scores` and its pull ``(dscores, dki) -> (dqi, dw,
    dki)``: ``dki`` float32 ``[>= keys, dim]``, the sum over the chunks
    so far, comes back with this chunk's part added to its first
    rows."""
    if indexer_engages(*qi.shape):
        qi, ki = qi.astype(dtype), ki.astype(dtype)
        return (chunk_scores(qi, ki, w, t0, dtype),
                functools.partial(_kernel_scores_pull, qi, ki, w, t0))
    scores, vjp = jax.vjp(lambda *a: chunk_scores(*a, t0, dtype), qi, ki, w)

    def pull(dscores, dki):
        dqi, dki_c, dw = vjp(dscores)
        return dqi, dw, dki.at[:ki.shape[0]].add(dki_c.astype(jnp.float32))

    return scores, pull


def selection_thresholds(qi, ki, w, *, topk: int, key_block: int,
                         q_chunk: int, dtype):
    """``[T]``: each query's ``topk``-th largest causal score, ``-inf``
    where it has no more than ``topk`` keys (it keeps them all). Query
    ``t`` then selects ``{s <= t : I[t, s] >= threshold[t]}``."""
    t = qi.shape[0]
    block, chunk = _blocks(t, key_block, q_chunk)
    out = []
    for b0 in range(0, t, block):
        end = b0 + block
        if end <= topk:
            out.append(jnp.full((block,), NEG, jnp.float32))
            continue

        def one(args, end=end):
            (qc, wc), t0 = args
            scores = chunk_scores(qc, ki[:end], wc, t0, dtype)
            with jax.named_scope("lm/attn/select"):
                masked = jnp.where(_causal(t0, chunk, end), scores, NEG)
                return kth_largest(masked, topk)

        out.append(_map_chunks(one, (qi, w), b0, block, chunk).reshape(-1))
    return lax.stop_gradient(jnp.concatenate(out))


def _attend(q, k, v, scores, mask, dtype):
    """Softmax attention of ``q [Tq, heads, dim]`` over the keys ``mask
    [Tq, Tk]`` keeps, each key/value head serving ``heads / groups``
    query heads; and the indexer's alignment loss
    ``sum_t KL(p_t || softmax(scores[t, mask]))``, ``p_t`` the detached
    probabilities summed over the heads. -> (``[Tq, heads x dim]``, loss)

    The ``[heads, Tq, Tk]`` tensors are what this costs on the chip
    (HBM traffic, not the matrix unit), so the softmax is shifted by a
    bound known before the logits (``|q_t| max_s |k_s| / sqrt(dim)``,
    Cauchy-Schwarz) instead of by the row's maximum, which would take a
    pass of its own over float32 logits: the exponentials leave the
    first product's fusion already in the compute dtype, the second
    product takes them unnormalised and its output is divided by the
    row's sum. The same function of the logits; the sum and the
    exponent are float32."""
    tq, heads, hd = q.shape
    groups = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope("lm/attn/sparse"):
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)), -1))
        qg = q.reshape(tq, groups, heads // groups, hd)
        bound = (norm(qg).transpose(1, 2, 0)
                 * jnp.max(norm(k), 0)[:, None, None]) * scale   # [G, R, Tq]
        logits = compute_einsum("tgrd,sgd->grts", qg, k, dtype) * scale
        # exp(x - bound) <= 1; -80 keeps a row whose every term would
        # underflow (norm scales far beyond any seen) off 0 / 0
        weights = jnp.where(mask, jnp.exp(jnp.maximum(
            logits - lax.stop_gradient(bound)[..., None], -80.0)), 0.0)
        # materialised once, in the compute dtype: XLA:TPU otherwise
        # fuses the whole softmax into the second product's operand,
        # which then runs 60 x slower (PERF.md, PR 28)
        weights = lax.optimization_barrier(weights.astype(dtype))
        total = jnp.sum(weights.astype(jnp.float32), -1)            # [G, R, Tq]
        out = compute_einsum("grts,sgd->tgrd", weights, v, dtype)
        out = out / total.transpose(2, 0, 1)[..., None]
    with jax.named_scope("lm/index_loss"):
        target = lax.stop_gradient(jnp.sum(
            weights.astype(jnp.float32) / total[..., None], (0, 1))
        ) * (1.0 / heads)
        kl = _alignment_loss(target, scores, mask)
    return out.reshape(tq, heads * hd).astype(dtype), kl


def _alignment_loss(target, scores, keep):
    """``sum_t KL(target_t || softmax(scores[t, keep[t]]))``; ``target``
    is 0 wherever ``keep`` is false."""
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, NEG), -1)
    live = target > 0
    kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                   - jnp.where(keep, log_q, 0.0)), 0.0)
    return jnp.sum(kl)


def sparse_attention(q, k, v, qi, ki, w, thresholds, *, key_block: int,
                     q_chunk: int, dtype, capture: bool = False):
    """One sequence. ``q [T, heads, dim]``, ``k``/``v`` ``[T, groups,
    dim]`` (rotated), the indexer's ``qi [T, iheads, idim]``, ``ki [T,
    idim]``, ``w [T, iheads]``, ``thresholds [T]`` (see
    :func:`selection_thresholds`). -> (``[T, heads x dim]``, alignment
    loss, selected pairs, and with ``capture`` the mask ``[T, T]``)."""
    t = q.shape[0]
    block, chunk = _blocks(t, key_block, q_chunk)
    outs, kl, pairs, masks = [], 0.0, 0, []
    for b0 in range(0, t, block):
        end = b0 + block

        @jax.checkpoint
        def one(args, end=end):
            (qc, qic, wc, thr), t0 = args
            scores = chunk_scores(qic, ki[:end], wc, t0, dtype)
            mask = _causal(t0, chunk, end) & (scores >= thr[:, None])
            o, kl_c = _attend(qc, k[:end], v[:end], scores, mask, dtype)
            return o, kl_c, jnp.sum(mask), (mask if capture else None)

        o, kl_b, n_b, m = _map_chunks(one, (q, qi, w, thresholds), b0,
                                      block, chunk)
        outs.append(o.reshape(block, -1))
        kl, pairs = kl + jnp.sum(kl_b), pairs + jnp.sum(n_b)
        if capture:
            masks.append(jnp.pad(m.reshape(block, end),
                                 ((0, 0), (0, t - end))))
    return (jnp.concatenate(outs), kl, pairs,
            jnp.concatenate(masks) if capture else None)


def gathered_attention(q, k, v, qi, ki, w, *, topk: int, dtype):
    """The same mathematics by a gather over the selected set instead of
    a mask: each query takes the indices of its ``topk`` best causal keys
    and attends over the gathered keys and values. Reads ``topk`` keys a
    query; no model runs it: it is what the tests hold the masked form
    to. -> (``[T, heads x dim]``, alignment loss, selected pairs)"""
    t, heads, hd = q.shape
    groups, k_eff = k.shape[1], min(topk, t)
    scores = jnp.where(_causal(0, t, t), index_scores(qi, ki, w, dtype), NEG)
    picked, idx = lax.top_k(lax.stop_gradient(scores), k_eff)
    live = picked > NEG                                     # [T, k]
    picked = jnp.take_along_axis(scores, idx, -1)
    with jax.named_scope("lm/attn/sparse"):
        kg, vg = k[idx], v[idx]                             # [T, k, G, D]
        qg = q.reshape(t, groups, heads // groups, hd)
        logits = compute_einsum("tgrd,tkgd->tgrk", qg, kg, dtype)
        logits = jnp.where(live[:, None, None, :],
                           logits * (1.0 / math.sqrt(hd)), NEG)
        probs = jax.nn.softmax(logits, -1)
        out = compute_einsum("tgrk,tkgd->tgrd", probs, vg, dtype)
    with jax.named_scope("lm/index_loss"):
        target = lax.stop_gradient(jnp.sum(probs, (1, 2))) * (1.0 / heads)
        kl = _alignment_loss(target, picked, live)
    return out.reshape(t, heads * hd).astype(dtype), kl, jnp.sum(live)


# The same attention through the Pallas kernels of ops/dsa_attention.py,
# which keep the [heads, Tq, Tk] tiles on the chip. Which of the two a
# call site takes is read from the backend and the shapes
# (:func:`kernel_engages`), and two registry counters say which it was.


def kernel_engages(t: int, heads: int, groups: int, head_dim: int,
                   key_block: int, q_chunk: int) -> bool:
    """The kernels take lane-wide heads (128), whole groups of query
    heads and chunks of queries that fill lane rows, on one TPU chip;
    everything else is :func:`sparse_attention`'s."""
    chunk = _blocks(t, key_block, q_chunk)[1]
    return (_on_one_tpu() and head_dim == 128 and heads % groups == 0
            and chunk % 128 == 0)


def _kernel_forward(q, k, v, qi, ki, w, thr, key_block, q_chunk, dtype):
    """:func:`sparse_attention` of every sequence, a chunk of queries a
    kernel call; the mask's count and the alignment loss stay XLA's.
    -> (``[B, T, heads x dim]``, loss ``[B]``, selected pairs ``[B]``,
    log-sum-exp ``[B, chunks, heads, chunk]``)"""
    from deepvision_tpu.ops import dsa_attention as dsa

    def sequence(args):
        q, k, v, qi, ki, w, thr = args
        t = q.shape[0]
        block, chunk = _blocks(t, key_block, q_chunk)
        q, k, v = (a.reshape(t, -1) for a in (q, k, v))
        outs, lses, kl, pairs = [], [], 0.0, 0
        for b0 in range(0, t, block):
            end = b0 + block

            def one(args, end=end):
                (qc, qic, wc, thr_c), t0 = args
                scores = chunk_scores(qic, ki[:end], wc, t0, dtype)
                with jax.named_scope("lm/attn/sparse"):
                    o, lse, target = dsa.forward(qc, k[:end], v[:end],
                                                 scores, thr_c, t0)
                mask = _causal(t0, chunk, end) & (scores >= thr_c[:, None])
                with jax.named_scope("lm/index_loss"):
                    kl_c = _alignment_loss(target, scores, mask)
                return o, lse, kl_c, jnp.sum(mask)

            o, lse, kl_b, n_b = _map_chunks(one, (q, qi, w, thr), b0, block,
                                            chunk)
            outs.append(o.reshape(block, -1))
            lses.append(lse)
            kl, pairs = kl + jnp.sum(kl_b), pairs + jnp.sum(n_b)
        return jnp.concatenate(outs), kl, pairs, jnp.concatenate(lses)

    return lax.map(sequence, (q, k, v, qi, ki, w, thr))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def kernel_attention(q, k, v, qi, ki, w, thr, key_block, q_chunk, dtype):
    """Sparse attention of a batch through the kernels: arguments as
    :func:`sparse_attention`'s with a leading batch axis. -> (``[B, T,
    heads x dim]``, alignment loss ``[B]``, selected pairs ``[B]``).

    The backward is written out (``custom_vjp``): what the forward
    keeps is the output and each row's log-sum-exp, named ``attn_out``
    and ``dsa_lse`` so that a recomputed layer keeps them too and never
    runs the forward kernels twice; chunk by chunk it computes the
    indexer's scores again (their third time in a step), runs the
    backward kernel, which also returns the alignment target, and
    pulls the alignment loss's gradient back through the scores
    (:func:`_scores_and_pull`). ``dk``, ``dv`` and the indexer keys'
    cotangent are summed over the chunks in float32."""
    return _kernel_forward(q, k, v, qi, ki, w, thr, key_block, q_chunk,
                           dtype)[:3]


def _kernel_attention_fwd(q, k, v, qi, ki, w, thr, key_block, q_chunk,
                          dtype):
    o, kl, pairs, lse = _kernel_forward(q, k, v, qi, ki, w, thr, key_block,
                                        q_chunk, dtype)
    o, lse = checkpoint_name(o, "attn_out"), checkpoint_name(lse, "dsa_lse")
    return (o, kl, pairs), (q, k, v, qi, ki, w, thr, o, lse)


def _kernel_attention_bwd(key_block, q_chunk, dtype, kept, cotangents):
    from deepvision_tpu.ops import dsa_attention as dsa

    def sequence(args):
        q, k, v, qi, ki, w, thr, o, lse, do, dkl = args
        t, heads, _hd = q.shape
        block, chunk = _blocks(t, key_block, q_chunk)
        shapes = q.shape, k.shape, v.shape
        q, k, v = (a.reshape(t, -1) for a in (q, k, v))
        f32 = jnp.float32
        dk, dv = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
        dki = jnp.zeros(ki.shape, f32)
        dq, dqi, dw = [], [], []
        for b0 in range(0, t, block):
            end = b0 + block

            def one(carry, args, end=end):
                dk, dv, dki = carry
                (qc, qic, wc, thr_c, o_c, do_c), t0 = args
                scores, pull = _scores_and_pull(qic, ki[:end], wc, t0,
                                                dtype)
                with jax.named_scope("lm/attn/sparse"):
                    di = jnp.sum((o_c.astype(f32) * do_c.astype(f32)).reshape(
                        chunk, heads, -1), -1).T
                    dq_c, dk, dv, target = dsa.backward(
                        qc, k[:end], v[:end], scores, thr_c, t0,
                        lse[t0 // chunk], di, do_c, dk, dv)
                mask = _causal(t0, chunk, end) & (scores >= thr_c[:, None])
                with jax.named_scope("lm/index_loss"):
                    dscores = dkl * jax.grad(_alignment_loss, 1)(
                        target, scores, mask)
                dqi_c, dw_c, dki = pull(dscores, dki)
                return (dk, dv, dki), (dq_c, dqi_c, dw_c)

            (dk, dv, dki), parts = lax.scan(
                one, (dk, dv, dki),
                _chunks_of((q, qi, w, thr, o, do), b0, block, chunk))
            for out, part in zip((dq, dqi, dw), parts):
                out.append(part.reshape(block, *part.shape[2:]))
        dq, dqi, dw = (jnp.concatenate(a) for a in (dq, dqi, dw))
        return (dq.reshape(shapes[0]), dk.astype(k.dtype).reshape(shapes[1]),
                dv.astype(v.dtype).reshape(shapes[2]), dqi,
                dki.astype(ki.dtype), dw)

    q, k, v, qi, ki, w, thr, o, lse = kept
    do, dkl, _pairs = cotangents
    grads = lax.map(sequence, (q, k, v, qi, ki, w, thr, o, lse, do, dkl))
    return (*grads, jnp.zeros_like(thr))


kernel_attention.defvjp(_kernel_attention_fwd, _kernel_attention_bwd)


def selection_mask(qi, ki, w, thresholds, *, key_block: int, q_chunk: int,
                   dtype):
    """``[T, T]``: the pairs :func:`sparse_attention` attends over (what
    its ``capture`` returns), for the path whose kernels form the mask
    a tile at a time and never hold it."""
    t = qi.shape[0]
    block, chunk = _blocks(t, key_block, q_chunk)
    rows = []
    for b0 in range(0, t, block):
        end = b0 + block

        def one(args, end=end):
            (qic, wc, thr), t0 = args
            scores = chunk_scores(qic, ki[:end], wc, t0, dtype)
            return _causal(t0, chunk, end) & (scores >= thr[:, None])

        m = _map_chunks(one, (qi, w, thresholds), b0, block, chunk)
        rows.append(jnp.pad(m.reshape(block, end), ((0, 0), (0, t - end))))
    return jnp.concatenate(rows)


def batched_sparse_attention(q, k, v, qi, ki, w, thr, *, key_block: int,
                             q_chunk: int, dtype, capture: bool = False):
    """:func:`sparse_attention` of a batch, through the kernels where
    :func:`kernel_engages` and through the XLA form elsewhere; either
    way the output is named ``attn_out`` for a recomputed layer to
    keep. -> (``[B, T, heads x dim]``, loss ``[B]``, pairs ``[B]``, and
    with ``capture`` the masks ``[B, T, T]``)"""
    from deepvision_tpu.obs.metrics import record_attention_site

    blocks = dict(key_block=key_block, q_chunk=q_chunk, dtype=dtype)
    by_kernel = kernel_engages(q.shape[1], q.shape[2], k.shape[2],
                               q.shape[3], key_block, q_chunk)
    record_attention_site(by_kernel)
    if not by_kernel:
        o, kl, pairs, mask = lax.map(lambda a: sparse_attention(
            *a, capture=capture, **blocks), (q, k, v, qi, ki, w, thr))
        # kept across the layer's recomputation, like the thresholds:
        # the way back then recomputes each chunk once, not twice
        return checkpoint_name(o, "attn_out"), kl, pairs, mask
    o, kl, pairs = kernel_attention(q, k, v, qi, ki, w, thr, key_block,
                                    q_chunk, dtype)
    mask = lax.map(lambda a: selection_mask(*a, **blocks),
                   (qi, ki, w, thr)) if capture else None
    return o, kl, pairs, mask


# ------------------------------------------------------ mixture of experts


def route(h, router, *, experts_per_token: int, norm_topk: bool,
          scoring: str = "softmax", bias=None, gate_scale: float = 1.0):
    """-> (chosen experts ``[N, k]``, their gates). Float32 throughout.

    ``scoring`` is the rule that turns the router's logits into scores:
    ``"softmax"`` over the experts, or ``"sigmoid"`` of each
    (DeepSeek-V3's, whose renormalisation adds 1e-20 to the sum). With a
    ``bias [experts]`` the ``k`` largest of ``score + bias`` are chosen
    and the gates are the chosen experts' scores: the bias moves the
    choice and never the gate, so no gradient reaches it. ``gate_scale``
    multiplies the gates."""
    logits = float32_dot(h, router)
    if scoring == "softmax":
        scores, eps = jax.nn.softmax(logits, -1), None
    elif scoring == "sigmoid":
        scores, eps = jax.nn.sigmoid(logits), 1e-20
    else:
        raise ValueError(f"unknown scoring rule {scoring!r}")
    if bias is None:
        gates, experts = lax.top_k(scores, experts_per_token)
    else:
        _, experts = lax.top_k(scores + bias.astype(jnp.float32),
                               experts_per_token)
        gates = jnp.take_along_axis(scores, experts, -1)
    if norm_topk:
        total = jnp.sum(gates, -1, keepdims=True)
        gates = gates / (total if eps is None else total + eps)
    if gate_scale != 1.0:
        gates = gates * gate_scale
    return experts, gates


# Rows of one window of an expert layer's grouped products, as a
# multiple of the expected number of local assignments.
WINDOW_FACTOR = 2.0


def moe_layer(h, router, gate_w, up_w, down_w, *, experts_per_token: int,
              norm_topk: bool, expert_share: tuple, dtype,
              capacity_factor: float = WINDOW_FACTOR,
              scoring: str = "softmax", bias=None, gate_scale: float = 1.0):
    """The part of the layer's result that this chip's experts give.

    ``h [N, hidden]``; ``router [hidden, all experts]``; ``gate_w``,
    ``up_w [held, hidden, width]`` and ``down_w [held, width, hidden]``
    are experts ``index * held .. (index + 1) * held`` of
    ``expert_share = (index, of)``. Routing is over all experts, by
    :func:`route`'s rule (``scoring``, ``bias``, ``gate_scale``); a
    token's choices that fall on absent experts add nothing here. The
    choices that fall on held experts are sorted by expert and go
    through three grouped products (``lax.ragged_dot``), in windows of
    ``capacity_factor`` times the expected number of rows: the first
    holds the usual load, the others, up to the worst case (every choice
    local), run only when the assignments reach them, so no token is
    dropped whatever the imbalance and the usual step pays for one. A
    window computes all of its rows, as a fixed-capacity expert layer
    does (the rows past the last assignment ride with the last expert at
    gate 0), so below the window's size a step costs the same whatever
    the routing.
    -> (``[N, hidden]``, chosen experts ``[N, k]``, dropped assignments)"""
    n, k = h.shape[0], experts_per_token
    held, every = gate_w.shape[0], router.shape[1]
    lo = expert_share[0] * held
    with jax.named_scope("lm/moe/route"):
        experts, gates = route(h, router, experts_per_token=k,
                               norm_topk=norm_topk, scoring=scoring,
                               bias=bias, gate_scale=gate_scale)
        local = (experts >= lo) & (experts < lo + held)
        # absent experts sort behind the held ones
        key = jnp.where(local, experts - lo, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        token = (order // k).astype(jnp.int32)
        gate = gates.reshape(-1)[order]
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                        0)[:held]
        n_local = jnp.sum(sizes)

    worst = n * min(k, held)
    rows = min(worst, _round_up(
        int(capacity_factor * n * k * held / every), 8))
    windows = -(-worst // rows)
    pad = windows * rows - token.shape[0]
    if pad > 0:
        token, gate = jnp.pad(token, (0, pad)), jnp.pad(gate, (0, pad))
    starts = jnp.cumsum(sizes) - sizes

    @jax.checkpoint
    def experts_on(lo):
        """The sorted assignments ``lo .. lo + rows``."""
        tok = lax.dynamic_slice(token, (lo,), (rows,))
        g = lax.dynamic_slice(gate, (lo,), (rows,))
        # what of each expert's group lies in this window
        in_window = (jnp.clip(starts + sizes, lo, lo + rows)
                     - jnp.clip(starts, lo, lo + rows))
        # the rows past the last assignment (other chips' choices, the
        # padding) go to the last expert at gate 0: no row is left to no
        # group, where what a grouped product writes is unspecified
        # (zeros on the CPU, whatever the buffer held on the chip)
        in_window = in_window.at[-1].add(rows - jnp.sum(in_window))
        g = jnp.where(lo + jnp.arange(rows) < n_local, g, 0.0)

        def grouped(a, weights):
            return lax.ragged_dot(a, weights.astype(dtype), in_window,
                                  preferred_element_type=jnp.float32)

        x = h[tok].astype(dtype)
        mid = (jax.nn.silu(grouped(x, gate_w))
               * grouped(x, up_w)).astype(dtype)
        y = grouped(mid, down_w) * g[:, None]
        return jnp.zeros((n, h.shape[1]), jnp.float32).at[tok].add(y)

    with jax.named_scope("lm/moe/experts"):
        # the first window holds the usual load; the others run only
        # when the assignments reach them
        out = experts_on(0)
        for i in range(1, windows):
            out = out + lax.cond(
                i * rows < n_local, experts_on,
                lambda lo: jnp.zeros((n, h.shape[1]), jnp.float32),
                i * rows)
    dropped = jnp.maximum(n_local - windows * rows, 0)
    return out.astype(dtype), experts, dropped


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


# ------------------------------------------------------------------- head


def token_nll(hidden, head, targets, dtype):
    """``-log softmax(hidden @ head)[target]`` of every position, one
    sequence after another with the logits recomputed on the way back:
    ``[B, T, hidden]``, ``[hidden, vocab]``, ``[B, T]`` -> ``[B, T]``."""

    @jax.checkpoint
    def one(args):
        h, y = args
        logits = compute_dot(h, head, dtype)
        return (jax.nn.logsumexp(logits, -1)
                - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])

    with jax.named_scope("lm/head"):
        return lax.map(one, (hidden, targets))


# ------------------------------------------------------------------ tower


class VisionLayer(nn.Module):
    """Pre-LayerNorm ViT block: biased q/k/v/o, tanh-GELU MLP. A scan's
    body: -> (``x``, nothing to stack)."""

    heads: int
    mlp: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        dh = d // self.heads
        h = LayerNorm(name="norm1")(x)
        attn = _AttnProj(d, self.dtype, name="attn")
        q, k, v = (t.reshape(b, n, self.heads, dh) for t in attn.qkv(h))
        logits = compute_einsum("bqhd,bkhd->bhqk", q, k, self.dtype)
        probs = jax.nn.softmax(logits * (1.0 / math.sqrt(dh)), -1)
        o = compute_einsum("bhqk,bkhd->bqhd", probs, v, self.dtype)
        x = x + attn.out(o.reshape(b, n, d).astype(self.dtype))
        h = LayerNorm(name="norm2")(x)
        return x + _Mlp(self.mlp, d, self.dtype, name="mlp")(h), None


class _AttnProj(nn.Module):
    width: int
    dtype: Dtype

    def setup(self):
        self.q = Linear(self.width, self.dtype)
        self.k = Linear(self.width, self.dtype)
        self.v = Linear(self.width, self.dtype)
        self.o = Linear(self.width, self.dtype)

    def qkv(self, h):
        return self.q(h), self.k(h), self.v(h)

    def out(self, o):
        return self.o(o)


class _Mlp(nn.Module):
    hidden: int
    out: int
    dtype: Dtype

    @nn.compact
    def __call__(self, x):
        x = Linear(self.hidden, self.dtype, name="fc1")(x)
        return Linear(self.out, self.dtype, name="fc2")(
            jax.nn.gelu(x, approximate=True))


def _stacked(layer, length: int):
    """``length`` layers of one kind as one scanned body: their
    parameters are stacked on a leading axis under one name, the device
    program holds the layer once, and what a layer returns beside the
    carry is stacked the same way."""
    return nn.scan(layer, variable_axes={"params": 0},
                   split_rngs={"params": True}, in_axes=nn.broadcast,
                   length=length)


class VisionTower(nn.Module):
    """Images ``[B, S, S, 3]`` -> ``[B, (S / patch)^2, hidden]``."""

    hidden: int
    mlp: int
    heads: int
    layers: int
    patch: int
    remat: bool = True
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        b, s = images.shape[0], images.shape[1]
        g, p = s // self.patch, self.patch
        x = images.astype(self.dtype).reshape(b, g, p, g, p, 3)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, g * g, p * p * 3)
        x = Linear(self.hidden, self.dtype, name="patch_embed")(x)
        pos = self.param("pos_embed", normal, (g * g, self.hidden))
        x = x + pos.astype(self.dtype)
        block = nn.remat(VisionLayer, prevent_cse=False) if self.remat \
            else VisionLayer
        x, _ = _stacked(block, self.layers)(
            self.heads, self.mlp, self.dtype, name="layers")(x)
        return LayerNorm(name="post_norm")(x)


class Projector(nn.Module):
    """2 x 2 merge of the tower's grid, LayerNorm, Linear, GELU, Linear."""

    out: int
    merge: int = 2
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        g = math.isqrt(n)
        m, s = g // self.merge, self.merge
        x = x.reshape(b, m, s, m, s, d).transpose(0, 1, 3, 2, 4, 5)
        x = LayerNorm(name="norm")(x.reshape(b, m * m, s * s * d))
        x = Linear(self.out, self.dtype, name="fc1")(x)
        return Linear(self.out, self.dtype, name="fc2")(
            jax.nn.gelu(x, approximate=True))


# ---------------------------------------------------------------- decoder


@dataclasses.dataclass(frozen=True)
class LayerConfig:
    """What a decoder layer is built from (hashable: it rides module
    fields and the jit cache key)."""

    heads: int
    kv_heads: int
    head_dim: int
    indexer_heads: int
    indexer_dim: int
    topk: int
    num_experts: int
    experts_per_token: int
    expert_share: tuple
    moe_width: int
    norm_topk: bool
    rms_eps: float
    rope_theta: float
    key_block: int
    q_chunk: int
    capture: bool = False
    dtype: Dtype = jnp.bfloat16


class DecoderLayer(nn.Module):
    cfg: LayerConfig

    @nn.compact
    def __call__(self, x, angles):
        c = self.cfg
        b, t, d = x.shape
        h = RMSNorm(c.rms_eps, name="attn_norm")(x)
        a, stats = _Attention(c, name="attn")(
            h, angles, _Indexer(c, name="indexer")(h))
        x = x + a
        h = RMSNorm(c.rms_eps, name="moe_norm")(x)
        y, experts, dropped = _Moe(c, name="moe")(h.reshape(b * t, d))
        experts = experts.reshape(b, t, -1)
        held = c.num_experts // c.expert_share[1]
        lo = c.expert_share[0] * held
        counts = jnp.sum(jax.nn.one_hot(experts - lo, held, dtype=jnp.int32),
                         (1, 2))
        stats.update(expert_tokens=counts,
                     # no sample owns a dropped assignment: every sample
                     # carries the layer's count
                     moe_dropped=jnp.broadcast_to(dropped, (b,)))
        if c.capture:
            stats["experts"] = experts
        return x + y.reshape(b, t, d).astype(c.dtype), stats


class _Indexer(nn.Module):
    """``qI``, ``kI`` and ``w`` of the lightning indexer, from the
    detached input: only the alignment loss trains these."""

    cfg: LayerConfig

    @nn.compact
    def __call__(self, h):
        c, dt = self.cfg, self.cfg.dtype
        b, t, d = h.shape
        hi, di = c.indexer_heads, c.indexer_dim
        wq = self.param("wq", normal, (d, hi * di))
        wk = self.param("wk", normal, (d, di))
        ww = self.param("ww", normal, (d, hi))
        h = lax.stop_gradient(h)
        with jax.named_scope("lm/attn/indexer"):
            angles = rope_angles(t, di // 4, c.rope_theta)
            qi = compute_dot(h, wq, dt).astype(dt).reshape(b, t, hi, di)
            ki = LayerNorm(name="k_norm")(compute_dot(h, wk, dt).astype(dt))
            qi = rotate(qi, angles)
            ki = rotate(ki[:, :, None, :], angles)[:, :, 0]
            w = compute_dot(h, ww, dt) * (di ** -0.5 * hi ** -0.5)
        return qi, ki, w


class _Attention(nn.Module):
    cfg: LayerConfig

    @nn.compact
    def __call__(self, h, angles, indexer):
        c, dt = self.cfg, self.cfg.dtype
        b, t, d = h.shape
        heads, kvh, hd = c.heads, c.kv_heads, c.head_dim
        kernels = {n: self.param(n, normal, (d, w * hd)) for n, w in
                   (("q", heads), ("k", kvh), ("v", kvh))}
        wo = self.param("o", normal, (heads * hd, d))
        with jax.named_scope("lm/attn/proj"):
            proj = lambda n, w: compute_dot(h, kernels[n], dt).astype(
                dt).reshape(b, t, w, hd)
            q = RMSNorm(c.rms_eps, name="q_norm")(proj("q", heads))
            k = RMSNorm(c.rms_eps, name="k_norm")(proj("k", kvh))
            v = proj("v", kvh)
            q, k = rotate(q, angles), rotate(k, angles)
        qi, ki, w = indexer
        blocks = dict(key_block=c.key_block, q_chunk=c.q_chunk, dtype=dt)
        # the selection runs once, ahead of the chunks, and survives the
        # layer's recomputation (a [B, T] array)
        thr = lax.map(lambda a: selection_thresholds(
            *a, topk=c.topk, **blocks), (qi, ki, w))
        thr = checkpoint_name(thr, "dsa_threshold")
        o, kl, pairs, mask = batched_sparse_attention(
            q, k, v, qi, ki, w, thr, capture=c.capture, **blocks)
        with jax.named_scope("lm/attn/proj"):
            out = compute_dot(o, wo, dt).astype(dt)
        stats = {"index_kl": kl, "selected_pairs": pairs}
        if c.capture:
            stats["mask"] = mask
        return out, stats


class _Moe(nn.Module):
    cfg: LayerConfig

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        d, held = h.shape[-1], c.num_experts // c.expert_share[1]
        router = self.param("router", normal, (d, c.num_experts))
        gate = self.param("gate", normal, (held, d, c.moe_width))
        up = self.param("up", normal, (held, d, c.moe_width))
        down = self.param("down", normal, (held, c.moe_width, d))
        return moe_layer(
            h, router, gate, up, down,
            experts_per_token=c.experts_per_token, norm_topk=c.norm_topk,
            expert_share=tuple(c.expert_share), dtype=c.dtype)


class KeyeVL2(nn.Module):
    """``{"image": [B, S, S, 3], "tokens": [B, L]}`` -> per-sample
    results of the next-token task on the text: ``nll [B, L]`` (the
    image's last token predicts the first text token, the last text
    token predicts nothing), ``index_kl [B]`` (the indexer's alignment
    loss summed over layers and positions), ``selected_pairs [B]``,
    ``expert_tokens [B, layers, held experts]`` and ``moe_dropped [B]``.
    ``logits=True`` adds ``logits [B, L, vocab]`` (short sequences);
    ``capture`` adds each layer's selection mask and routing choice.

    ``vocab_size`` and the experts held (``num_experts`` over
    ``expert_share[1]``) may be one chip's share of a deployment; the
    router keeps its ``num_experts`` outputs."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)
    rms_eps: float = 1e-6
    num_experts: int = 128
    experts_per_token: int = 8
    expert_share: tuple = (0, 1)
    moe_width: int = 768
    norm_topk: bool = True
    indexer_heads: int = 16
    indexer_dim: int = 64
    topk: int = 2048
    image_size: int = 448
    patch_size: int = 14
    merge: int = 2
    vision_hidden: int = 1152
    vision_mlp: int = 4304
    vision_heads: int = 16
    vision_layers: int = 27
    sample_text_len: int = 12
    key_block: int = 2048
    q_chunk: int = 512
    remat: str | None = "layer"
    capture: bool = False
    dtype: Dtype = jnp.bfloat16

    def sample_input(self) -> dict:
        """What ``init`` traces: parameter shapes do not depend on the
        text's length."""
        s = self.image_size
        return {"image": np.zeros((1, s, s, 3), np.float32),
                "tokens": np.zeros((1, self.sample_text_len), np.int32)}

    @nn.compact
    def __call__(self, inputs, train: bool = False, logits: bool = False):
        del train                                # no dropout, no statistics
        dt = self.dtype
        images, tokens = inputs["image"], inputs["tokens"]
        with jax.named_scope("vlm/vision"):
            x = VisionTower(self.vision_hidden, self.vision_mlp,
                            self.vision_heads, self.vision_layers,
                            self.patch_size, self.remat is not None, dt,
                            name="vision")(images)
        with jax.named_scope("vlm/projector"):
            img = Projector(self.hidden_size, self.merge, dt,
                            name="projector")(x)
        n_img, text_len = img.shape[1], tokens.shape[1]
        embed = self.param("embed", nn.initializers.normal(0.1),
                           (self.vocab_size, self.hidden_size))
        x = jnp.concatenate([img, embed[tokens].astype(dt)], 1)
        angles = mrope_angles(
            mrope_positions(math.isqrt(n_img), text_len), self.head_dim,
            self.rope_theta, self.mrope_section)

        layer = DecoderLayer
        if self.remat is not None:
            layer = nn.remat(
                DecoderLayer, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "dsa_threshold", "attn_out", "dsa_lse"))
        cfg = LayerConfig(
            self.heads, self.kv_heads, self.head_dim, self.indexer_heads,
            self.indexer_dim, self.topk, self.num_experts,
            self.experts_per_token, tuple(self.expert_share),
            self.moe_width, self.norm_topk, self.rms_eps, self.rope_theta,
            self.key_block, self.q_chunk, self.capture, dt)
        # every layer's statistic, stacked [layers, B, ...]
        x, stats = _stacked(layer, self.num_layers)(cfg, name="layers")(
            x, angles)
        per_sample = lambda a: jnp.moveaxis(a, 0, 1)
        x = RMSNorm(self.rms_eps, name="final_norm")(x)
        head = self.param("lm_head", normal,
                          (self.hidden_size, self.vocab_size))
        hidden = x[:, n_img - 1: n_img - 1 + text_len]
        out = {
            "nll": token_nll(hidden, head, tokens, dt),
            "index_kl": jnp.sum(stats["index_kl"], 0),
            "selected_pairs": jnp.sum(stats["selected_pairs"], 0),
            "expert_tokens": per_sample(stats["expert_tokens"]),
            "moe_dropped": jnp.sum(stats["moe_dropped"], 0),
        }
        if logits:
            out["logits"] = compute_dot(hidden, head, dt)
        if self.capture:
            out["experts"] = per_sample(stats["experts"])
            out["masks"] = per_sample(stats["mask"])
        return out


# --------------------------------------------------------------- registry

# One chip's share of the 8-chip expert-parallel deployment the benchmark
# measures (benchmark/configs/keye_vl2_30b_a3b.json): every width as
# published; depth, experts held and vocabulary rows are the chip's.
_SHARE_OF_8 = dict(num_layers=5, vision_layers=6, vocab_size=18992,
                   expert_share=(0, 8))
# CPU-sized preset for tests and ``train.py -m keye_vl2_tiny``.
_TINY = dict(
    vocab_size=128, hidden_size=64, num_layers=2, heads=4, kv_heads=2,
    head_dim=16, mrope_section=(2, 3, 3), num_experts=8,
    experts_per_token=2, expert_share=(0, 2), moe_width=32,
    indexer_heads=4, indexer_dim=16, topk=16, image_size=16, patch_size=4,
    vision_hidden=32, vision_mlp=64, vision_heads=2, vision_layers=2,
    key_block=32, q_chunk=8)


def _factory(defaults: dict):
    def make(dtype=jnp.bfloat16, **kwargs):
        kw = {**defaults, **kwargs}
        for name in ("expert_share", "mrope_section"):
            if name in kw:
                kw[name] = tuple(kw[name])
        return KeyeVL2(dtype=dtype, **kw)
    return make


register("keye_vl2", remat="layer")(_factory({}))
register("keye_vl2_ep8", remat="layer")(_factory(_SHARE_OF_8))
register("keye_vl2_tiny", remat="layer")(_factory(_TINY))
