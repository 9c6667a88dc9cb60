"""Pallas TPU kernels: blocked attention of one chunk of queries whose
``[heads, queries, keys]`` tiles never leave the chip, in two forms that
share tiling, the bound-shifted softmax and the transposed-tile
backward: masked grouped-query attention behind an indexer's selection
(this page: ``forward``, ``backward``; ``models/transformer.py``'s) and
plain causal latent attention (``latent_forward``, ``latent_backward``,
at the end of the module, where what differs is said;
``models/latent_moe.py``'s). Which form a model's call site takes is
read from the operands it has (a threshold and scores, or a rotary key
of its own), all of it while the program is traced.

The token model's sparse attention (``models/transformer.py``) keeps,
for query ``t``, the causal keys whose indexer score reaches the
query's threshold. The XLA form (``transformer._attend``) writes
logits, exponentials and their gradients as ``[heads, Tq, Tk]`` tensors
to HBM and is bound by that traffic (PERF.md, PR 28). Here a chunk of
``Tq`` queries meets its keys tile by tile: the mask is formed in the
kernel from the ``[Tq, Tk]`` float32 scores and the ``[Tq]`` thresholds
(``s <= t`` and ``score >= threshold``, ties kept), one key/value head
serves its ``heads / groups`` query heads, and what goes back to HBM is
``[Tq, .]``-sized.

Three kernels, one chunk a call:

- ``dsa_attention_forward``: output and each row's log-sum-exp. The
  softmax is shifted by a bound known before the logits (``|q_t| max_s
  |k_s| / sqrt(dim)``, as the XLA form's), so no running maximum is
  kept and nothing is rescaled; ``-80`` keeps a row whose every term
  would underflow off ``0 / 0``. A key tile of a row may be empty: its
  terms are exact zeros.
- ``dsa_attention_target``: the indexer's alignment target
  ``(1 / heads) sum_h softmax_h`` over the kept keys, a second sweep
  over the key tiles (the rows' normalisers have to be whole first).
- ``dsa_attention_backward``: the usual blocked form from the output's
  cotangent, the log-sum-exp and ``di = sum(o * do)``: probabilities
  recomputed a tile at a time, ``dq``, ``dk``, ``dv`` accumulated in
  float32. It works on transposed tiles (``[keys, queries]``), where a
  query's statistics are lane rows and four of its five products need
  no transpose. The probabilities are there anyway, so it also returns
  the alignment target again, for the alignment loss's own backward.

Numerics, both forms: operands of both products in the inputs' dtype,
float32 accumulation; logits, exponent, row sums, division, target
float32; the exponentials go to the second product unnormalised, cast
to the inputs' dtype; no running maximum, nothing rescaled. Key tiles
strictly above the diagonal are skipped. Layouts are the model's (``[T,
heads x dim]``): no transpose outside the kernels.

``interpret=True`` runs the Pallas interpreter (CPU tests); left to the
default it is chosen by the backend.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
KEY_TILE = 512
VMEM_LIMIT = 100 * 1024 * 1024      # of a v5e core's 128 MiB
_NEG = -1e30                        # exp() of it is an exact zero
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_SCALE = 1.0 / math.sqrt(LANES)     # of the logits: heads are lane-wide


def key_tile(keys: int) -> int:
    """Keys a grid step: 512 where they divide the keys (v5e, PR 30:
    256 read the three kernels 7, 23 and 2% slower, 1024 does not fit
    the scoped VMEM once XLA fuses the operands' slices into the call),
    else a lane row."""
    return KEY_TILE if keys % KEY_TILE == 0 else LANES


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _tile_is_live(t0, kk, tq: int, tk: int):
    """The key tile holds a key at or below the chunk's last query."""
    return kk * tk <= t0 + tq - 1


def _kept(t0, kk, scores_ref, thr_ref):
    """``[tq, tk]``: causal and selected, ties at the threshold kept."""
    tq, tk = scores_ref.shape
    row = t0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    col = kk * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return (col <= row) & (scores_ref[...] >= thr_ref[...])


def _sizes(q, k) -> tuple:
    """-> (queries, keys, groups, query heads a group, key tile)."""
    groups = k.shape[1] // LANES
    return (q.shape[0], k.shape[0], groups, q.shape[1] // k.shape[1],
            key_tile(k.shape[0]))


def _rows_to_columns(row, tq: int):
    """``[1, tq]`` -> ``[tq, LANES]``, every lane the row's value."""
    return jnp.broadcast_to(row, (LANES, tq)).T


def _lane_tiles(x, n: int):
    return x if n == 1 else jnp.tile(x, (1, n))


def _accumulate(r: int, p, v_t, l_ref, acc_ref):
    """Head ``r`` of the step: the tile's unnormalised exponentials
    ``p [tq, tk]`` into the row sums and the output's accumulator."""
    # row sums stay lane-wise partial sums until the last tile
    l_ref[r] += sum(p[:, j * LANES:(j + 1) * LANES]
                    for j in range(p.shape[1] // LANES))
    acc_ref[r] += jnp.dot(p.astype(v_t.dtype), v_t,
                          preferred_element_type=jnp.float32)


def _finish(r: int, o_ref, lse_ref, acc_ref, l_ref, bound_ref):
    """Head ``r``'s output, divided by its rows' sums, and their
    log-sum-exp as a lane row."""
    tq, hd = acc_ref.shape[1:]
    total = jnp.sum(l_ref[r], -1, keepdims=True)                # [tq, 1]
    o_ref[:, r * hd:(r + 1) * hd] = (acc_ref[r] / total).astype(o_ref.dtype)
    lse = bound_ref[r] + jnp.log(jnp.broadcast_to(total, (tq, LANES)))
    lse_ref[r:r + 1, :] = lse.T[:1, :]


# ---------------------------------------------------------------- forward


def _forward_kernel(t0_ref, q_ref, k_ref, v_ref, scores_ref, thr_ref,
                    kmax_ref, o_ref, lse_ref, acc_ref, l_ref, bound_ref, *,
                    reps: int):
    tq, tk = scores_ref.shape
    hd = k_ref.shape[1]
    kk, last = pl.program_id(1), pl.num_programs(1) - 1
    t0 = t0_ref[0]

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        for r in range(reps):
            qf = q_ref[:, r * hd:(r + 1) * hd].astype(jnp.float32)
            norm = jnp.sqrt(jnp.sum(qf * qf, -1, keepdims=True))
            bound_ref[r] = (jnp.broadcast_to(norm, (tq, LANES))
                            * kmax_ref[...] * _SCALE)

    @pl.when(_tile_is_live(t0, kk, tq, tk))
    def _():
        keep = _kept(t0, kk, scores_ref, thr_ref)
        k_t, v_t = k_ref[...], v_ref[...]
        for r in range(reps):
            s = lax.dot_general(q_ref[:, r * hd:(r + 1) * hd], k_t, _NT,
                                preferred_element_type=jnp.float32)
            z = s * _SCALE - _lane_tiles(bound_ref[r], tk // LANES)
            p = jnp.where(keep, jnp.exp(jnp.maximum(z, -80.0)), 0.0)
            _accumulate(r, p, v_t, l_ref, acc_ref)

    @pl.when(kk == last)
    def _():
        for r in range(reps):
            _finish(r, o_ref, lse_ref, acc_ref, l_ref, bound_ref)


def _forward_call(q, k, v, scores, thr, t0, interpret):
    tq, keys, groups, reps, tk = _sizes(q, k)
    norm = jnp.sqrt(jnp.sum(jnp.square(k.astype(jnp.float32).reshape(
        keys, groups, LANES)), -1))
    kmax = jnp.repeat(jnp.max(norm, 0), LANES)[None]        # [1, G x 128]
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(groups, keys // tk),
        in_specs=[
            pl.BlockSpec((tq, reps * LANES), lambda g, kk, t0: (0, g)),
            pl.BlockSpec((tk, LANES), lambda g, kk, t0: (kk, g)),
            pl.BlockSpec((tk, LANES), lambda g, kk, t0: (kk, g)),
            pl.BlockSpec((tq, tk), lambda g, kk, t0: (0, kk)),
            pl.BlockSpec((tq, 1), lambda g, kk, t0: (0, 0)),
            pl.BlockSpec((1, LANES), lambda g, kk, t0: (0, g)),
        ],
        out_specs=[
            pl.BlockSpec((tq, reps * LANES), lambda g, kk, t0: (0, g)),
            pl.BlockSpec((reps, tq), lambda g, kk, t0: (g, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((reps, tq, LANES), jnp.float32),
                        pltpu.VMEM((reps, tq, LANES), jnp.float32),
                        pltpu.VMEM((reps, tq, LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_forward_kernel, reps=reps),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((groups * reps, tq), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        name="dsa_attention_forward", interpret=interpret,
    )(t0, q, k, v, scores, thr[:, None], kmax)


# ----------------------------------------------------------------- target


def _target_kernel(t0_ref, q_ref, k_ref, scores_ref, thr_ref, lse_ref,
                   target_ref, lse_cols_ref, *, reps: int):
    tq, tk = scores_ref.shape
    hd = k_ref.shape[1]
    kk, g = pl.program_id(0), pl.program_id(1)
    heads = reps * pl.num_programs(1)
    t0 = t0_ref[0]

    @pl.when(kk == 0)
    def _():
        for r in range(reps):
            lse_cols_ref[g * reps + r] = _rows_to_columns(
                lse_ref[r:r + 1, :], tq)

    @pl.when(g == 0)
    def _():
        target_ref[...] = jnp.zeros_like(target_ref)

    @pl.when(_tile_is_live(t0, kk, tq, tk))
    def _():
        keep = _kept(t0, kk, scores_ref, thr_ref)
        k_t = k_ref[...]
        total = jnp.zeros((tq, tk), jnp.float32)
        for r in range(reps):
            s = lax.dot_general(q_ref[:, r * hd:(r + 1) * hd], k_t, _NT,
                                preferred_element_type=jnp.float32)
            z = s * _SCALE - _lane_tiles(lse_cols_ref[g * reps + r],
                                        tk // LANES)
            total += jnp.exp(jnp.where(keep, z, _NEG))
        target_ref[...] += total * (1.0 / heads)


def _target_call(q, k, scores, thr, lse, t0, interpret):
    tq, keys, groups, reps, tk = _sizes(q, k)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(keys // tk, groups),
        in_specs=[
            pl.BlockSpec((tq, reps * LANES), lambda kk, g, t0: (0, g)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
            pl.BlockSpec((tq, tk), lambda kk, g, t0: (0, kk)),
            pl.BlockSpec((tq, 1), lambda kk, g, t0: (0, 0)),
            pl.BlockSpec((reps, tq), lambda kk, g, t0: (g, 0)),
        ],
        out_specs=pl.BlockSpec((tq, tk), lambda kk, g, t0: (0, kk)),
        scratch_shapes=[pltpu.VMEM((groups * reps, tq, LANES),
                                   jnp.float32)])
    return pl.pallas_call(
        functools.partial(_target_kernel, reps=reps),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((tq, keys), jnp.float32),
        compiler_params=_params("arbitrary", "arbitrary"),
        name="dsa_attention_target", interpret=interpret,
    )(t0, q, k, scores, thr[:, None], lse)


def forward(q, k, v, scores, thr, t0, *, interpret: bool | None = None):
    """One chunk. ``q [Tq, heads x 128]``, ``k``/``v`` ``[Tk, groups x
    128]``, ``scores [Tq, Tk]`` float32, ``thr [Tq]``, ``t0`` the first
    query's position among the keys. -> (output ``[Tq, heads x 128]`` in
    ``q``'s dtype, log-sum-exp ``[heads, Tq]``, alignment target ``[Tq,
    Tk]``, both float32)."""
    interpret = _interpret() if interpret is None else interpret
    t0 = jnp.asarray(t0, jnp.int32).reshape(1)
    o, lse = _forward_call(q, k, v, scores, thr, t0, interpret)
    target = _target_call(q, k, scores, thr, lse, t0, interpret)
    return o, lse, target


# --------------------------------------------------------------- backward


def _backward_kernel(t0_ref, q_ref, do_ref, k_ref, v_ref, scores_ref,
                     thr_ref, lse_ref, di_ref, dk_in_ref, dv_in_ref, dq_ref,
                     dk_ref, dv_ref, target_ref, dq_acc_ref, target_acc_ref,
                     *, reps: int):
    tq, tk = scores_ref.shape
    hd = k_ref.shape[1]
    kk, g = pl.program_id(0), pl.program_id(1)
    groups = pl.num_programs(1)
    t0 = t0_ref[0]
    live = _tile_is_live(t0, kk, tq, tk)

    @pl.when(kk == 0)
    def _():
        dq_acc_ref[g] = jnp.zeros(dq_acc_ref.shape[1:], jnp.float32)

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[...] = dk_in_ref[...]
        dv_ref[...] = dv_in_ref[...]
        target_ref[...] = jnp.zeros_like(target_ref)

    @pl.when(live)
    def _():
        key = kk * tk + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
        query = t0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
        keep = (key <= query) & (scores_ref[...].T >= thr_ref[...])
        k_t, v_t = k_ref[...], v_ref[...]
        dk = jnp.zeros((tk, hd), jnp.float32)
        dv = dv_in_ref[...]
        total = jnp.zeros((tk, tq), jnp.float32)
        for r in range(reps):
            q_r = q_ref[:, r * hd:(r + 1) * hd]
            do_r = do_ref[:, r * hd:(r + 1) * hd]
            s = lax.dot_general(k_t, q_r, _NT,
                                preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(keep, s * _SCALE - lse_ref[r:r + 1, :],
                                  _NEG))                        # [tk, tq]
            total += p
            dv += jnp.dot(p.astype(do_r.dtype), do_r,
                          preferred_element_type=jnp.float32)
            dp = lax.dot_general(v_t, do_r, _NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - di_ref[r:r + 1, :])
            dk += jnp.dot(ds.astype(q_r.dtype), q_r,
                          preferred_element_type=jnp.float32)
            dq_acc_ref[g, :, r * hd:(r + 1) * hd] += jnp.dot(
                ds.T.astype(k_t.dtype), k_t,
                preferred_element_type=jnp.float32)
        dk_ref[...] = dk_in_ref[...] + dk * _SCALE
        dv_ref[...] = dv

        @pl.when(g == 0)
        def _():
            target_acc_ref[...] = total

        @pl.when(g > 0)
        def _():
            target_acc_ref[...] += total

        @pl.when(g == groups - 1)
        def _():
            target_ref[...] = target_acc_ref[...].T * (
                1.0 / (reps * groups))

    @pl.when((kk == pl.num_programs(0) - 1) & (g == groups - 1))
    def _():
        for g2 in range(dq_acc_ref.shape[0]):
            dq_ref[:, g2 * reps * hd:(g2 + 1) * reps * hd] = (
                dq_acc_ref[g2] * _SCALE).astype(dq_ref.dtype)


def backward(q, k, v, scores, thr, t0, lse, di, do, dk, dv, *,
             interpret: bool | None = None):
    """The chunk's cotangents from its output's (``do``, as ``q``), the
    forward's ``lse`` and ``di [heads, Tq] = sum(o * do)`` per head.
    ``dk``, ``dv`` (float32, ``[>= Tk, groups x 128]``) are the sums
    over the chunks so far; this chunk's part is added to their first
    ``Tk`` rows in place. -> (``dq`` in ``q``'s dtype, ``dk``, ``dv``,
    the alignment target ``[Tq, Tk]``)."""
    interpret = _interpret() if interpret is None else interpret
    tq, keys, groups, reps, tk = _sizes(q, k)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(keys // tk, groups),
        in_specs=[
            pl.BlockSpec((tq, reps * LANES), lambda kk, g, t0: (0, g)),
            pl.BlockSpec((tq, reps * LANES), lambda kk, g, t0: (0, g)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
            pl.BlockSpec((tq, tk), lambda kk, g, t0: (0, kk)),
            pl.BlockSpec((1, tq), lambda kk, g, t0: (0, 0)),
            pl.BlockSpec((reps, tq), lambda kk, g, t0: (g, 0)),
            pl.BlockSpec((reps, tq), lambda kk, g, t0: (g, 0)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
        ],
        out_specs=[
            pl.BlockSpec(q.shape, lambda kk, g, t0: (0, 0)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
            pl.BlockSpec((tk, LANES), lambda kk, g, t0: (kk, g)),
            pl.BlockSpec((tq, tk), lambda kk, g, t0: (0, kk)),
        ],
        scratch_shapes=[pltpu.VMEM((groups, tq, reps * LANES), jnp.float32),
                        pltpu.VMEM((tk, tq), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_backward_kernel, reps=reps),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(dk.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dv.shape, jnp.float32),
                   jax.ShapeDtypeStruct((tq, keys), jnp.float32)],
        # operands count the prefetched scalar: dk, dv are 9 and 10
        input_output_aliases={9: 1, 10: 2},
        compiler_params=_params("arbitrary", "arbitrary"),
        name="dsa_attention_backward", interpret=interpret,
    )(jnp.asarray(t0, jnp.int32).reshape(1), q, do, k, v, scores,
      thr[None, :], lse, di, dk, dv)


# ------------------------------------------------- latent attention (MLA)
#
# The same blocked attention for the second token model's heads
# (``models/latent_moe.py``): the mask is the causal one alone (no
# scores, no threshold, no alignment target), every query head has its
# own keys and values (the ``groups == heads`` case, ``heads`` of them a
# grid step so that a step is worth its overhead), value heads may be
# wider or narrower than score heads, and a head's score is the sum of
# two products: ``q . k`` over the head's own ``dn`` columns and
# ``q_rope . k_rope`` over ``dr`` rotary columns whose key is ONE
# ``[T, dr]`` array for all heads (never broadcast). The bound-shifted
# softmax, the lane-wise row sums and the transposed-tile backward are
# the ones above.
#
# One call takes a whole batch in each direction: the grid is (sequence,
# head step, live pair), and two scalar-prefetched tables name each
# live (query chunk, key tile) pair, so that no grid step lies above the
# diagonal and nothing loops around the call. The operands are read in
# the projections' layouts, heads side by side in lane rows: ``kv [B, T,
# heads x (dn + dv)]`` holds head ``r``'s key at columns ``r (dn + dv)``
# and its values right after it, and the backward writes their cotangent
# in that layout. The forward walks the pairs chunk by chunk (a chunk's
# output gathers over its tiles), the backward tile by tile (a tile's
# ``dk`` and ``dv`` gather over its chunks in VMEM and leave it once, cast;
# every chunk's ``dq`` gathers in a float32 scratch of the sequence for
# the step's heads and leaves it after the chunk's last tile).


# Most heads a grid step. The forward's 4 are what fitted the 16 MB of
# scoped VMEM that XLA held a call to inside a loop over chunks (found
# compiling the cell's step for a described v5e); no such loop is left,
# and 8 read 2.5% faster alone on a v5e, but are not measured in a step.
FORWARD_HEADS, BACKWARD_HEADS = 4, 8


def head_step(heads: int, most: int) -> int:
    """Heads a grid step: the largest divisor of ``heads`` that is at
    most ``most``."""
    return max(n for n in range(1, min(most, heads) + 1) if heads % n == 0)


def _latent_widths(q, q_rope, kv, k_rope) -> tuple:
    """-> (heads, dn, dr, dv) of the operands' last axes."""
    dr = k_rope.shape[-1]
    heads = q_rope.shape[-1] // dr
    dn = q.shape[-1] // heads
    return heads, dn, dr, kv.shape[-1] // heads - dn


def _last_tile(c, tq: int, tk: int):
    """The key tile that holds query chunk ``c``'s last query."""
    return (c * tq + tq - 1) // tk


def _table(values) -> jax.Array:
    """A scalar-prefetched table of the grid's live pairs."""
    return jnp.asarray(values, jnp.int32)


def live_pairs(t: int, tq: int, tk: int, by_tile: bool = False):
    """The (query chunk, key tile) pairs whose tile holds a key at or
    below the chunk's last query, chunk by chunk with each chunk's tiles
    in order (``by_tile``: tile by tile with each tile's chunks in
    order) -> (chunks, tiles), a tuple of ints each."""
    chunks = range(t // tq)
    if by_tile:
        pairs = [(c, kk) for kk in range(t // tk) for c in chunks
                 if _last_tile(c, tq, tk) >= kk]
    else:
        pairs = [(c, kk) for c in chunks
                 for kk in range(_last_tile(c, tq, tk) + 1)]
    return tuple(zip(*pairs))


def _scale(scale, dn: int, dr: int) -> float:
    """The softmax's scale: the caller's, or ``1 / sqrt(dn + dr)``."""
    return 1.0 / math.sqrt(dn + dr) if scale is None else float(scale)


def _cols(r: int, width: int) -> slice:
    """Head ``r``'s columns among heads ``width`` wide side by side."""
    return slice(r * width, (r + 1) * width)


def _causal_tile(t0, kk, tq: int, tk: int, transposed: bool = False):
    """``[tq, tk]`` (``[tk, tq]`` transposed): key at or below query."""
    shape, q_axis = ((tk, tq), 1) if transposed else ((tq, tk), 0)
    query = t0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    key = kk * tk + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return key <= query


def latent_key_norms(kv, k_rope, heads: int, dn: int, block: int):
    """``[..., T / block, heads]`` float32: the largest norm of each
    head's whole key ``[k_h | k_rope]`` (``k_h`` the first ``dn`` of head
    ``h``'s columns of ``kv [..., T, heads x (dn + dv)]``) over the keys
    up to each block's end: the key part of the softmax's bound for the
    block's queries (``kmax``)."""
    sq = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)), -1)
    k = kv.reshape(*kv.shape[:-1], heads, -1)[..., :dn]
    norms = jnp.sqrt(sq(k) + sq(k_rope)[..., None])
    *lead, t = norms.shape[:-1]
    blocks = jnp.max(norms.reshape(*lead, t // block, block, heads), -2)
    return lax.cummax(blocks, len(lead))


def _latent_forward_kernel(chunk_ref, tile_ref, q_ref, qr_ref, kv_ref,
                           kr_ref, kmax_ref, o_ref, lse_ref, acc_ref, l_ref,
                           bound_ref, *, heads: int, scale: float):
    tq, tk = q_ref.shape[0], kv_ref.shape[0]
    dn, dr, dv = q_ref.shape[1] // heads, kr_ref.shape[1], acc_ref.shape[2]
    pair = pl.program_id(2)
    t0, kk = chunk_ref[pair] * tq, tile_ref[pair]

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        for r in range(heads):
            qf = q_ref[:, _cols(r, dn)].astype(jnp.float32)
            qrf = qr_ref[:, _cols(r, dr)].astype(jnp.float32)
            norm = jnp.sqrt(jnp.sum(qf * qf, -1, keepdims=True)
                            + jnp.sum(qrf * qrf, -1, keepdims=True))
            bound_ref[r] = (jnp.broadcast_to(norm, (tq, LANES))
                            * kmax_ref[:, _cols(r, LANES)] * scale)

    keep = _causal_tile(t0, kk, tq, tk)
    kr_t = kr_ref[...]
    for r in range(heads):
        k0 = r * (dn + dv)
        s = lax.dot_general(q_ref[:, _cols(r, dn)], kv_ref[:, k0:k0 + dn],
                            _NT, preferred_element_type=jnp.float32)
        s += lax.dot_general(qr_ref[:, _cols(r, dr)], kr_t, _NT,
                             preferred_element_type=jnp.float32)
        z = s * scale - _lane_tiles(bound_ref[r], tk // LANES)
        p = jnp.where(keep, jnp.exp(jnp.maximum(z, -80.0)), 0.0)
        _accumulate(r, p, kv_ref[:, k0 + dn:k0 + dn + dv], l_ref, acc_ref)

    @pl.when(kk == (t0 + tq - 1) // tk)           # the chunk's last tile
    def _():
        for r in range(heads):
            _finish(r, o_ref, lse_ref, acc_ref, l_ref, bound_ref)


def latent_forward(q, q_rope, kv, k_rope, kmax, *, q_chunk: int,
                   scale: float | None = None,
                   interpret: bool | None = None):
    """Causal attention of a batch with scores ``(q_h . k_h + q_rope_h .
    k_rope) x scale``, ``scale`` by default ``1 / sqrt(dn + dr)``: ``q [B,
    T, heads x dn]``, ``q_rope [B, T, heads x dr]``, ``kv [B, T, heads x
    (dn + dv)]`` (head ``h``'s key at columns ``h (dn + dv)``, its values
    after it), ``k_rope [B, T, dr]``; queries in chunks of ``q_chunk``.
    ``kmax [B, blocks, heads]`` float32: for each of ``blocks`` equal
    blocks of positions, the maximum of :func:`latent_key_norms` over the
    keys up to the block's end, which bounds the block's logits. ->
    (output ``[B, T, heads x dv]`` in ``q``'s dtype, log-sum-exp ``[B,
    heads, T]`` float32)."""
    interpret = _interpret() if interpret is None else interpret
    b, t = q.shape[:2]
    heads, dn, dr, dv = _latent_widths(q, q_rope, kv, k_rope)
    hb, tq, tk = head_step(heads, FORWARD_HEADS), q_chunk, key_tile(t)
    block = t // kmax.shape[1]
    chunks, tiles = live_pairs(t, tq, tk)
    rows = lambda w: pl.BlockSpec(
        (None, tq, hb * w), lambda i, g, p, c, kk: (i, c[p], g))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, heads // hb, len(chunks)),
        in_specs=[
            rows(dn), rows(dr),
            pl.BlockSpec((None, tk, hb * (dn + dv)),
                         lambda i, g, p, c, kk: (i, kk[p], g)),
            pl.BlockSpec((None, tk, dr), lambda i, g, p, c, kk: (i, kk[p], 0)),
            pl.BlockSpec((None, None, 1, hb * LANES),
                         lambda i, g, p, c, kk: (i, c[p] * tq // block, 0, g)),
        ],
        out_specs=[
            rows(dv),
            pl.BlockSpec((None, None, hb, tq),
                         lambda i, g, p, c, kk: (i, g, 0, c[p])),
        ],
        scratch_shapes=[pltpu.VMEM((hb, tq, dv), jnp.float32),
                        pltpu.VMEM((hb, tq, LANES), jnp.float32),
                        pltpu.VMEM((hb, tq, LANES), jnp.float32)])
    o, lse = pl.pallas_call(
        functools.partial(_latent_forward_kernel, heads=hb,
                          scale=_scale(scale, dn, dr)),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((b, t, heads * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, heads // hb, hb, t),
                                        jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="mla_attention_forward", interpret=interpret,
    )(_table(chunks), _table(tiles), q, q_rope, kv, k_rope,
      jnp.repeat(kmax.astype(jnp.float32), LANES, -1)[:, :, None])
    return o, lse.reshape(b, heads, t)


def _latent_backward_kernel(chunk_ref, tile_ref, done_ref, q_ref, qr_ref,
                            do_ref, kv_ref, kr_ref, lse_ref, di_ref, dq_ref,
                            dqr_ref, dkv_ref, dkr_ref, dq_acc_ref,
                            dqr_acc_ref, dkv_acc_ref, dkr_acc_ref, *,
                            heads: int, scale: float):
    del done_ref                                  # the index maps' alone
    tq, tk = q_ref.shape[0], kv_ref.shape[0]
    dn, dr = q_ref.shape[1] // heads, kr_ref.shape[1]
    dv = do_ref.shape[1] // heads
    pair = pl.program_id(2)
    c, kk = chunk_ref[pair], tile_ref[pair]
    t0 = c * tq

    @pl.when(c == kk * tk // tq)                  # the tile's first chunk
    def _():
        dkv_acc_ref[...] = jnp.zeros_like(dkv_acc_ref)
        dkr_acc_ref[...] = jnp.zeros_like(dkr_acc_ref)

    @pl.when(kk == 0)                             # the chunk's first tile
    def _():
        dq_acc_ref[c] = jnp.zeros(dq_acc_ref.shape[1:], jnp.float32)
        dqr_acc_ref[c] = jnp.zeros(dqr_acc_ref.shape[1:], jnp.float32)

    keep = _causal_tile(t0, kk, tq, tk, transposed=True)
    kr_t = kr_ref[...]
    dkr = jnp.zeros((tk, dr), jnp.float32)
    for r in range(heads):
        k0 = r * (dn + dv)
        q_r = q_ref[:, _cols(r, dn)]
        qr_r = qr_ref[:, _cols(r, dr)]
        do_r = do_ref[:, _cols(r, dv)]
        k_t = kv_ref[:, k0:k0 + dn]
        s = lax.dot_general(k_t, q_r, _NT,
                            preferred_element_type=jnp.float32)
        s += lax.dot_general(kr_t, qr_r, _NT,
                             preferred_element_type=jnp.float32)
        p = jnp.exp(jnp.where(keep, s * scale - lse_ref[r:r + 1, :],
                              _NEG))                        # [tk, tq]
        dkv_acc_ref[:, k0 + dn:k0 + dn + dv] += jnp.dot(
            p.astype(do_r.dtype), do_r, preferred_element_type=jnp.float32)
        dp = lax.dot_general(kv_ref[:, k0 + dn:k0 + dn + dv], do_r, _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[r:r + 1, :])
        ds_t = ds.astype(q_r.dtype)
        dkv_acc_ref[:, k0:k0 + dn] += jnp.dot(
            ds_t, q_r, preferred_element_type=jnp.float32)
        dkr += jnp.dot(ds_t, qr_r, preferred_element_type=jnp.float32)
        ds_q = ds.T.astype(k_t.dtype)
        dq_acc_ref[c, :, _cols(r, dn)] += jnp.dot(
            ds_q, k_t, preferred_element_type=jnp.float32)
        dqr_acc_ref[c, :, _cols(r, dr)] += jnp.dot(
            ds_q, kr_t, preferred_element_type=jnp.float32)
    dkr_acc_ref[...] += dkr

    @pl.when(kk == (t0 + tq - 1) // tk)           # the chunk's last tile
    def _():
        dq_ref[...] = (dq_acc_ref[c] * scale).astype(dq_ref.dtype)
        dqr_ref[...] = (dqr_acc_ref[c] * scale).astype(dqr_ref.dtype)

    @pl.when(c == dq_acc_ref.shape[0] - 1)        # the tile's last chunk
    def _():
        for r in range(heads):
            k0 = r * (dn + dv)
            dkv_ref[:, k0:k0 + dn] = (dkv_acc_ref[:, k0:k0 + dn]
                                      * scale).astype(dkv_ref.dtype)
            dkv_ref[:, k0 + dn:k0 + dn + dv] = dkv_acc_ref[
                :, k0 + dn:k0 + dn + dv].astype(dkv_ref.dtype)
        dkr_ref[...] = dkr_acc_ref[...] * scale


def latent_backward(q, q_rope, kv, k_rope, lse, di, do, *, q_chunk: int,
                    scale: float | None = None,
                    interpret: bool | None = None):
    """The batch's cotangents from its output's (``do [B, T, heads x
    dv]``), the forward's ``lse`` and ``di [B, heads, T] = sum(o * do)``
    per head; operands, ``q_chunk`` and ``scale`` as the forward's. ->
    (``dq``, ``dq_rope``, ``dkv`` in ``kv``'s layout, ``dk_rope``, each
    in its operand's dtype)."""
    interpret = _interpret() if interpret is None else interpret
    b, t = q.shape[:2]
    heads, dn, dr, dv = _latent_widths(q, q_rope, kv, k_rope)
    hb, tq, tk = head_step(heads, BACKWARD_HEADS), q_chunk, key_tile(t)
    steps = heads // hb
    chunks, tiles = live_pairs(t, tq, tk, by_tile=True)
    # a chunk's dq leaves after its last tile: each step names the last
    # chunk done, so that a block is written back once it is whole
    done = list(itertools.accumulate(
        (c if _last_tile(c, tq, tk) == kk else 0
         for c, kk in zip(chunks, tiles)), max))
    chunk_rows = lambda w: pl.BlockSpec(
        (None, tq, hb * w), lambda i, g, p, c, kk, d: (i, c[p], g))
    done_rows = lambda w: pl.BlockSpec(
        (None, tq, hb * w), lambda i, g, p, c, kk, d: (i, d[p], g))
    keyed = pl.BlockSpec((None, tk, hb * (dn + dv)),
                         lambda i, g, p, c, kk, d: (i, kk[p], g))
    stats = pl.BlockSpec((None, None, hb, tq),
                         lambda i, g, p, c, kk, d: (i, g, 0, c[p]))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b, steps, len(chunks)),
        in_specs=[chunk_rows(dn), chunk_rows(dr), chunk_rows(dv), keyed,
                  pl.BlockSpec((None, tk, dr),
                               lambda i, g, p, c, kk, d: (i, kk[p], 0)),
                  stats, stats],
        out_specs=[done_rows(dn), done_rows(dr), keyed,
                   pl.BlockSpec((None, None, tk, dr),
                                lambda i, g, p, c, kk, d: (i, g, kk[p], 0))],
        scratch_shapes=[
            pltpu.VMEM((t // tq, tq, hb * dn), jnp.float32),
            pltpu.VMEM((t // tq, tq, hb * dr), jnp.float32),
            pltpu.VMEM((tk, hb * (dn + dv)), jnp.float32),
            pltpu.VMEM((tk, dr), jnp.float32)])
    per_step = lambda a: a.reshape(b, steps, hb, t)
    dq, dq_rope, dkv, dk_rope = pl.pallas_call(
        functools.partial(_latent_backward_kernel, heads=hb,
                          scale=_scale(scale, dn, dr)),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
                   jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                   # the one rotary key's: a sum a head step, added below
                   jax.ShapeDtypeStruct((b, steps, t, dr), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="mla_attention_backward", interpret=interpret,
    )(_table(chunks), _table(tiles), _table(done), q, q_rope,
      do, kv, k_rope, per_step(lse), per_step(di))
    return dq, dq_rope, dkv, jnp.sum(dk_rope, 1).astype(k_rope.dtype)
