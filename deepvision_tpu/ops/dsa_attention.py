"""Pallas TPU kernels: masked grouped-query attention of one chunk of
queries whose ``[heads, queries, keys]`` tiles never leave the chip.

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

Numerics: operands of both products in the inputs' dtype, float32
accumulation; logits, exponent, row sums, division, target float32.
Key tiles strictly above the diagonal are skipped. Layouts are the
model's (``[T, heads x dim]``): no transpose outside the kernels.

``interpret=True`` runs the Pallas interpreter (CPU tests); left to the
default it is chosen by the backend.
"""

from __future__ import annotations

import functools
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
            # row sums stay lane-wise partial sums until the last tile
            l_ref[r] += sum(p[:, j * LANES:(j + 1) * LANES]
                            for j in range(tk // LANES))
            acc_ref[r] += jnp.dot(p.astype(v_t.dtype), v_t,
                                  preferred_element_type=jnp.float32)

    @pl.when(kk == last)
    def _():
        for r in range(reps):
            total = jnp.sum(l_ref[r], -1, keepdims=True)        # [tq, 1]
            o_ref[:, r * hd:(r + 1) * hd] = (acc_ref[r] / total).astype(
                o_ref.dtype)
            lse = bound_ref[r] + jnp.log(jnp.broadcast_to(total,
                                                          (tq, LANES)))
            lse_ref[r:r + 1, :] = lse.T[:1, :]


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
