"""Pallas TPU kernels: the lightning indexer's scores of one chunk of
queries, whose ``[heads, queries, keys]`` products never leave the chip.

The token model's sparse attention (``models/transformer.py``) selects,
for query ``t``, the causal keys with the largest indexer scores

    I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])

over ``J`` indexer heads of width ``D``. The XLA form
(``transformer.index_scores``) fuses the weighted sum into the product
where only the scores are wanted, but where it is differentiated it
writes the per-head products as a float32 ``[Tq, J, keys]`` tensor to
HBM and reads it back for the scores, for relu's two comparison masks,
for ``dw`` and, as a cotangent of the same size, for the two gradient
products: at a contraction of 64 that traffic, not the matrix unit, was
what the indexer cost on the way back (PERF.md, PR 34). Here a chunk of
``Tq`` queries meets its keys tile by tile, a head at a time, forward
and backward, and what goes to HBM is ``[Tq, keys]``.

Shapes, one chunk a call: ``qi [Tq, J x D]`` (the model's layout, heads
side by side), ``ki [keys, D]``, ``w [Tq, J]`` float32 (it carries the
score's scale), ``t0`` the first query's position among the keys.

- ``dsa_indexer_forward`` -> float32 ``scores [Tq, keys]``.
- ``dsa_indexer_backward``: from ``dscores [Tq, keys]`` it forms each
  tile's per-head product again, ``g = dscores * w_j * (dots > 0)``, and
  accumulates ``dqi_j += g @ ki``, ``dki += g.T @ qi_j`` and ``dw_j +=
  sum_s dscores * relu(dots)``. ``dki`` (float32, ``[>= keys, D]``) is
  the sum over the chunks so far; this chunk's part is added to its
  first ``keys`` rows in place.

Both work on transposed tiles (``[keys, queries]``), where ``w_j`` is a
lane row that broadcasts over the keys for nothing and ``dw_j`` is a sum
over sublanes; the forward turns its tile once, after the last head.

Numerics are the configuration's: both operands of every product in the
inputs' dtype, float32 accumulation; the relu, ``w``, the sum over the
heads (head 0 first, one after another) and the scores float32. ``g``
goes into its two products in the inputs' dtype, as XLA's default
precision takes a float32 cotangent on this chip. Nothing is
approximated.

Skipped: key tiles strictly above the chunk's last query. The forward
writes zeros there and the backward leaves them out; every consumer
masks those pairs as not causal (``transformer._causal``). Inside a
live tile every pair is computed, the masked half of the diagonal tile
too.

One chunk's scores are computed three times a training step: for the
selection's thresholds, in the attention's forward and in its backward.
A threshold is a value of the first evaluation, compared with ``>=`` in
the other two: the three have to be the same function of the same bits
or the selected set changes, so every call site takes this kernel or
none does (``transformer.chunk_scores``).

``interpret=True`` runs the Pallas interpreter (CPU tests); left to the
default it is chosen by the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepvision_tpu.ops import dsa_attention as dsa

_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _live_tile(kk, t0_ref, tq: int, tk: int):
    """Key tile ``kk``, or the last one ``dsa._tile_is_live`` keeps where
    ``kk`` lies above it: a block index that names the block of the
    step before costs no fetch."""
    return jnp.minimum(kk, (t0_ref[0] + tq - 1) // tk)


def _products(q_ref, k_t, heads: int):
    """Each head's ``[tk, tq]`` float32 product of the key tile with the
    chunk's queries, head 0 first."""
    dim = k_t.shape[1]
    for j in range(heads):
        q_j = q_ref[:, j * dim:(j + 1) * dim]
        yield j, q_j, lax.dot_general(k_t, q_j, dsa._NT,
                                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- forward


def _forward_kernel(t0_ref, q_ref, k_ref, w_ref, o_ref, *, heads: int):
    tq, tk = o_ref.shape
    kk = pl.program_id(0)
    live = dsa._tile_is_live(t0_ref[0], kk, tq, tk)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        acc = jnp.zeros((tk, tq), jnp.float32)
        for j, _q, dots in _products(q_ref, k_ref[...], heads):
            acc += w_ref[j:j + 1, :] * jnp.maximum(dots, 0.0)
        o_ref[...] = acc.T


def forward(qi, ki, w, t0, *, interpret: bool | None = None):
    """One chunk's scores: see the module's docstring. -> float32
    ``[Tq, keys]``, zeros in the key tiles above the chunk's last
    query."""
    interpret = dsa._interpret() if interpret is None else interpret
    tq, heads = w.shape
    keys, dim = ki.shape
    tk = dsa.key_tile(keys)
    k_map = lambda kk, t0: (_live_tile(kk, t0, tq, tk), 0)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(keys // tk,),
        in_specs=[
            pl.BlockSpec((tq, heads * dim), lambda kk, t0: (0, 0)),
            pl.BlockSpec((tk, dim), k_map),
            pl.BlockSpec((heads, tq), lambda kk, t0: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tq, tk), lambda kk, t0: (0, kk)))
    return pl.pallas_call(
        functools.partial(_forward_kernel, heads=heads),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((tq, keys), jnp.float32),
        compiler_params=dsa._params("arbitrary"),
        name="dsa_indexer_forward", interpret=interpret,
    )(jnp.asarray(t0, jnp.int32).reshape(1), qi, ki,
      w.astype(jnp.float32).T)


# --------------------------------------------------------------- backward


def _backward_kernel(t0_ref, q_ref, k_ref, w_ref, ds_ref, dk_in_ref,
                     dq_ref, dw_ref, dk_ref, dq_acc_ref, dw_acc_ref, *,
                     heads: int):
    tq, tk = ds_ref.shape
    dim = k_ref.shape[1]
    kk = pl.program_id(0)
    live = dsa._tile_is_live(t0_ref[0], kk, tq, tk)

    @pl.when(kk == 0)
    def _():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        dw_acc_ref[...] = jnp.zeros_like(dw_acc_ref)

    @pl.when(live)
    def _():
        k_t = k_ref[...]
        ds = ds_ref[...].T                                      # [tk, tq]
        dk = dk_in_ref[...]
        for j, q_j, dots in _products(q_ref, k_t, heads):
            dw_acc_ref[j:j + 1, :] += jnp.sum(
                ds * jnp.maximum(dots, 0.0), 0, keepdims=True)
            g = jnp.where(dots > 0.0, ds * w_ref[j:j + 1, :], 0.0).astype(
                k_t.dtype)
            dk += jnp.dot(g, q_j, preferred_element_type=jnp.float32)
            dq_acc_ref[:, j * dim:(j + 1) * dim] += lax.dot_general(
                g, k_t, _TN, preferred_element_type=jnp.float32)
        dk_ref[...] = dk

    @pl.when(kk == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_acc_ref[...]


def backward(qi, ki, w, t0, dscores, dki, *, interpret: bool | None = None):
    """The chunk's cotangents from its scores' (``dscores``, float32
    ``[Tq, keys]``). -> (``dqi`` in ``qi``'s dtype, ``dw`` float32
    ``[Tq, J]``, ``dki`` with this chunk's part added to its first
    ``keys`` rows in place)."""
    interpret = dsa._interpret() if interpret is None else interpret
    tq, heads = w.shape
    keys, dim = ki.shape
    tk = dsa.key_tile(keys)
    # the tiles above the last live one are not visited: nothing is
    # fetched and dki's rows there stay as they are
    k_map = lambda kk, t0: (_live_tile(kk, t0, tq, tk), 0)
    ds_map = lambda kk, t0: (0, _live_tile(kk, t0, tq, tk))
    whole = lambda kk, t0: (0, 0)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(keys // tk,),
        in_specs=[
            pl.BlockSpec((tq, heads * dim), whole),
            pl.BlockSpec((tk, dim), k_map),
            pl.BlockSpec((heads, tq), whole),
            pl.BlockSpec((tq, tk), ds_map),
            pl.BlockSpec((tk, dim), k_map),
        ],
        out_specs=[
            pl.BlockSpec((tq, heads * dim), whole),
            pl.BlockSpec((heads, tq), whole),
            pl.BlockSpec((tk, dim), k_map),
        ],
        scratch_shapes=[pltpu.VMEM((tq, heads * dim), jnp.float32),
                        pltpu.VMEM((heads, tq), jnp.float32)])
    dq, dw, dki = pl.pallas_call(
        functools.partial(_backward_kernel, heads=heads),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct(qi.shape, qi.dtype),
                   jax.ShapeDtypeStruct((heads, tq), jnp.float32),
                   jax.ShapeDtypeStruct(dki.shape, jnp.float32)],
        # operands count the prefetched scalar: dki is 5
        input_output_aliases={5: 2},
        compiler_params=dsa._params("arbitrary"),
        name="dsa_indexer_backward", interpret=interpret,
    )(jnp.asarray(t0, jnp.int32).reshape(1), qi, ki,
      w.astype(jnp.float32).T, dscores, dki)
    return dq, dw.T, dki
