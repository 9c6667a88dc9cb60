"""Pallas TPU kernels: the manifold-constrained hyper-connections (mHC) of
``models/hyper_latent.py`` over tiles of tokens, forward and backward,
with the residual streams read and written in their compute dtype and no
float32 copy of them, or of their cotangent, in HBM.

Around a sublayer ``F`` the hyper-connection computes, per token, from
``x~ = RMSNorm(vec X)`` (``X [n, C]``, width ``n C``, no scale) the maps
``H_pre = sigmoid(a_pre (x~ phi_pre) + b_pre)``, ``H_post = 2 sigmoid(...)``
and ``H_res = Sinkhorn(exp(clip(a_res (x~ phi_res) + b_res)))``, then the
pre-mix ``u = sum_s H_pre[s] X[s]`` and, once ``F`` has run, the
post-mix ``X' = H_res X + H_post^T F(u)``. The XLA form
(``hyper_latent.HyperMaps``, ``hyper_sublayer``) turns the bf16 streams
into float32 operands for each of these and their cotangents into
float32 arrays of the streams' size. Here four kernels each make one
pass over the token tiles (``[tokens, n C]``, the streams side by side
in lane rows):

- ``mhc_pre_forward`` reads ``X`` once: the RMS factor, the product,
  the sigmoids, the clamped exponentials, ``sinkhorn_iters`` rounds with
  ``hc_eps`` in each denominator, the Sinkhorn error, and ``u`` summed in
  float32 and written in ``X``'s dtype. A token's maps go to HBM as one
  lane row of float32 (``h [tokens, 128]``, layout below).
- ``mhc_post_forward`` reads ``X``, ``y = F(u)`` and ``h``; writes ``X'``.
- ``mhc_post_backward`` reads ``dX'``, ``X``, ``y`` and ``h``; writes
  ``H_res^T dX'`` and ``dy`` in the streams' dtype and the maps'
  cotangents ``dH_post``, ``dH_res`` (``dh``, ``h``'s layout).
- ``mhc_pre_backward`` reads ``X``, ``du``, the running ``dX`` (the
  post-mix's part) and ``dh``: ``dH_pre``, the maps' backward (the
  Sinkhorn rounds are run again and walked back), ``dX`` in the streams'
  dtype and, summed in float32 over the token grid (an "arbitrary" axis
  into resident output blocks), the gradients of ``phi``, of the three
  scales and of the biases.

Numerics are the XLA form's. The streams are exact in the compute dtype
(bf16), so ``x phi`` at float32 accuracy needs only ``phi`` in three
bf16 parts, whose sum is float32 ``phi``: one product of the tile with
the three parts stacked, the parts' results added in float32. The same
split of the float32 cotangent gives ``dphi``; ``dX``'s share of the
product takes the cotangent in two parts. The norm, the maps, the
Sinkhorn rounds and every sum of the mixes are float32.

The ``[n, n]`` arithmetic runs with tokens on the lane axis: the 24 map
rows of ``n = 4`` streams fill three sublane groups, ``[H_pre; H_post]``,
``[H_res[:, 0]; H_res[:, 1]]`` and ``[H_res[:, 2]; H_res[:, 3]]`` (each
half of a group one column of ``H_res``, row ``i`` of the matrix on
sublane ``i``). A row sum of ``H_res`` is the two groups' sum plus its
roll by half a group; a column sum is a sum over half a group's
sublanes. ``phi``'s columns and the biases are put in this order outside
the kernels, so that autodiff puts their gradients back.

``h``'s lane row, kernel order: ``[0, 8)`` ``H_pre``, ``H_post``; ``[8,
24)`` ``H_res`` by columns (lane ``8 + 4 j + i`` is ``H_res[i, j]``);
``24`` the Sinkhorn error; ``25`` the RMS factor; ``[32, 56)`` the
projections ``x~ phi`` before scale and bias.

A token count the tile does not divide is padded with zero tokens,
which give zero cotangents, and cut off again. ``interpret=True`` runs
the Pallas interpreter (CPU tests); left to the default it is chosen by
the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
STREAMS = 4
MAPS = STREAMS * (STREAMS + 2)      # map rows a token: pre, post, res
GROUP = 8                           # sublanes of a float32 vreg
ERR, INV, PROJ = 24, 25, 32         # lanes of h
TOKEN_TILE = 256
VMEM_LIMIT = 100 * 1024 * 1024      # of a v5e core's 128 MiB
F32, BF16 = jnp.float32, jnp.bfloat16
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def token_tile(tokens: int) -> int:
    """Tokens a grid step: 256 where they divide the tokens, else a lane
    row (the tokens then padded to a multiple of it)."""
    return TOKEN_TILE if tokens % TOKEN_TILE == 0 else LANES


def _column_chunk(width: int) -> int:
    """Lanes of one stream that a step of a kernel's inner loop takes."""
    return next(c for c in (512, 256, LANES) if width % c == 0)


def _kernel_order() -> tuple:
    """The model's map index (``[pre | post | res row by row]``) of each
    kernel row (``[pre | post | res column by column]``)."""
    n = STREAMS
    res = [2 * n + i * n + j for j in range(n) for i in range(n)]
    return tuple(range(2 * n)) + tuple(res)


# ------------------------------------------------- the maps, tokens on lanes


def _sublanes(shape):
    return lax.broadcasted_iota(jnp.int32, shape, 0)


def _row_sums(g1, g2):
    """``H_res``'s row sums, each on the sublanes of its row in both
    halves of a group."""
    s = g1 + g2
    return s + pltpu.roll(s, STREAMS, 0)


def _col_sums(g):
    """The column sums of the two columns a group holds, each on its
    half's sublanes."""
    low = _sublanes(g.shape) < STREAMS
    a = jnp.sum(jnp.where(low, g, 0.0), 0, keepdims=True)
    b = jnp.sum(jnp.where(low, 0.0, g), 0, keepdims=True)
    return jnp.where(low, a, b)


def _maps(z, hc, keep: bool = False):
    """Pre-activations ``z [32, tq]`` (kernel rows) -> (``[H_pre;
    H_post]``, the two ``H_res`` groups, the Sinkhorn error ``[1, tq]``,
    each half round's (output, denominators) where ``keep``)."""
    v = jax.nn.sigmoid(z[0:GROUP])
    pp = jnp.where(_sublanes(v.shape) < STREAMS, v, 2.0 * v)
    c, eps = hc.res_clamp, hc.hc_eps
    g1 = jnp.exp(jnp.clip(z[GROUP:2 * GROUP], -c, c))
    g2 = jnp.exp(jnp.clip(z[2 * GROUP:3 * GROUP], -c, c))
    kept = []
    for _ in range(hc.sinkhorn_iters):
        d = _row_sums(g1, g2) + eps
        g1, g2 = g1 / d, g2 / d
        if keep:
            kept.append((g1, g2, d, None))
        d1, d2 = _col_sums(g1) + eps, _col_sums(g2) + eps
        g1, g2 = g1 / d1, g2 / d2
        if keep:
            kept.append((g1, g2, d1, d2))
    off = lambda a: jnp.max(jnp.abs(a - 1.0), 0, keepdims=True)
    err = jnp.maximum(off(_row_sums(g1, g2)),
                      jnp.maximum(off(_col_sums(g1)), off(_col_sums(g2))))
    return pp, g1, g2, err, kept


def _maps_backward(z, dpp, dg1, dg2, hc):
    """The cotangent of ``z [32, tq]`` from those of :func:`_maps`'s
    three groups: the rounds run again, then walked back (a half round
    ``Y = X / D``, ``D`` a row's or a column's sum plus ``hc_eps``, gives
    ``dX = (dY - sum over the row or column of dY Y) / D``)."""
    v = jax.nn.sigmoid(z[0:GROUP])
    two = jnp.where(_sublanes(v.shape) < STREAMS, 1.0, 2.0)
    dz0 = dpp * two * v * (1.0 - v)
    c = hc.res_clamp
    z1, z2 = z[GROUP:2 * GROUP], z[2 * GROUP:3 * GROUP]
    e1, e2 = jnp.exp(jnp.clip(z1, -c, c)), jnp.exp(jnp.clip(z2, -c, c))
    *_, kept = _maps(z, hc, keep=True)
    for g1, g2, d1, d2 in reversed(kept):
        if d2 is None:                                        # rows
            t = _row_sums(dg1 * g1, dg2 * g2)
            dg1, dg2 = (dg1 - t) / d1, (dg2 - t) / d1
        else:                                                 # columns
            dg1 = (dg1 - _col_sums(dg1 * g1)) / d1
            dg2 = (dg2 - _col_sums(dg2 * g2)) / d2
    inside = lambda a: (a > -c) & (a < c)
    dz1 = jnp.where(inside(z1), dg1 * e1, 0.0)
    dz2 = jnp.where(inside(z2), dg2 * e2, 0.0)
    return jnp.concatenate([dz0, dz1, dz2, jnp.zeros_like(dz0)], 0)


def _lane_fold(a):
    """``[tq, k x 128]`` -> ``[tq, 128]``: the lane groups added."""
    return sum(a[:, j * LANES:(j + 1) * LANES]
               for j in range(a.shape[1] // LANES))


def _stream(ref, s: int, k, cw: int, width: int):
    """Chunk ``k`` (``cw`` lanes) of stream ``s`` of a ``[tq, n C]`` ref."""
    return ref[:, pl.ds(pl.multiple_of(s * width + k * cw, LANES), cw)]


def _scaled(proj, sb):
    """``a proj + b``, row by row (``sb``: column 0 the scales, 1 the
    biases)."""
    return sb[:, 0:1] * proj + sb[:, 1:2]


# ---------------------------------------------------------------- forward


def _pre_forward_kernel(x_ref, pt_ref, sb_ref, u_ref, h_ref, raw_ref,
                        ssq_ref, *, hc, cw: int):
    tq, c = u_ref.shape
    n = STREAMS
    raw_ref[...] = jnp.zeros_like(raw_ref)
    ssq_ref[...] = jnp.zeros_like(ssq_ref)

    for s in range(n):
        def read(k, carry, s=s):
            xc = _stream(x_ref, s, k, cw, c)
            xf = xc.astype(F32)
            ssq_ref[...] += _lane_fold(xf * xf)
            raw_ref[...] += lax.dot_general(
                _stream(pt_ref, s, k, cw, c), xc, _NT,
                preferred_element_type=F32)
            return carry

        lax.fori_loop(0, c // cw, read, 0)
    ssq = jnp.sum(ssq_ref[...], 1, keepdims=True)                # [tq, 1]
    inv = lax.rsqrt(ssq / (n * c) + hc.rms_eps)
    inv_row = jnp.broadcast_to(inv, (tq, LANES)).T[0:1]          # [1, tq]
    raw = raw_ref[...]
    proj = (raw[0:32] + raw[32:64] + raw[64:96]) * inv_row       # [32, tq]
    pp, g1, g2, err, _ = _maps(_scaled(proj, sb_ref[...]), hc)
    row = _sublanes((GROUP, tq))
    tail = jnp.where(row == 0, err,
                     jnp.where(row == INV - ERR, inv_row, 0.0))
    h = jnp.concatenate([pp, g1, g2, tail, proj,
                         jnp.zeros((64, tq), F32)], 0).T          # [tq, 128]
    h_ref[...] = h
    pre = [h[:, s:s + 1] for s in range(n)]

    def mix(k, carry):
        acc = sum(pre[s] * _stream(x_ref, s, k, cw, c).astype(F32)
                  for s in range(n))
        u_ref[:, pl.ds(pl.multiple_of(k * cw, LANES), cw)] = acc.astype(
            u_ref.dtype)
        return carry

    lax.fori_loop(0, c // cw, mix, 0)


def _post_forward_kernel(x_ref, y_ref, h_ref, o_ref, *, cw: int):
    c, n = y_ref.shape[1], STREAMS
    h = h_ref[...]
    post = [h[:, n + i:n + i + 1] for i in range(n)]
    res = [[h[:, 2 * n + j * n + i:2 * n + j * n + i + 1] for j in range(n)]
           for i in range(n)]

    def body(k, carry):
        xs = [_stream(x_ref, j, k, cw, c).astype(F32) for j in range(n)]
        y = _stream(y_ref, 0, k, cw, c).astype(F32)
        for i in range(n):
            acc = res[i][0] * xs[0]
            for j in range(1, n):
                acc = acc + res[i][j] * xs[j]
            acc = acc + post[i] * y
            o_ref[:, pl.ds(pl.multiple_of(i * c + k * cw, LANES), cw)] = \
                acc.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, c // cw, body, 0)


# --------------------------------------------------------------- backward


def _post_backward_kernel(do_ref, x_ref, y_ref, h_ref, dx_ref, dy_ref,
                          dh_ref, acc_ref, *, cw: int):
    tq, c = y_ref.shape
    n = STREAMS
    h = h_ref[...]
    post = [h[:, n + i:n + i + 1] for i in range(n)]
    res = [[h[:, 2 * n + j * n + i:2 * n + j * n + i + 1] for j in range(n)]
           for i in range(n)]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(k, carry):
        dos = [_stream(do_ref, i, k, cw, c).astype(F32) for i in range(n)]
        xs = [_stream(x_ref, j, k, cw, c).astype(F32) for j in range(n)]
        y = _stream(y_ref, 0, k, cw, c).astype(F32)
        for j in range(n):
            dx = res[0][j] * dos[0]
            for i in range(1, n):
                dx = dx + res[i][j] * dos[i]
            dx_ref[:, pl.ds(pl.multiple_of(j * c + k * cw, LANES), cw)] = \
                dx.astype(dx_ref.dtype)
        dy = post[0] * dos[0]
        for i in range(1, n):
            dy = dy + post[i] * dos[i]
        dy_ref[:, pl.ds(pl.multiple_of(k * cw, LANES), cw)] = dy.astype(
            dy_ref.dtype)
        for i in range(n):
            acc_ref[n + i] += _lane_fold(dos[i] * y)
            for j in range(n):
                acc_ref[2 * n + j * n + i] += _lane_fold(dos[i] * xs[j])
        return carry

    lax.fori_loop(0, c // cw, body, 0)
    lane = lax.broadcasted_iota(jnp.int32, (tq, LANES), 1)
    dh = jnp.zeros((tq, LANES), F32)
    for col in range(n, MAPS):
        dh = jnp.where(lane == col,
                       jnp.sum(acc_ref[col], 1, keepdims=True), dh)
    dh_ref[...] = dh


def _pre_backward_kernel(x_ref, du_ref, dxr_ref, dh_ref, h_ref, pt_ref,
                         sb_ref, dx_ref, dpt_ref, dsb_ref, acc_ref, a_ref, *,
                         hc, cw: int):
    tq, c = du_ref.shape
    n = STREAMS

    @pl.when(pl.program_id(0) == 0)
    def _():
        dpt_ref[...] = jnp.zeros_like(dpt_ref)
        dsb_ref[...] = jnp.zeros_like(dsb_ref)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def pre_sums(k, carry):
        du = _stream(du_ref, 0, k, cw, c).astype(F32)
        for s in range(n):
            acc_ref[s] += _lane_fold(
                du * _stream(x_ref, s, k, cw, c).astype(F32))
        return carry

    lax.fori_loop(0, c // cw, pre_sums, 0)
    lane = lax.broadcasted_iota(jnp.int32, (tq, LANES), 1)
    dh = jnp.where(lane < n, 0.0, dh_ref[...])
    for s in range(n):
        dh = jnp.where(lane == s, jnp.sum(acc_ref[s], 1, keepdims=True), dh)
    dht, h = dh.T, h_ref[...]
    ht = h.T                                                     # [128, tq]
    proj = ht[PROJ:PROJ + 32]
    inv_row = ht[ERR:ERR + GROUP][INV - ERR:INV - ERR + 1]
    sb = sb_ref[...]
    dz = _maps_backward(_scaled(proj, sb), dht[0:GROUP],
                        dht[GROUP:2 * GROUP], dht[2 * GROUP:3 * GROUP], hc)
    dsb_ref[0:32] += dz
    dsb_ref[32:64] += dz * proj
    dproj = sb[:, 0:1] * dz                                      # [32, tq]
    g = dproj * inv_row                                          # d(x phi)
    coef = (inv_row * inv_row * jnp.sum(dproj * proj, 0, keepdims=True)
            / (n * c))                                           # [1, tq]
    hi = g.astype(BF16).astype(F32)
    rest = g - hi
    mid = rest.astype(BF16).astype(F32)
    zeros = jnp.zeros((32, tq), F32)
    g3 = jnp.concatenate([hi, mid, rest - mid, zeros], 0).astype(BF16)
    row = _sublanes((32, tq))
    a_hi = jnp.concatenate([hi, hi, hi, jnp.where(row == 0, coef, 0.0)],
                           0).T                                  # [tq, 128]
    coef_col = a_hi[:, 96:97]
    # the two parts of d(x phi) by tokens, read by every step of the loop
    a_ref[0] = a_hi.astype(BF16)
    a_ref[1] = jnp.concatenate([rest, rest, rest, zeros], 0).T.astype(BF16)
    pre = [h[:, s:s + 1] for s in range(n)]

    for s in range(n):
        def write(k, carry, s=s):
            xc = _stream(x_ref, s, k, cw, c)
            ptc = _stream(pt_ref, s, k, cw, c)
            dmap = (jnp.dot(a_ref[0], ptc, preferred_element_type=F32)
                    + jnp.dot(a_ref[1], ptc, preferred_element_type=F32))
            dx = (_stream(dxr_ref, s, k, cw, c).astype(F32)
                  + pre[s] * _stream(du_ref, 0, k, cw, c).astype(F32)
                  + dmap - coef_col * xc.astype(F32))
            cols = pl.ds(pl.multiple_of(s * c + k * cw, LANES), cw)
            dx_ref[:, cols] = dx.astype(dx_ref.dtype)
            dpt_ref[:, cols] += jnp.dot(g3, xc, preferred_element_type=F32)
            return carry

        lax.fori_loop(0, c // cw, write, 0)


# ------------------------------------------------------------------ calls
# Each call is jitted: a step's dozen sites then trace each kernel once a
# shape (the Sinkhorn rounds make the bodies long), not once a site.


def _padded(a, rows: int):
    return a if a.shape[0] == rows else jnp.pad(
        a, ((0, rows - a.shape[0]), (0, 0)))


def _grid(tokens: int):
    tq = token_tile(tokens)
    return tq, -(-tokens // tq) * tq


def _rows_spec(tq: int, width: int):
    return pl.BlockSpec((tq, width), lambda i: (i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _phi_rows(phi):
    """``[n C, 24]`` float32 (kernel order) -> ``[128, n C]`` bf16: its
    three bf16 parts, transposed, 32 rows each, and 32 rows of zeros."""
    parts, rest = [], phi.astype(F32)
    for _ in range(3):
        part = rest.astype(BF16)
        rest = rest - part.astype(F32)
        parts.append(jnp.pad(part.T, ((0, 32 - MAPS), (0, 0))))
    return jnp.concatenate(parts + [jnp.zeros_like(parts[0])], 0)


def _scale_bias(alpha, bias):
    """-> ``[32, 128]`` float32: each kernel row's scale (lane 0) and
    bias (lane 1)."""
    n = STREAMS
    scale = jnp.concatenate([jnp.full((n,), alpha[0]),
                             jnp.full((n,), alpha[1]),
                             jnp.full((n * n,), alpha[2])]).astype(F32)
    both = jnp.stack([scale, bias.astype(F32)], 1)               # [24, 2]
    return jnp.pad(both, ((0, 32 - MAPS), (0, LANES - 2)))


@functools.partial(jax.jit, static_argnames=("hc", "interpret"))
def _pre_forward(x, phi, alpha, bias, hc, interpret):
    tokens, width = x.shape
    c = width // STREAMS
    tq, rows = _grid(tokens)
    u, h = pl.pallas_call(
        functools.partial(_pre_forward_kernel, hc=hc, cw=_column_chunk(c)),
        grid=(rows // tq,),
        in_specs=[_rows_spec(tq, width), _whole((LANES, width)),
                  _whole((32, LANES))],
        out_specs=[_rows_spec(tq, c), _rows_spec(tq, LANES)],
        out_shape=[jax.ShapeDtypeStruct((rows, c), x.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((LANES, tq), F32),
                        pltpu.VMEM((tq, LANES), F32)],
        compiler_params=_params("parallel"),
        name="mhc_pre_forward", interpret=interpret,
    )(_padded(x, rows), _phi_rows(phi), _scale_bias(alpha, bias))
    return u[:tokens], h[:tokens]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _post_forward(x, y, h, interpret):
    tokens, width = x.shape
    c = y.shape[1]
    tq, rows = _grid(tokens)
    out = pl.pallas_call(
        functools.partial(_post_forward_kernel, cw=_column_chunk(c)),
        grid=(rows // tq,),
        in_specs=[_rows_spec(tq, width), _rows_spec(tq, c),
                  _rows_spec(tq, LANES)],
        out_specs=_rows_spec(tq, width),
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        compiler_params=_params("parallel"),
        name="mhc_post_forward", interpret=interpret,
    )(_padded(x, rows), _padded(y, rows), _padded(h, rows))
    return out[:tokens]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _post_backward(do, x, y, h, interpret):
    tokens, width = x.shape
    c = y.shape[1]
    tq, rows = _grid(tokens)
    dx, dy, dh = pl.pallas_call(
        functools.partial(_post_backward_kernel, cw=_column_chunk(c)),
        grid=(rows // tq,),
        in_specs=[_rows_spec(tq, width), _rows_spec(tq, width),
                  _rows_spec(tq, c), _rows_spec(tq, LANES)],
        out_specs=[_rows_spec(tq, width), _rows_spec(tq, c),
                   _rows_spec(tq, LANES)],
        out_shape=[jax.ShapeDtypeStruct((rows, width), x.dtype),
                   jax.ShapeDtypeStruct((rows, c), y.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((MAPS, tq, LANES), F32)],
        compiler_params=_params("parallel"),
        name="mhc_post_backward", interpret=interpret,
    )(*(_padded(a, rows) for a in (do, x, y, h)))
    return dx[:tokens], dy[:tokens], dh[:tokens]


@functools.partial(jax.jit, static_argnames=("hc", "interpret"))
def _pre_backward(x, du, dxr, dh, h, phi, alpha, bias, hc, interpret):
    tokens, width = x.shape
    c = du.shape[1]
    tq, rows = _grid(tokens)
    dx, dpt, dsb = pl.pallas_call(
        functools.partial(_pre_backward_kernel, hc=hc, cw=_column_chunk(c)),
        grid=(rows // tq,),
        in_specs=[_rows_spec(tq, width), _rows_spec(tq, c),
                  _rows_spec(tq, width), _rows_spec(tq, LANES),
                  _rows_spec(tq, LANES), _whole((LANES, width)),
                  _whole((32, LANES))],
        out_specs=[_rows_spec(tq, width), _whole((LANES, width)),
                   _whole((64, tq))],
        out_shape=[jax.ShapeDtypeStruct((rows, width), x.dtype),
                   jax.ShapeDtypeStruct((LANES, width), F32),
                   jax.ShapeDtypeStruct((64, tq), F32)],
        scratch_shapes=[pltpu.VMEM((STREAMS, tq, LANES), F32),
                        pltpu.VMEM((2, tq, LANES), BF16)],
        compiler_params=_params("arbitrary"),
        name="mhc_pre_backward", interpret=interpret,
    )(*(_padded(a, rows) for a in (x, du, dxr, dh, h)), _phi_rows(phi),
      _scale_bias(alpha, bias))
    dphi = (dpt[0:32] + dpt[32:64] + dpt[64:96])[:MAPS].T
    dz, dzp = jnp.sum(dsb[:32], 1)[:MAPS], jnp.sum(dsb[32:], 1)[:MAPS]
    n = STREAMS
    dalpha = jnp.stack([jnp.sum(dzp[:n]), jnp.sum(dzp[n:2 * n]),
                        jnp.sum(dzp[2 * n:])])
    return dx[:tokens], dphi, dalpha.astype(alpha.dtype), dz


# ---------------------------------------------------------- differentiable


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pre(x, phi, alpha, bias, hc, interpret):
    u, h = _pre_forward(x, phi, alpha, bias, hc, interpret)
    return u, h, x


def _pre_fwd(x, phi, alpha, bias, hc, interpret):
    u, h = _pre_forward(x, phi, alpha, bias, hc, interpret)
    return (u, h, x), (x, phi, alpha, bias, h)


def _pre_bwd(hc, interpret, kept, cts):
    x, phi, alpha, bias, h = kept
    du, dh, dxr = cts
    return _pre_backward(x, du, dxr, dh, h, phi, alpha, bias, hc, interpret)


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(x, y, h, interpret):
    return _post_forward(x, y, h, interpret)


def _post_fwd(x, y, h, interpret):
    return _post_forward(x, y, h, interpret), (x, y, h)


def _post_bwd(interpret, kept, do):
    return _post_backward(do, *kept, interpret)


_post.defvjp(_post_fwd, _post_bwd)


def premix(x, phi, alpha, bias, hc, *, interpret: bool | None = None):
    """The maps and the pre-mix of ``x [tokens, n C]`` (the streams side
    by side, ``n = 4``), ``phi [n C, n (n + 2)]``, ``alpha [3]``, ``bias
    [n (n + 2)]`` in the model's order; ``hc`` carries
    ``sinkhorn_iters``, ``hc_eps``, ``res_clamp`` and ``rms_eps``. ->
    (``u [tokens, C]`` in ``x``'s dtype, the maps ``h [tokens, 128]``
    float32, ``x``). Hand ``h`` and the returned ``x`` to
    :func:`postmix`: the way back then adds the post-mix's cotangent of
    ``x`` inside the pre-mix's backward kernel."""
    interpret = _interpret() if interpret is None else interpret
    order = jnp.asarray(_kernel_order())
    return _pre(x, phi[:, order], alpha, bias[order], hc, interpret)


def postmix(x, y, h, *, interpret: bool | None = None):
    """``X' = H_res X + H_post^T y``: ``x [tokens, n C]``, ``y [tokens,
    C]``, ``h`` from :func:`premix` -> ``[tokens, n C]`` in ``x``'s
    dtype."""
    interpret = _interpret() if interpret is None else interpret
    return _post(x, y, h, interpret)


def sinkhorn_error(h):
    """Each token's largest ``|row or column sum of H_res - 1|``, no
    gradient."""
    return lax.stop_gradient(h[:, ERR])
