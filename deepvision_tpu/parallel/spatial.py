"""Spatial partitioning with explicit ring halo exchange.

Shards the image-height dim of NHWC activations across a mesh axis and
runs convolutions locally, exchanging ``halo`` boundary rows with ring
neighbors via ``lax.ppermute`` — one hop over ICI per direction, exactly
the neighbor-exchange schedule ring attention uses for sequence shards
(SURVEY §5.7: spatial partitioning is the CNN analog of
sequence/context parallelism).

The framework's default path lets GSPMD infer these halos from a
``NamedSharding`` (tests/test_spatial.py); this module is the explicit
form for when the schedule must be controlled (e.g. overlapping the two
halo sends with interior compute) and as the documented pattern for
porting ring algorithms. Numerics vs the unsharded conv are pinned by
tests/test_parallel.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepvision_tpu.core.mesh import AXIS_DATA, AXIS_MODEL


def halo_exchange(x: jax.Array, halo: int, axis_name: str) -> jax.Array:
    """Concatenate ``halo`` rows from the ring neighbors onto a local
    H-shard (B, H_local, W, C) → (B, H_local + 2·halo, W, C).

    Boundary shards receive zero rows (SAME zero-padding semantics).
    Runs inside ``shard_map`` over ``axis_name``; each direction is one
    ``ppermute`` hop (nearest-neighbor over ICI on a real ring).
    """
    if halo == 0:  # 1x1 kernels need no neighbor rows
        return x
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    zeros = jnp.zeros_like(x[:, :halo])
    if n == 1:
        return jnp.concatenate([zeros, x, zeros], axis=1)
    # my bottom rows become the NEXT shard's top halo
    from_prev = lax.ppermute(
        x[:, -halo:], axis_name, [(i, i + 1) for i in range(n - 1)]
    )
    # my top rows become the PREVIOUS shard's bottom halo
    from_next = lax.ppermute(
        x[:, :halo], axis_name, [(i + 1, i) for i in range(n - 1)]
    )
    top = jnp.where(idx == 0, zeros, from_prev)
    bottom = jnp.where(idx == n - 1, zeros, from_next)
    return jnp.concatenate([top, x, bottom], axis=1)


def _local_conv(x_local, kernel, axis_name: str):
    """Per-shard body: halo exchange + VALID-in-H / SAME-in-W conv."""
    kh, kw = kernel.shape[0], kernel.shape[1]
    halo = (kh - 1) // 2
    x_ext = halo_exchange(x_local, halo, axis_name)
    return lax.conv_general_dilated(
        x_ext,
        kernel,
        window_strides=(1, 1),
        padding=((0, 0), ((kw - 1) // 2, kw // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def spatial_conv2d(
    x: jax.Array,
    kernel: jax.Array,
    mesh: Mesh,
    *,
    spatial_axis: str = AXIS_MODEL,
) -> jax.Array:
    """Stride-1 SAME conv with H sharded over ``mesh[spatial_axis]`` and
    batch over the ``data`` axis; halos move by explicit ring ppermute.

    x: (B, H, W, C) with H divisible by the spatial axis size and the
    kernel (KH, KW, C, O) with odd KH; returns (B, H, W, O) with the
    same sharding as the input.
    """
    spec = P(AXIS_DATA, spatial_axis)
    shmap = shard_map(
        partial(_local_conv, axis_name=spatial_axis),
        mesh=mesh,
        in_specs=(spec, P()),
        out_specs=spec,
    )
    return shmap(
        jax.device_put(x, NamedSharding(mesh, spec)),
        jax.device_put(kernel, NamedSharding(mesh, P())),
    )
