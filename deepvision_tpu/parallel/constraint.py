"""Spatial-sharding guard for thin feature maps.

Round-5 finding: under GSPMD spatial partitioning (input
H sharded over the ``model`` mesh axis), XLA's SPMD partitioner
miscomputes the BACKWARD of strided-conv → residual-block chains once a
feature map's H shard thins to a single row — the forward is exact
(loss matches to 1e-16 in f64) but parameter gradients diverge by up to
68x. Minimal repro: three [ConvBN(stride 2) → DarknetBlock] stages on a
(8, 16, 8, 4) f64 input over a 4x2 (data x model) CPU mesh vs the same
step on 8x1; rel grad error 1.3 at 1-row shards. YOLO's FPN
(upsample+concat) shows the same class of error even at 2-row shards,
so the guard threshold carries a 2x margin.

The guard re-shards thin maps to data-only: :func:`guard_thin_h`
inserts a ``with_sharding_constraint`` dropping the H sharding when
``H // model_shards < min_rows``. This is also the PERFORMANT choice —
at a few rows per shard the halo exchange dominates the conv compute,
so deep low-resolution stages want data-only sharding regardless; the
spatial mesh axis earns its keep on the high-resolution stages.

The mesh is communicated via a TRACE-TIME thread-local
(:func:`spatial_mesh_scope`): the compiled-step factories in core/step
enter it around the traced step function, so every model traced through
them sees the mesh, while execution-time behavior (argument resharding,
donation) is completely untouched. Raw ``jax.jit`` users wrap their
step function body in ``with spatial_mesh_scope(mesh): ...``. Without
a scope the guard is a no-op, so annotated models remain valid
single-device programs.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepvision_tpu.core.mesh import AXIS_DATA, AXIS_MODEL

_tls = threading.local()


@contextmanager
def spatial_mesh_scope(mesh: Mesh):
    """Expose ``mesh`` to :func:`guard_thin_h` for the duration of a
    trace. Nestable; re-entrant per thread."""
    prev = getattr(_tls, "mesh", None)
    _tls.mesh = mesh
    try:
        yield
    finally:
        _tls.mesh = prev


def current_spatial_mesh() -> Mesh | None:
    return getattr(_tls, "mesh", None)


def spatial_model_shards() -> int:
    """Size of the scoped mesh's ``model`` axis (1 when no scope is
    active or the mesh has no model axis)."""
    mesh = current_spatial_mesh()
    if mesh is not None and AXIS_MODEL in mesh.axis_names:
        return int(mesh.shape[AXIS_MODEL])
    return 1


# Minimum H rows per model-axis shard before a map is forced back to
# data-only sharding. 1-row shards are the proven-broken regime; 2-row
# shards measured exact in plain chains but NOT in the YOLO FPN's
# upsample+concat graph (f64 parity harness, tests/test_spatial.py) — 4 holds
# across every architecture tested and doubles as the point where halo
# overhead stops paying for itself anyway.
MIN_ROWS_PER_SHARD = 4


def guard_thin_h(x, min_rows: int = MIN_ROWS_PER_SHARD):
    """Constrain ``x`` (NHWC) to data-only sharding when H-sharding it
    over the scoped mesh's model axis would leave < ``min_rows`` rows
    per shard (the XLA SPMD backward-miscomputation regime). No-op
    outside a :func:`spatial_mesh_scope`."""
    mesh = current_spatial_mesh()
    shards = spatial_model_shards()
    if mesh is None or shards <= 1 or x.ndim < 3:
        return x
    if x.shape[1] // shards >= min_rows:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(AXIS_DATA, *([None] * (x.ndim - 1)))))
