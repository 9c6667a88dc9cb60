#!/usr/bin/env python
"""Standalone reproducer: XLA GSPMD miscomputes the backward of
strided-conv + residual chains under thin spatial (H) sharding.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/spmd_thin_h_repro.py

Everything runs in float64 on 8 virtual CPU devices, comparing one
train-style grad computation on a 4x2 (data x model, H-sharded) mesh
against the same computation on an 8x1 (data-only) mesh:

- the LOSS matches across meshes to ~1e-16 (forward exact);
- the parameter GRADIENTS diverge by O(1) relative error once the
  deepest feature map thins to one H row per shard;
- re-sharding thin maps to data-only via with_sharding_constraint
  (what deepvision_tpu.parallel.constraint.guard_thin_h does) restores
  gradient parity to ~1e-15.

Single blocks at the same shapes are exact — the chain is required —
which is why this escaped the usual per-op SPMD unit tests. Found by
tests/test_spatial.py's f64 YOLO parity test (round 5).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepvision_tpu.core import create_mesh
from deepvision_tpu.models.layers import ConvBN
from deepvision_tpu.models.yolo import DarknetBlock, leaky
from deepvision_tpu.train.state import create_train_state


class Chain(nn.Module):
    """n x [ConvBN(3x3, stride 2, leaky) -> DarknetBlock] — the minimal
    failing pattern. ``constrain``: un-H-shard maps once H <= value
    (0 = never), mimicking guard_thin_h."""

    n: int = 3
    constrain: int = 0

    @nn.compact
    def __call__(self, x, train=False):
        d = jnp.float64
        for i in range(self.n):
            x = ConvBN(4, (3, 3), strides=(2, 2), act=leaky, dtype=d,
                       name=f"down{i}")(x, train)
            if self.constrain and x.shape[1] <= self.constrain:
                try:
                    x = jax.lax.with_sharding_constraint(
                        x, P("data", None, None, None))
                except RuntimeError:
                    pass  # no mesh in context (model.init trace)
            x = DarknetBlock(4, dtype=d, name=f"blk{i}")(x, train)
        return x


def run(model, images, spatial):
    mesh = create_mesh(4, 2) if spatial else create_mesh(8, 1)
    state = create_train_state(model, optax.sgd(0.01), images[:1], rng=0)
    state = state.replace(
        params=jax.tree.map(lambda a: a.astype(jnp.float64), state.params),
        batch_stats=jax.tree.map(lambda a: a.astype(jnp.float64),
                                 state.batch_stats),
    )
    img_spec = P("data", "model", None, None) if spatial else P("data")
    img_sh = NamedSharding(mesh, img_spec)
    rep = NamedSharding(mesh, P())

    def f(params, img):
        out, _ = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            img, train=True, mutable=["batch_stats"])
        return jnp.sum(out ** 2)

    with mesh:  # mesh context resolves the bare-P constraint
        loss, g = jax.jit(
            jax.value_and_grad(f), in_shardings=(rep, img_sh),
            out_shardings=(rep, rep),
        )(state.params, jax.device_put(images, img_sh))
    flat = np.concatenate([np.ravel(v) for v in jax.tree.leaves(g)])
    return float(loss), flat


def compare(tag, model, images):
    loss_ref, g_ref = run(model, images, spatial=False)
    loss_sp, g_sp = run(model, images, spatial=True)
    loss_rel = abs(loss_ref - loss_sp) / abs(loss_ref)
    grad_rel = float(np.max(np.abs(g_ref - g_sp))
                     / (np.max(np.abs(g_ref)) + 1e-30))
    print(f"{tag:28s} loss rel diff {loss_rel:9.2e}   "
          f"grad rel diff {grad_rel:9.2e}")
    return loss_rel, grad_rel


def main():
    rng = np.random.default_rng(0)
    images = rng.normal(size=(8, 16, 8, 4)).astype(np.float64)

    print(f"jax {jax.__version__}; devices: {len(jax.devices())} cpu\n")
    l1, g1 = compare("chain (1-row H shards)", Chain(n=3), images)
    l2, g2 = compare("chain + thin-H guard", Chain(n=3, constrain=2),
                     images)
    print()
    assert l1 < 1e-12 and l2 < 1e-12, \
        "forward should be exact in BOTH configurations"
    if g2 >= 1e-10:
        print(f"GUARD REGRESSION: guarded grads still diverge ({g2:.2g})"
              " — the thin-H re-shard no longer restores parity.")
        sys.exit(2)
    if g1 < 1e-10:
        print("NOT reproduced on this jax/XLA version — the upstream "
              "bug may be fixed; guard_thin_h is then harmless.")
        sys.exit(1)
    print("REPRODUCED: forward exact, backward diverges "
          f"{g1:.2g}x under thin H shards; guard restores parity.")


if __name__ == "__main__":
    main()
