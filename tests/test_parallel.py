"""Explicit ring halo exchange + spatially-sharded conv: numerics vs the
unsharded XLA conv on the virtual 8-device mesh (4 data × 2 spatial).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepvision_tpu.core.mesh import create_mesh
from deepvision_tpu.parallel import halo_exchange, spatial_conv2d


@pytest.fixture(scope="module")
def mesh42():
    return create_mesh(4, 2)


def test_halo_exchange_rows(mesh42):
    """Each shard sees its neighbors' boundary rows; ring edges get
    zeros."""
    n_spatial = 2
    h_local = 4
    x = (
        np.arange(n_spatial * h_local, dtype=np.float32)
        .reshape(1, n_spatial * h_local, 1, 1)
        .repeat(4, axis=0)  # batch divisible by the 4-way data axis
    )

    out = shard_map(
        lambda v: halo_exchange(v, 1, "model"),
        mesh=mesh42,
        in_specs=P("data", "model"),
        out_specs=P("data", "model"),
    )(jax.device_put(
        x, jax.sharding.NamedSharding(mesh42, P("data", "model"))
    ))
    # global result: per shard [halo_top, local, halo_bottom] concatenated
    got = np.asarray(out)[0, :, 0, 0]
    # shard 0 rows 0-3: top halo = 0, bottom halo = row 4
    np.testing.assert_allclose(got[:6], [0, 0, 1, 2, 3, 4])
    # shard 1 rows 4-7: top halo = row 3, bottom halo = 0
    np.testing.assert_allclose(got[6:], [3, 4, 5, 6, 7, 0])


@pytest.mark.parametrize("kh,kw", [(1, 1), (3, 3), (5, 3)])
def test_spatial_conv_matches_unsharded(mesh42, kh, kw):
    r = np.random.default_rng(0)
    x = r.normal(size=(4, 16, 8, 3)).astype(np.float32)
    k = r.normal(size=(kh, kw, 3, 5)).astype(np.float32)

    got = np.asarray(spatial_conv2d(jnp.array(x), jnp.array(k), mesh42))
    want = np.asarray(
        jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    )
    np.testing.assert_allclose(got, want, atol=1e-4)
