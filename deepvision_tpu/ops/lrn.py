"""Local Response Normalization (cross-channel), the AlexNet-era op.

The PT reference uses ``nn.LocalResponseNorm`` (ref:
AlexNet/pytorch/models/alexnet_v1.py LRN layers); the TF reference hand-rolls
a Keras layer over ``tf.nn.local_response_normalization`` (ref:
AlexNet/tensorflow/models/alexnet_v2.py:9-24). JAX has no built-in, so this
is written as a windowed reduction over the channel axis — XLA fuses the
square/add/pow chain into one elementwise kernel around the reduce-window,
which is the right TPU lowering for this (rare, bandwidth-bound) op.

Semantics match torch: ``b_c = a_c / (k + (alpha/n) * sum_{c'} a_{c'}^2)^beta``
with the sum over a window of ``n`` channels centered at ``c``.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp


def select_lrn_impl(backend: str, device_count: int) -> tuple[str, str]:
    """-> (implementation, reason) for the default dispatch. The fused
    Pallas kernel (ops/lrn_pallas.py — one VMEM-resident pass instead
    of XLA's reduce_window + elementwise chain) runs on a single-device
    TPU backend only; it compiled natively and matched the jnp lowering
    at every LRN shape of the zoo on a v5e (alexnet1 55x55x96 and
    27x27x256 at size 5, inception1 56x56x64 at size 64 and 56x56x192
    at size 192, batch 128, bf16 and f32, forward and gradient; chip
    run, PR 21), so no shape is excluded."""
    if backend != "tpu":
        return "jnp", f"backend is {backend!r}, the kernel is TPU-only"
    if device_count != 1:
        return "jnp", (
            f"{device_count} devices: a bare pallas_call has no "
            "partitioning rule and would force a gather under a "
            "sharded jit")
    return "pallas", "single-device TPU backend"


def local_response_norm(
    x: jax.Array,
    size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    k: float = 2.0,
    impl: str | None = None,
) -> jax.Array:
    """NHWC input; normalizes over the trailing channel axis.

    ``impl`` ("jnp" | "pallas") overrides :func:`select_lrn_impl`; left
    to the default, the choice is announced once per trace, so a
    four-chip host that runs the jnp lowering says so. Both paths are
    parity-pinned by tests/test_ops.py.
    """
    if impl is None:
        impl, why = select_lrn_impl(jax.default_backend(),
                                    jax.device_count())
        # deliberately a trace-time print of STATIC facts (shape,
        # window, backend): once per compilation, never per step
        print(f"[lrn] {tuple(x.shape)} size={size}: {impl} ({why})",  # jaxlint: disable=JX106
              file=sys.stderr, flush=True)
    if impl == "pallas":
        from deepvision_tpu.ops.lrn_pallas import local_response_norm_pallas

        return local_response_norm_pallas(x, size, alpha, beta, k)
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    sq = x32 * x32
    half = size // 2
    window = [1] * (x.ndim - 1) + [size]
    sums = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add,
        window_dimensions=window,
        window_strides=[1] * x.ndim,
        padding=[(0, 0)] * (x.ndim - 1) + [(half, size - 1 - half)],
    )
    denom = jnp.power(k + (alpha / size) * sums, beta)
    return (x32 / denom).astype(dtype)
