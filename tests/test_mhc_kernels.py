"""The hyper-connections' kernels (ops/mhc.py) in the Pallas interpreter
on the CPU, held to the XLA form they stand in for
(models/hyper_latent.HyperMaps, hyper_sublayer); which path a sublayer
takes and the two counters that say so. Their compile for a v5e chip at
the cell's widths is in test_dsa_attention.py (one process of a test run
describes the chip)."""

import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from deepvision_tpu.models import hyper_latent as H  # noqa: E402
from deepvision_tpu.models import transformer as T  # noqa: E402
from deepvision_tpu.obs.metrics import default_registry  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
HC = H.HyperConfig(4, 20, 1e-6, 30.0, 1e-6)
B, N, C = 2, 4, 256


class _Sublayer(nn.Module):
    """One hyper-connected sublayer around ``tanh(u W) + probe``, the
    streams side by side as the model carries them; -> (``X'``, the
    Sinkhorn error, ``u``)."""

    @nn.compact
    def __call__(self, x, probe):
        w = self.param("w", nn.initializers.normal(0.05), (C, C))
        f = lambda u: ((jnp.tanh(jnp.dot(u.astype(F32), w)) + probe).astype(
            BF16), u)
        return H.hyper_sublayer(H.HyperMaps(HC, name="hc"), x, f, BF16)


def _module(remat: bool):
    if not remat:
        return _Sublayer()
    keep = jax.checkpoint_policies.save_only_these_names("attn_out",
                                                         "mla_lse")
    return nn.remat(_Sublayer, prevent_cse=False, policy=keep)()


def _setup(t: int):
    """Seeded streams, cotangent and parameters, the maps' scales and
    biases away from their initial values so that every map moves."""
    k = jax.random.split(jax.random.key(t), 5)
    x = jax.random.normal(k[0], (B, t, N * C), F32).astype(BF16)
    ct = jax.random.normal(k[1], (B, t, N * C), F32).astype(BF16)
    probe = jnp.zeros((B, t, C), F32)
    params = _Sublayer().init(k[2], x, probe)["params"]
    hc = dict(params["hc"], phi=3.0 * params["hc"]["phi"],
              alpha=jnp.array([0.3, 0.5, 0.7], F32),
              b=0.5 * jax.random.normal(k[3], (N * (N + 2),), F32))
    return x, ct, probe, dict(params, hc=hc)


def _run(model, params, x, ct, probe):
    """-> (``X'``, error, ``u``), (d params, ``dX``, ``dy``)."""

    def loss(p, x, probe):
        out, err, u = model.apply({"params": p}, x, probe)
        return jnp.sum(out.astype(F32) * ct.astype(F32)), (out, err, u)

    grads, outs = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(
        params, x, probe)
    return outs, grads


def _gap(got, want):
    got, want = (np.asarray(a, np.float64).ravel() for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("remat,t", [
    (False, 256),           # two tiles of 256 tokens
    (True, 256),            # the sublayer recomputed on the way back
    (False, 200),           # 400 tokens: tiles of 128, padded to 512
])
def test_the_kernels_agree_with_the_xla_form(monkeypatch, remat, t):
    """Forward (``X'``, ``u``, the Sinkhorn error) and the way back
    (``dX``, ``dy``, ``dphi``, ``dalpha``, ``db`` and the sublayer's own
    weight) against the XLA form on the same bf16 streams. The forward
    differs by the rounding of the last bit; the kernels sum the three
    parts of ``dX`` in float32 and round once, where the XLA form rounds
    each part, so ``dX`` and what flows from it differ by bf16's
    rounding, and the maps' gradients by float32's."""
    x, ct, probe, params = _setup(t)
    model = _module(remat)
    (want, want_grads) = _run(model, params, x, ct, probe)
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    (got, grads) = _run(model, params, x, ct, probe)
    for g, w in zip(got[::2], want[::2]):                       # X', u
        np.testing.assert_allclose(np.asarray(g, F32), np.asarray(w, F32),
                                   rtol=2 ** -7, atol=2 ** -7)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-6)
    assert 0 < float(np.max(got[1])) < 1e-2
    (dp, dx, dy), (wp, wx, wy) = grads, want_grads
    for name in ("phi", "alpha", "b"):
        assert _gap(dp["hc"][name], wp["hc"][name]) < 1e-3, name
    assert _gap(dp["w"], wp["w"]) < 1e-2
    assert _gap(dx, wx) < 1e-2
    assert _gap(dy, wy) < 1e-2
    assert dx.dtype == BF16


def test_the_kernels_engage_on_one_tpu_chip_alone(monkeypatch):
    """Not on the CPU; on one TPU chip for 4 bf16 streams whose width
    fills lane rows, whatever the token count; xing4_tiny's width of 64,
    other stream counts and float32 streams take the XLA form."""
    assert not H.mhc_engages(4096, 4, 3584)
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    assert H.mhc_engages(4096, 4, 3584)
    assert H.mhc_engages(400, 4, 256)
    assert not H.mhc_engages(4096, 4, 64)
    assert not H.mhc_engages(4096, 2, 3584)
    assert not H.mhc_engages(4096, 4, 3584, F32)


@pytest.mark.parametrize("on_tpu,counter", [
    (False, "mhc_xla_sites"), (True, "mhc_kernel_sites")])
def test_a_traced_sublayer_counts_one_site(monkeypatch, on_tpu, counter):
    """One sublayer traced once is one site, of the path it took; the
    shape trace of ``init`` is none."""
    monkeypatch.setattr(T, "_on_one_tpu", lambda: on_tpu)
    reg = default_registry()
    names = ("mhc_kernel_sites", "mhc_xla_sites")
    before = {n: reg.value_of(n) for n in names}
    x, _ct, probe, _params = _setup(256)
    params = _Sublayer().init(jax.random.key(0), x, probe)["params"]
    assert {n: reg.value_of(n) for n in names} == before
    jax.make_jaxpr(lambda p: _Sublayer().apply({"params": p}, x, probe))(
        params)
    after = {n: reg.value_of(n) for n in names}
    assert after[counter] == before[counter] + 1
    other = next(n for n in names if n != counter)
    assert after[other] == before[other]
