"""The latent-attention kernels (ops/dsa_attention.py, ``latent_*``) in
the Pallas interpreter on the CPU, held to the XLA form they stand in
for (models/latent_moe._attend, causal_attention); which path a call
site takes; what a recomputed layer keeps; and that the sibling's
kernels, which share the module, trace to the operations they traced to
before the module was shared. The kernels' compile for a described v5e
sits in tests/test_dsa_attention.py with the sibling's: one process of a
test run describes the chip."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from deepvision_tpu.models import get_model  # noqa: E402
from deepvision_tpu.models import latent_moe as L  # noqa: E402
from deepvision_tpu.models import transformer as T  # noqa: E402
from deepvision_tpu.obs.metrics import default_registry  # noqa: E402
from test_dsa_attention import (  # noqa: E402, F401
    _gap,
    _kernel_calls,
    _params,
    as_on_one_tpu,
)

F32, BF16 = jnp.float32, jnp.bfloat16
FIXTURE = ROOT / "tests/fixtures/keye_dsa_kernels.jaxpr.txt"
KANANA_FIXTURE = ROOT / "tests/fixtures/kanana_mla_kernels.jaxpr.txt"


def _dsa():
    from deepvision_tpu.ops import dsa_attention

    return dsa_attention


def _sites():
    reg = default_registry()
    return (reg.value_of("mla_kernel_sites"), reg.value_of("mla_xla_sites"))


def _whole(q, q_rope, k, k_rope, v):
    """The XLA form's operands: ``[q | q_rope]``, and the one rotary key
    in every head's ``[k | k_rope]``."""
    heads = q.shape[-2]
    k_rope = jnp.broadcast_to(k_rope[..., None, :],
                              (*k.shape[:-2], heads, k_rope.shape[-1]))
    return (jnp.concatenate([q, q_rope], -1),
            jnp.concatenate([k, k_rope], -1), v)


# ------------------------------------------------- one chunk, the kernels

# name: (queries, keys, first query's position, heads, dn, dr, dv)
CHUNKS = {
    "one_tile": (128, 128, 0, 4, 128, 64, 128),
    "first_tiles_live_then_the_diagonal": (128, 384, 256, 4, 128, 64, 128),
    "tiles_above_the_diagonal": (128, 640, 130, 4, 128, 64, 128),
    "key_tiles_of_512": (256, 1024, 512, 2, 128, 64, 128),
    "two_steps_of_eight_heads": (128, 256, 128, 16, 128, 64, 128),
    "wider_heads_narrower_values": (128, 256, 128, 2, 256, 128, 128),
}


def _chunk(name, dtype):
    tq, keys, t0, heads, dn, dr, dv = CHUNKS[name]
    ks = jax.random.split(jax.random.key(len(name)), 6)
    normal = lambda k, *s: jax.random.normal(k, s, F32).astype(dtype)
    # keys and values hold rows past the chunk's keys: never read
    rows = keys + 128
    return (normal(ks[0], tq, heads, dn), normal(ks[1], tq, heads, dr),
            normal(ks[2], rows, heads, dn), normal(ks[3], rows, dr),
            normal(ks[4], rows, heads, dv), normal(ks[5], tq, heads * dv))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", list(CHUNKS))
def test_the_latent_kernels_match_the_xla_form_on_a_chunk(name, dtype, tol):
    """Output, log-sum-exp and the gradients to q, q_rope, k, k_rope, v."""
    dsa = _dsa()
    dtype = jnp.dtype(dtype)
    tq, keys, t0, heads, dn, dr, dv = CHUNKS[name]
    q, q_rope, k, k_rope, v, do = _chunk(name, dtype)
    mask = T._causal(t0, tq, keys)

    def xla(q, q_rope, k, k_rope, v):
        return L._attend(*_whole(q, q_rope, k[:keys], k_rope[:keys],
                                 v[:keys]), mask, dtype)

    want_o, pull = jax.vjp(xla, q, q_rope, k, k_rope, v)
    want = pull(do)
    qw, kw, _ = _whole(*(a.astype(F32) for a in (q, q_rope, k[:keys],
                                                 k_rope[:keys], v[:keys])))
    logits = jnp.einsum("thd,shd->hts", qw, kw, precision="highest")
    want_lse = jax.nn.logsumexp(
        jnp.where(mask, logits / np.sqrt(dn + dr), -jnp.inf), -1)

    flat = lambda a: a.reshape(a.shape[0], -1)
    kmax = jnp.max(dsa.latent_key_norms(flat(k), k_rope, heads)[:keys], 0)
    o, lse = dsa.latent_forward(flat(q), flat(q_rope), flat(k), k_rope,
                                flat(v), kmax, t0, keys=keys, interpret=True)
    assert o.dtype == dtype and o.shape == (tq, heads * dv)
    assert lse.dtype == F32 and lse.shape == (heads, tq)
    assert _gap(o, want_o) < tol
    assert _gap(lse, want_lse) < 2e-5 + tol / 10

    di = jnp.sum((o.astype(F32) * do.astype(F32)).reshape(tq, heads, dv),
                 -1).T
    # sums over the chunks so far: this chunk's part is added in place,
    # and rows past the chunk's keys are left alone
    before = lambda a: jnp.full(flat(a).shape, 0.5, F32)
    dq, dq_rope, dk, dk_rope, dv_ = dsa.latent_backward(
        flat(q), flat(q_rope), flat(k), k_rope, flat(v), t0, lse, di, do,
        before(k), before(k_rope), before(v), keys=keys, interpret=True)
    assert dq.dtype == dtype and dq_rope.dtype == dtype
    assert _gap(dq, flat(want[0])) < tol
    assert _gap(dq_rope, flat(want[1])) < tol
    for got, w in ((dk, want[2]), (dk_rope, want[3]), (dv_, want[4])):
        assert got.dtype == F32
        assert _gap(got[:keys] - 0.5, flat(w)[:keys]) < tol
        assert np.all(np.asarray(got[keys:]) == 0.5)


# ---------------------------------------- a batch, through the model's path


def _batch_of(t, heads, dtype, rows=2, dn=128, dr=64, dv=128):
    ks = jax.random.split(jax.random.key(t), 5)
    normal = lambda k, *s: jax.random.normal(k, s, F32).astype(dtype)
    return (normal(ks[0], rows, t, heads, dn), normal(ks[1], rows, t, heads, dr),
            normal(ks[2], rows, t, heads, dn), normal(ks[3], rows, t, dr),
            normal(ks[4], rows, t, heads, dv))


SEQUENCES = {
    # name: (length, heads, key_block, q_chunk)
    "one_block": (128, 4, 128, 128),
    "blocks_and_chunks": (512, 4, 256, 128),
    "key_tiles_of_512": (1024, 2, 1024, 256),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", list(SEQUENCES))
def test_kernel_attention_matches_causal_attention(name, dtype, tol):
    """Output and the gradients to all five inputs, whole sequences."""
    t, heads, key_block, q_chunk = SEQUENCES[name]
    dtype = jnp.dtype(dtype)
    args = _batch_of(t, heads, dtype)
    weights = jax.random.normal(jax.random.key(9), (2, t, heads * 128), F32)

    def scalar(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o.astype(F32) * weights), o
        return jax.jit(jax.value_and_grad(loss, range(5), has_aux=True))

    xla = scalar(lambda *a: jax.lax.map(lambda b: L.causal_attention(
        *b, key_block=key_block, q_chunk=q_chunk, dtype=dtype), _whole(*a)))
    kernel = scalar(lambda *a: L.kernel_attention(*a, key_block, q_chunk))
    (_, want_o), want = xla(*args)
    (_, o), got = kernel(*args)
    assert o.dtype == dtype and _gap(o, want_o) < tol
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert _gap(g, w) < tol


def test_four_held_heads_and_the_callers_scale():
    """The hyper-connected model's share: 4 heads (the backward takes all
    four a grid step, as 8 do not divide them) and yarn's softmax scale
    (2.00475 / sqrt(192)), output and gradients against the XLA form
    with the same scale."""
    import math

    t, heads, key_block, q_chunk = 256, 4, 128, 128
    scale = (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192)
    args = _batch_of(t, heads, F32)
    weights = jax.random.normal(jax.random.key(9), (2, t, heads * 128), F32)

    def scalar(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o * weights), o
        return jax.jit(jax.value_and_grad(loss, range(5), has_aux=True))

    xla = scalar(lambda *a: jax.lax.map(lambda b: L.causal_attention(
        *b, key_block=key_block, q_chunk=q_chunk, dtype=F32, scale=scale),
        _whole(*a)))
    kernel = scalar(lambda *a: L.kernel_attention(*a, key_block, q_chunk,
                                                  scale))
    (_, want_o), want = xla(*args)
    (_, o), got = kernel(*args)
    assert _gap(o, want_o) < 2e-5
    for g, w in zip(got, want):
        assert _gap(g, w) < 2e-5
    # without the scale the result is another one
    (_, plain_o), _ = scalar(lambda *a: L.kernel_attention(
        *a, key_block, q_chunk))(*args)
    assert _gap(plain_o, want_o) > 1e-2


@pytest.mark.parametrize("heads,steps", [
    (32, (4, 8)), (16, (4, 8)), (4, (4, 4)), (6, (3, 6)), (2, (2, 2))])
def test_heads_a_grid_step_divide_the_heads_held(heads, steps):
    dsa = _dsa()
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    q, q_rope = shape(128, heads * 128), shape(128, heads * 64)
    k_rope, v = shape(512, 64), shape(512, heads * 128)
    got = tuple(dsa._latent_sizes(q, q_rope, k_rope, v, None, most)[3]
                for most in (dsa.FORWARD_HEADS, dsa.BACKWARD_HEADS))
    assert got == steps


# ------------------------------------------------- which path a site takes

# the tiny preset with heads the kernels take: 256 positions, two key blocks
LANE_WIDE = dict(heads=2, nope_dim=128, rope_dim=64, v_dim=128,
                 key_block=128, q_chunk=128)


def _tokens(text_len=257, rows=2):
    rng = np.random.RandomState(0)
    return {"tokens": rng.randint(0, 128, (rows, text_len)).astype(np.int32)}


def _loss(model, batch):
    def loss(params):
        out = model.apply({"params": params}, batch, train=True)
        return jnp.mean(out["nll"]), out
    return loss


@pytest.mark.parametrize("t,nope,rope,v,key_block,q_chunk,engages", [
    (8192, 128, 64, 128, 2048, 512, True),      # the benchmark cell's
    (1024, 256, 128, 128, 512, 128, True),
    (64, 16, 8, 16, 32, 8, False),              # kanana2_tiny: a chunk of 8
    (1024, 128, 64, 128, 512, 8, False),
    (1024, 128, 32, 128, 512, 128, False),      # a quarter lane row of rotary
    (1024, 192, 64, 128, 512, 128, False),
    (1024, 128, 64, 64, 512, 128, False),
    (1000, 128, 64, 128, 512, 128, False),      # taken in one chunk of 1000
])
def test_shapes_that_do_not_tile_take_the_xla_form(
        as_on_one_tpu, t, nope, rope, v, key_block, q_chunk, engages):
    assert L.mla_engages(t, nope, rope, v, key_block, q_chunk) is engages


@pytest.mark.parametrize("backend,devices,engages", [
    ("tpu", 1, True), ("tpu", 2, False), ("cpu", 1, False)])
def test_the_kernels_engage_on_one_tpu_chip_alone(
        monkeypatch, backend, devices, engages):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert L.mla_engages(8192, 128, 64, 128, 2048, 512) is engages


@pytest.mark.parametrize("why,kwargs,text_len", [
    ("cpu_backend", LANE_WIDE, 257),
    ("heads_not_lane_wide", {}, 65),
])
def test_the_xla_form_runs_where_the_kernels_do_not_apply(
        why, kwargs, text_len, monkeypatch):
    if why != "cpu_backend":
        monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    model = get_model("kanana2_tiny", dtype=F32, **kwargs)
    before = _sites()
    params = _params(model)
    assert _sites() == before       # the shape trace of init is no site
    jaxpr = jax.make_jaxpr(lambda p, b: model.apply({"params": p}, b))(
        params, _tokens(text_len))
    after = _sites()
    assert not _kernel_calls(jaxpr)
    # the dense layer's site and the scanned body's, once a trace of each
    assert after[0] == before[0] and after[1] >= before[1] + 2


def test_the_model_takes_the_kernels_and_agrees_with_the_xla_form(
        monkeypatch):
    """Loss, counts and every parameter's gradient of the two paths,
    the layers recomputed on the way back (``remat='layer'``)."""
    model = get_model("kanana2_tiny", dtype=F32, **LANE_WIDE)
    params, batch = _params(model), _tokens()
    step = jax.jit(jax.value_and_grad(_loss(model, batch), has_aux=True))
    (want, want_out), want_grads = step(params)
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    before = _sites()
    step = jax.jit(jax.value_and_grad(_loss(model, batch), has_aux=True))
    (got, out), grads = step(params)
    assert _sites()[0] > before[0] and _sites()[1] == before[1]
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_array_equal(out["expert_counts"],
                                  want_out["expert_counts"])
    np.testing.assert_allclose(out["nll"], want_out["nll"], rtol=2e-4,
                               atol=2e-5)
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    got, want = flat(grads), flat(want_grads)
    floor = float(np.median([np.linalg.norm(v) for v in want.values()]))
    for name, w in want.items():
        gap = np.linalg.norm(got[name] - w) / max(np.linalg.norm(w), floor)
        assert gap < 2e-4, (name, gap)


def test_a_recomputed_layer_keeps_the_log_sum_exp(as_on_one_tpu):
    """Forward and backward of the recomputed layers (the dense one and
    the scanned body): the forward kernel once a key block (two here) a
    layer, in the forward pass only; the backward kernel once a key
    block. Without ``mla_lse`` among the names a layer keeps, the way
    back would run the forward kernel again just to have it."""
    model = get_model("kanana2_tiny", dtype=F32, **LANE_WIDE)
    params, batch = _params(model), _tokens()
    forward = _kernel_calls(jax.make_jaxpr(_loss(model, batch))(params))
    assert forward == {"mla_attention_forward": 4}
    both = _kernel_calls(jax.make_jaxpr(
        jax.grad(_loss(model, batch), has_aux=True))(params))
    assert both == {"mla_attention_forward": 4, "mla_attention_backward": 4}


# --------------------------- the sibling's kernels, which share the module


def keye_kernels_jaxpr() -> str:
    """``dsa.forward`` and ``dsa.backward`` traced at the Keye cell's
    shapes (512 queries of 32 heads over 4 key/value heads of 128, bf16,
    a key block's first extent): every operation of the three kernels,
    their grids, blocks and each block's index map."""
    dsa = _dsa()
    tq, keys, heads, groups, hd = 512, 2048, 32, 4, 128
    shape = jax.ShapeDtypeStruct
    q, kv = shape((tq, heads * hd), BF16), shape((keys, groups * hd), BF16)
    scores, thr = shape((tq, keys), F32), shape((tq,), F32)
    t0, rows = shape((), jnp.int32), shape((heads, tq), F32)
    sums = shape((8192, groups * hd), F32)
    traced = [
        jax.make_jaxpr(lambda *a: dsa.forward(*a, interpret=False))(
            q, kv, kv, scores, thr, t0),
        jax.make_jaxpr(lambda *a: dsa.backward(*a, interpret=False))(
            q, kv, kv, scores, thr, t0, rows, rows, q, sums, sums)]
    lines = []
    for jaxpr in traced:
        lines.append(str(jaxpr))
        for eqn in jaxpr.jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                lines += [f"{eqn.params['name']} index map: "
                          f"{bm.index_map_jaxpr}"
                          for bm in eqn.params["grid_mapping"].block_mappings]
    return "\n".join(lines) + "\n"


def test_keyes_kernels_trace_to_the_operations_they_were():
    """The fixture was written by this function on the parent of the PR
    that put the latent kernels into the module (PR 36): an edit to the
    shared helpers that changes what Keye's cell runs fails here. To
    change Keye's kernels on purpose, write the fixture again
    (``python tests/test_mla_attention.py``) and measure its cell."""
    assert keye_kernels_jaxpr() == FIXTURE.read_text()


def kanana_kernels_jaxpr() -> str:
    """``latent_forward`` and ``latent_backward`` traced at the kanana
    cell's shapes (512 queries of 32 heads, 128 + 64 wide for scores and
    128 for values, over the first 2,048 of 8,192 keys, bf16, the
    default scale): every operation of the two kernels, their grids,
    blocks and each block's index map."""
    dsa = _dsa()
    tq, keys, heads, dn, dr, dv = 512, 2048, 32, 128, 64, 128
    shape = jax.ShapeDtypeStruct
    q, qr = shape((tq, heads * dn), BF16), shape((tq, heads * dr), BF16)
    k, kr = shape((8192, heads * dn), BF16), shape((8192, dr), BF16)
    v, kmax = shape((8192, heads * dv), BF16), shape((heads,), F32)
    t0, rows = shape((), jnp.int32), shape((heads, tq), F32)
    do = shape((tq, heads * dv), BF16)
    sums = [shape((8192, heads * dn), F32), shape((8192, dr), F32),
            shape((8192, heads * dv), F32)]
    traced = [
        jax.make_jaxpr(lambda *a: dsa.latent_forward(
            *a, keys=keys, interpret=False))(q, qr, k, kr, v, kmax, t0),
        jax.make_jaxpr(lambda *a: dsa.latent_backward(
            *a, keys=keys, interpret=False))(
            q, qr, k, kr, v, t0, rows, rows, do, *sums)]
    lines = []
    for jaxpr in traced:
        lines.append(str(jaxpr))
        for eqn in jaxpr.jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                lines += [f"{eqn.params['name']} index map: "
                          f"{bm.index_map_jaxpr}"
                          for bm in eqn.params["grid_mapping"].block_mappings]
    return "\n".join(lines) + "\n"


def test_kananas_kernels_trace_to_the_operations_they_were():
    """The fixture was written by this function on the parent of the PR
    that let the latent kernels take a caller's scale and any number of
    heads (PR 38): kanana's cell runs the same kernels, its default scale
    the same constant. To change them on purpose, write the fixture again
    (``python tests/test_mla_attention.py``) and measure the cell."""
    assert kanana_kernels_jaxpr() == KANANA_FIXTURE.read_text()


if __name__ == "__main__":
    FIXTURE.parent.mkdir(exist_ok=True)
    FIXTURE.write_text(keye_kernels_jaxpr())
    KANANA_FIXTURE.write_text(kanana_kernels_jaxpr())
