"""The latent-attention kernels (ops/dsa_attention.py, ``latent_*``) in
the Pallas interpreter on the CPU, held to the XLA form they stand in
for (models/latent_moe._attend, causal_attention); which path a call
site takes; what a recomputed layer keeps; and that the sibling's
kernels, which share the module, trace to the operations they traced to
before the module was shared. The kernels' compile for a described v5e
sits in tests/test_dsa_attention.py with the sibling's: one process of a
test run describes the chip."""

import collections
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


# ----------------------------------------------- a batch, the kernels alone

SCALE = float((0.1 * np.log(64) + 1) ** 2 / np.sqrt(192))  # yarn's


def _batch_of(t, heads, dtype, rows=2, dn=128, dr=64, dv=128):
    """The kernels' operands as the projections write them: ``q [B, T,
    heads x dn]``, ``q_rope [B, T, heads x dr]``, ``kv [B, T, heads x (dn
    + dv)]`` (each head's key, then its values), ``k_rope [B, T, dr]``."""
    ks = jax.random.split(jax.random.key(t + heads), 4)
    normal = lambda k, *s: jax.random.normal(k, s, F32).astype(dtype)
    return (normal(ks[0], rows, t, heads * dn),
            normal(ks[1], rows, t, heads * dr),
            normal(ks[2], rows, t, heads * (dn + dv)),
            normal(ks[3], rows, t, dr))


def _xla_form(heads, key_block, q_chunk, dtype, scale=None):
    """:func:`causal_attention` of every sequence, on the kernels'
    operands: ``(q, q_rope, kv, k_rope) -> [B, T, heads x dv]``."""
    def attention(q, q_rope, kv, k_rope):
        split = lambda a: a.reshape(*a.shape[:2], heads, -1)
        q, q_rope, kv = split(q), split(q_rope), split(kv)
        k, v = kv[..., :q.shape[-1]], kv[..., q.shape[-1]:]
        return jax.lax.map(lambda a: L.causal_attention(
            *a, key_block=key_block, q_chunk=q_chunk, dtype=dtype,
            scale=scale), _whole(q, q_rope, k, k_rope, v))
    return attention


def _exact_lse(q, q_rope, kv, k_rope, heads, scale):
    """``[B, heads, T]``: each causal row's log-sum-exp in float32."""
    split = lambda a: a.astype(F32).reshape(*a.shape[:2], heads, -1)
    q, q_rope, kv = split(q), split(q_rope), split(kv)
    dn = q.shape[-1]
    qw, kw, _ = _whole(q, q_rope, kv[..., :dn], k_rope.astype(F32), kv)
    logits = jnp.einsum("bthd,bshd->bhts", qw, kw, precision="highest")
    t = q.shape[1]
    return jax.nn.logsumexp(jnp.where(
        T._causal(0, t, t), logits * scale, -jnp.inf), -1)


# name: (length, heads, key_block, q_chunk, dn, dr, dv, scale), 2 sequences
CASES = {
    "one_tile": (128, 4, 128, 128, 128, 64, 128, None),
    # key tiles of 128: chunk c sees c whole tiles, then the diagonal
    "first_tiles_live_then_the_diagonal": (768, 4, 384, 128, 128, 64, 128,
                                           None),
    # a chunk over two tiles of 128, the diagonal through both
    "tiles_above_the_diagonal": (768, 4, 256, 256, 128, 64, 128, None),
    # two chunks a tile of 512
    "key_tiles_of_512": (1024, 2, 512, 256, 128, 64, 128, None),
    "two_steps_of_eight_heads": (512, 16, 256, 128, 128, 64, 128, None),
    "wider_heads_narrower_values": (256, 2, 128, 128, 256, 128, 128, None),
    # the kanana cell's head steps, 4 forward and 8 backward
    "thirty_two_heads_and_the_callers_scale": (512, 32, 256, 128, 128, 64,
                                               128, SCALE),
    # chunks of 384 against tiles of 512: a tile's first chunk begins
    # before it, a chunk's last tile ends after it
    "chunks_that_straddle_tiles": (1536, 2, 768, 384, 128, 64, 128, None),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", list(CASES))
def test_the_latent_kernels_match_the_xla_form_on_a_chunk(name, dtype, tol):
    """Every chunk of two sequences in one call each way: the output,
    log-sum-exp and the cotangents of ``q``, ``q_rope``, ``kv`` (in its
    layout) and ``k_rope`` against :func:`causal_attention` and its
    ``jax.vjp``."""
    dsa = _dsa()
    dtype = jnp.dtype(dtype)
    t, heads, key_block, q_chunk, dn, dr, dv, scale = CASES[name]
    args = _batch_of(t, heads, dtype, dn=dn, dr=dr, dv=dv)
    do = jax.random.normal(jax.random.key(5), (2, t, heads * dv),
                           F32).astype(dtype)
    want_o, pull = jax.vjp(_xla_form(heads, key_block, q_chunk, dtype,
                                     scale), *args)
    want = pull(do)
    want_lse = _exact_lse(*args, heads,
                          1 / np.sqrt(dn + dr) if scale is None else scale)

    block, chunk = T._blocks(t, key_block, q_chunk)
    kmax = dsa.latent_key_norms(args[2], args[3], heads, dn, block)
    assert kmax.shape == (2, t // block, heads)
    o, lse = dsa.latent_forward(*args, kmax, q_chunk=chunk, scale=scale,
                                interpret=True)
    assert o.dtype == dtype and o.shape == (2, t, heads * dv)
    assert lse.dtype == F32 and lse.shape == (2, heads, t)
    assert _gap(o, want_o) < tol
    assert _gap(lse, want_lse) < 2e-5 + tol / 10

    di = jnp.sum((o.astype(F32) * do.astype(F32)).reshape(2, t, heads, dv),
                 -1)
    got = dsa.latent_backward(*args, lse, jnp.swapaxes(di, 1, 2), do,
                              q_chunk=chunk, scale=scale, interpret=True)
    for g, w, a in zip(got, want, args):
        assert g.dtype == dtype and g.shape == a.shape
        assert _gap(g, w) < tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_forward_is_its_chunks_bit_for_bit(dtype):
    """Each chunk alone, over its sequence's keys up to its last key
    tile, at the bound of its key block: the batched call's output and
    log-sum-exp are those bits (two sequences, two key blocks of two
    chunks, two chunks a tile of 512)."""
    dsa = _dsa()
    t, heads, block, chunk = 1024, 4, 512, 256
    q, q_rope, kv, k_rope = _batch_of(t, heads, jnp.dtype(dtype))
    kmax = dsa.latent_key_norms(kv, k_rope, heads, 128, block)
    o, lse = dsa.latent_forward(q, q_rope, kv, k_rope, kmax, q_chunk=chunk,
                                interpret=True)
    tile = dsa.key_tile(t)
    for i in range(2):
        for c in range(t // chunk):
            end = -(-(c + 1) * chunk // tile) * tile
            part = lambda a: a[i:i + 1, :end]
            bound = kmax[i:i + 1, c * chunk // block][:, None]
            o_c, lse_c = dsa.latent_forward(
                part(q), part(q_rope), part(kv), part(k_rope), bound,
                q_chunk=chunk, interpret=True)
            rows = slice(c * chunk, (c + 1) * chunk)
            np.testing.assert_array_equal(np.asarray(o_c[0, rows], F32),
                                          np.asarray(o[i, rows], F32))
            np.testing.assert_array_equal(lse_c[0, :, rows], lse[i, :, rows])


@pytest.mark.parametrize("t,chunk,tile,pairs", [
    (8192, 512, 512, 136), (2048, 512, 512, 10), (1536, 384, 512, 9),
    (768, 256, 128, 12)])
def test_the_grid_visits_the_live_pairs_alone(t, chunk, tile, pairs):
    """Each (chunk, tile) pair whose tile holds a key at or below the
    chunk's last query, once, in both orders."""
    dsa = _dsa()
    by_chunk, by_tile = (list(zip(*dsa.live_pairs(
        t, chunk, tile, by_tile=order))) for order in (False, True))
    want = {(c, kk) for c in range(t // chunk) for kk in range(t // tile)
            if kk * tile <= c * chunk + chunk - 1}
    assert set(by_chunk) == set(by_tile) == want and len(want) == pairs
    assert by_chunk == sorted(want)
    assert by_tile == sorted(want, key=lambda pair: pair[::-1])


# ---------------------------------------- a batch, through the model's path


SEQUENCES = {
    # name: (length, heads, key_block, q_chunk)
    "one_block": (128, 4, 128, 128),
    "blocks_and_chunks": (512, 4, 256, 128),
    "key_tiles_of_512": (1024, 2, 1024, 256),
}


def _value_and_grads(fn, weights):
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(F32) * weights), o
    return jax.jit(jax.value_and_grad(loss, range(4), has_aux=True))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", list(SEQUENCES))
def test_kernel_attention_matches_causal_attention(name, dtype, tol):
    """Output and the gradients to all four inputs, whole sequences."""
    t, heads, key_block, q_chunk = SEQUENCES[name]
    dtype = jnp.dtype(dtype)
    args = _batch_of(t, heads, dtype)
    weights = jax.random.normal(jax.random.key(9), (2, t, heads * 128), F32)
    xla = _value_and_grads(_xla_form(heads, key_block, q_chunk, dtype),
                           weights)
    kernel = _value_and_grads(
        lambda *a: L.kernel_attention(*a, key_block, q_chunk), weights)
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
    t, heads, key_block, q_chunk = 256, 4, 128, 128
    args = _batch_of(t, heads, F32)
    weights = jax.random.normal(jax.random.key(9), (2, t, heads * 128), F32)
    xla = _value_and_grads(_xla_form(heads, key_block, q_chunk, F32, SCALE),
                           weights)
    kernel = _value_and_grads(
        lambda *a: L.kernel_attention(*a, key_block, q_chunk, SCALE), weights)
    (_, want_o), want = xla(*args)
    (_, o), got = kernel(*args)
    assert _gap(o, want_o) < 2e-5
    for g, w in zip(got, want):
        assert _gap(g, w) < 2e-5
    # without the scale the result is another one
    (_, plain_o), _ = _value_and_grads(lambda *a: L.kernel_attention(
        *a, key_block, q_chunk), weights)(*args)
    assert _gap(plain_o, want_o) > 1e-2


@pytest.mark.parametrize("heads,steps", [
    (32, (4, 8)), (16, (4, 8)), (4, (4, 4)), (6, (3, 6)), (2, (2, 2))])
def test_heads_a_grid_step_divide_the_heads_held(heads, steps):
    dsa = _dsa()
    got = tuple(dsa.head_step(heads, most)
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
    the scanned body): one forward kernel call a layer, in the forward
    pass only, and one backward kernel call a layer. Without ``mla_lse``
    among the names a layer keeps, the way back would run the forward
    kernel again just to have it."""
    model = get_model("kanana2_tiny", dtype=F32, **LANE_WIDE)
    params, batch = _params(model), _tokens()
    forward = _kernel_calls(jax.make_jaxpr(_loss(model, batch))(params))
    assert forward == {"mla_attention_forward": 2}
    both = _kernel_calls(jax.make_jaxpr(
        jax.grad(_loss(model, batch), has_aux=True))(params))
    assert both == {"mla_attention_forward": 2, "mla_attention_backward": 2}


def _scope_operations(jaxpr, scope: str) -> collections.Counter:
    """Operations of a traced function under the named scope ``scope``,
    by primitive: loops' bodies and recomputed regions included, a
    kernel's body not."""
    counts = collections.Counter()

    def walk(jp, inside):
        for eqn in jp.eqns:
            here = inside or scope in str(eqn.source_info.name_stack)
            counts[eqn.primitive.name] += here
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, here)

    walk(jaxpr.jaxpr, False)
    return +counts


def test_nothing_loops_slices_or_concatenates_around_the_kernels(
        as_on_one_tpu):
    """The traced training step (two key blocks a sequence here): under
    ``lm/mla/attn`` the two kernel calls of each layer and the small
    operations of their operands, and no loop over sequences or chunks,
    no slice or update of a sequence or a chunk and no concatenation of
    the attention's outputs."""
    model = get_model("kanana2_tiny", dtype=F32, **LANE_WIDE)
    params, batch = _params(model), _tokens()
    ops = _scope_operations(jax.make_jaxpr(
        jax.grad(_loss(model, batch), has_aux=True))(params), "lm/mla/attn")
    loops = ("scan", "while", "concatenate", "dynamic_slice",
             "dynamic_update_slice")
    assert not {op: ops[op] for op in loops if ops[op]}
    assert ops["pallas_call"] == 4


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
    cell's shapes (2 sequences of 8,192 positions, 32 heads, 128 + 64
    wide for scores and 128 for values, chunks of 512 against key blocks
    of 2,048, bf16, the default scale): every operation of the two
    kernels, their grids, blocks and each block's index map."""
    dsa = _dsa()
    b, t, heads, dn, dr, dv = 2, 8192, 32, 128, 64, 128
    shape = jax.ShapeDtypeStruct
    q, qr = shape((b, t, heads * dn), BF16), shape((b, t, heads * dr), BF16)
    kv, kr = shape((b, t, heads * (dn + dv)), BF16), shape((b, t, dr), BF16)
    kmax, rows = shape((b, t // 2048, heads), F32), shape((b, heads, t), F32)
    do = shape((b, t, heads * dv), BF16)
    traced = [
        jax.make_jaxpr(lambda *a: dsa.latent_forward(
            *a, q_chunk=512, interpret=False))(q, qr, kv, kr, kmax),
        jax.make_jaxpr(lambda *a: dsa.latent_backward(
            *a, q_chunk=512, interpret=False))(q, qr, kv, kr, rows, rows, do)]
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
    """The fixture was written by this function when the latent kernels
    took a whole batch a call, the chunks in their grid: the cell runs
    these operations. To change them on purpose, write the fixture again
    (``python tests/test_mla_attention.py``) and measure the cell."""
    assert kanana_kernels_jaxpr() == KANANA_FIXTURE.read_text()


if __name__ == "__main__":
    FIXTURE.parent.mkdir(exist_ok=True)
    FIXTURE.write_text(keye_kernels_jaxpr())
    KANANA_FIXTURE.write_text(kanana_kernels_jaxpr())
