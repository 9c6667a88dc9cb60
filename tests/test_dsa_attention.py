"""The sparse-attention kernels (ops/dsa_attention.py) in the Pallas
interpreter on the CPU, held to the XLA form they stand in for
(models/transformer._attend, sparse_attention) and to the gather form
(gathered_attention); which path a call site takes; what a recomputed
layer keeps; and the kernels' compile for a v5e chip at the benchmark
cell's widths, without the chip."""

import collections
import subprocess
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
from deepvision_tpu.models import transformer as T  # noqa: E402
from deepvision_tpu.obs.metrics import default_registry  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
HD = 128


def _dsa():
    from deepvision_tpu.ops import dsa_attention

    return dsa_attention


def _gap(got, want):
    got, want = (np.asarray(a, np.float64).ravel() for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _sites():
    reg = default_registry()
    return (reg.value_of("dsa_kernel_sites"), reg.value_of("dsa_xla_sites"))


# ------------------------------------------------- one chunk, the kernels

# name: (queries, keys, first query's position, heads, groups, topk, ties)
CHUNKS = {
    "one_tile": (128, 128, 0, 8, 1, 48, False),
    "several_tiles_gqa_8_to_1": (128, 512, 384, 8, 1, 100, False),
    "tiles_above_the_diagonal": (128, 512, 130, 16, 2, 64, False),
    "topk_of_the_length_is_dense_causal": (256, 256, 0, 8, 1, 256, False),
    "ties_at_the_threshold": (128, 384, 256, 8, 1, 64, True),
    "gqa_1_to_1": (128, 256, 128, 4, 4, 40, False),
}


def _chunk(name, dtype):
    tq, keys, t0, heads, groups, topk, ties = CHUNKS[name]
    ks = jax.random.split(jax.random.key(len(name)), 5)
    normal = lambda k, *s: jax.random.normal(k, s, F32)
    q = normal(ks[0], tq, heads, HD).astype(dtype)
    k = normal(ks[1], keys, groups, HD).astype(dtype)
    v = normal(ks[2], keys, groups, HD).astype(dtype)
    scores = normal(ks[3], tq, keys)
    if ties:        # a few distinct values: the threshold is shared
        scores = jnp.round(scores * 2.0) / 2.0
    causal = T._causal(t0, tq, keys)
    thr = T.kth_largest(jnp.where(causal, scores, -jnp.inf), topk)
    do = normal(ks[4], tq, heads * HD).astype(dtype)
    return q, k, v, scores, thr, causal & (scores >= thr[:, None]), t0, do


def _reference_target(q, k, mask):
    tq, heads, _ = q.shape
    groups = k.shape[1]
    qg = q.astype(F32).reshape(tq, groups, heads // groups, HD)
    logits = jnp.einsum("tgrd,sgd->grts", qg, k.astype(F32),
                        precision="highest") / np.sqrt(HD)
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), -1)
    return jnp.sum(probs, (0, 1)) / heads


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", list(CHUNKS))
def test_the_kernels_match_the_xla_form_on_a_chunk(name, dtype, tol):
    """Output, alignment target (from the forward and again from the
    backward) and the gradients to q, k and v."""
    dsa = _dsa()
    dtype = jnp.dtype(dtype)
    q, k, v, scores, thr, mask, t0, do = _chunk(name, dtype)
    tq, heads, _ = q.shape
    keys = k.shape[0]
    if CHUNKS[name][6]:
        kept = np.asarray(jnp.sum(mask, -1))
        assert kept.max() > CHUNKS[name][5]         # ties: all kept
    want_o, pull = jax.vjp(
        lambda *a: T._attend(*a, scores, mask, dtype)[0], q, k, v)
    want = pull(do)
    want_target = _reference_target(q, k, mask)

    flat = lambda a: a.reshape(a.shape[0], -1)
    o, lse, target = dsa.forward(flat(q), flat(k), flat(v), scores, thr, t0,
                                 interpret=True)
    assert o.dtype == dtype and lse.shape == (heads, tq)
    assert target.dtype == F32 and target.shape == (tq, keys)
    assert _gap(o, want_o) < tol
    assert _gap(target, want_target) < 2e-5 + tol / 10
    assert not np.any(np.asarray(target)[~np.asarray(mask)])

    di = jnp.sum((o.astype(F32) * do.astype(F32)).reshape(tq, heads, HD),
                 -1).T
    # sums over the chunks so far: this chunk's part is added in place,
    # and rows past the chunk's keys are left alone
    before = jnp.full((keys + 128, k.shape[1] * HD), 0.5, F32)
    dq, dk, dv, again = dsa.backward(
        flat(q), flat(k), flat(v), scores, thr, t0, lse, di, do, before,
        before, interpret=True)
    assert _gap(dq, flat(want[0])) < tol
    assert _gap(dk[:keys] - 0.5, flat(want[1])) < tol
    assert _gap(dv[:keys] - 0.5, flat(want[2])) < tol
    assert np.all(np.asarray(dk[keys:]) == 0.5)
    assert np.all(np.asarray(dv[keys:]) == 0.5)
    assert _gap(again, target) < 1e-6


# ------------------------------------------ a batch, through the model's path


def _sequence(t, heads, groups, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    normal = lambda k, *s: jax.random.normal(k, s, F32)
    # the indexer's products all positive: no score is an exact 0 (every
    # relu shut), so no two tie and the gather form keeps the same set
    return (normal(ks[0], 1, t, heads, HD).astype(dtype),
            normal(ks[1], 1, t, groups, HD).astype(dtype),
            normal(ks[2], 1, t, groups, HD).astype(dtype),
            jnp.abs(normal(ks[3], 1, t, 4, 16)).astype(dtype),
            jnp.abs(normal(ks[4], 1, t, 16)).astype(dtype),
            jnp.abs(normal(ks[5], 1, t, 4)) * 0.125 + 0.01)


SEQUENCES = {
    # name: (length, heads, groups, topk, key_block, q_chunk)
    "one_block": (128, 8, 1, 40, 128, 128),
    "blocks_and_chunks": (512, 8, 2, 100, 256, 128),
    "dense_causal": (256, 8, 8, 256, 256, 128),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", list(SEQUENCES))
def test_kernel_attention_matches_the_masked_and_the_gathered_form(
        name, dtype, tol):
    """Output, alignment loss, selected pairs and the gradients to all
    six inputs, the indexer's through the alignment loss."""
    t, heads, groups, topk, key_block, q_chunk = SEQUENCES[name]
    dtype = jnp.dtype(dtype)
    args = _sequence(t, heads, groups, dtype)
    blocks = dict(key_block=key_block, q_chunk=q_chunk, dtype=dtype)
    thr = jax.lax.map(lambda a: T.selection_thresholds(
        *a, topk=topk, **blocks), args[3:])
    weights = jax.random.normal(jax.random.key(9), (1, t, heads * HD), F32)

    def scalar(fn):
        def loss(*a):
            o, kl, pairs = fn(*a)
            return (jnp.sum(o.astype(F32) * weights) + 0.7 * jnp.sum(kl),
                    (o, kl, pairs))
        return jax.jit(jax.value_and_grad(loss, range(6), has_aux=True))

    masked = scalar(lambda *a: jax.lax.map(
        lambda b: T.sparse_attention(*b, **blocks)[:3], (*a, thr)))
    kernel = scalar(lambda *a: T.kernel_attention(
        *a, thr, key_block, q_chunk, dtype))
    (_, (want_o, want_kl, want_n)), want = masked(*args)
    (_, (o, kl, n)), got = kernel(*args)
    assert o.dtype == dtype and _gap(o, want_o) < tol
    assert float(kl[0]) == pytest.approx(float(want_kl[0]), rel=5e-4 + tol / 10)
    if dtype == F32:
        assert int(n[0]) == int(want_n[0])
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert _gap(g, w) < tol

    o_g, kl_g, n_g = T.gathered_attention(
        *(a[0] for a in args), topk=topk, dtype=dtype)
    assert _gap(o[0], o_g) < tol
    if dtype == F32:        # no tie among these scores: the same set
        assert int(n[0]) == int(n_g)
        assert float(kl[0]) == pytest.approx(float(kl_g), rel=1e-4)


# ------------------------------------------------- which path a site takes

# the tiny preset with lane-wide heads: 256 positions, two key blocks
LANE_WIDE = dict(head_dim=HD, heads=8, kv_heads=2, mrope_section=(16, 24, 24),
                 key_block=128, q_chunk=128, topk=48, sample_text_len=252)


def _batch(text_len, rows=2):
    rng = np.random.RandomState(0)
    return {"image": rng.randn(rows, 16, 16, 3).astype(np.float32),
            "tokens": rng.randint(0, 128, (rows, text_len)).astype(np.int32)}


def _params(model, scale=0.05):
    params = model.init(jax.random.key(0), model.sample_input())["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return jax.tree.unflatten(tree, [
        a + scale * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    """The path is chosen from the backend: the test stands in for it.
    The kernels themselves see the CPU and run in the interpreter."""
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)


@pytest.mark.parametrize("why,kwargs,text_len", [
    ("cpu_backend", LANE_WIDE, 252),
    ("heads_not_lane_wide", {}, 12),
])
def test_the_xla_form_runs_where_the_kernels_do_not_apply(
        why, kwargs, text_len, monkeypatch):
    if why != "cpu_backend":
        monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    model = get_model("keye_vl2_tiny", dtype=F32, **kwargs)
    params = _params(model)
    before = _sites()
    jaxpr = jax.make_jaxpr(lambda p, b: model.apply({"params": p}, b))(
        params, _batch(text_len))
    after = _sites()
    assert not _kernel_calls(jaxpr)
    # one call site a trace of the scanned layer's body
    assert after[0] == before[0] and after[1] > before[1]


@pytest.mark.parametrize("t,key_block,q_chunk,heads,head_dim,engages", [
    (8192, 2048, 512, 32, 128, True),       # the benchmark cell's
    (1024, 512, 128, 32, 128, True),
    (1024, 512, 64, 32, 128, False),        # half a lane row of queries
    (768, 192, 192, 32, 128, False),
    (1000, 512, 128, 32, 128, False),       # taken in one chunk of 1000
    (1024, 512, 128, 32, 64, False),        # heads not lane-wide
    (1024, 512, 128, 30, 128, False),       # 30 heads on 4 key/value heads
])
def test_shapes_that_do_not_tile_take_the_xla_form(
        as_on_one_tpu, t, key_block, q_chunk, heads, head_dim, engages):
    assert T.kernel_engages(t, heads, 4, head_dim, key_block,
                            q_chunk) is engages


def _kernel_calls(jaxpr) -> collections.Counter:
    """Pallas calls of a traced function by kernel name, loops'
    bodies and recomputed regions included."""
    counts = collections.Counter()

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[eqn.params["name"]] += 1
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return counts


def _loss(model, batch):
    from deepvision_tpu.train.steps import _vlm_losses

    def loss(params):
        out = model.apply({"params": params}, batch, train=True)
        return _vlm_losses(out, 1.0)[0], out
    return loss


def test_the_model_takes_the_kernels_and_agrees_with_the_xla_form(
        monkeypatch):
    """Loss, counts and every parameter's gradient of the two paths,
    the layer recomputed on the way back (``remat='layer'``)."""
    model = get_model("keye_vl2_tiny", dtype=F32, **LANE_WIDE)
    params, batch = _params(model), _batch(252)
    step = jax.jit(jax.value_and_grad(_loss(model, batch), has_aux=True))
    (want, want_out), want_grads = step(params)
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    before = _sites()
    step = jax.jit(jax.value_and_grad(_loss(model, batch), has_aux=True))
    (got, out), grads = step(params)
    assert _sites()[0] > before[0] and _sites()[1] == before[1]
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_array_equal(out["selected_pairs"],
                                  want_out["selected_pairs"])
    np.testing.assert_allclose(out["index_kl"], want_out["index_kl"],
                               rtol=1e-5)
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    got, want = flat(grads), flat(want_grads)
    floor = float(np.median([np.linalg.norm(v) for v in want.values()]))
    for name, w in want.items():
        gap = np.linalg.norm(got[name] - w) / max(np.linalg.norm(w), floor)
        assert gap < 2e-4, (name, gap)


def test_a_recomputed_layer_keeps_the_log_sum_exp(as_on_one_tpu):
    """Forward and backward of the scanned, recomputed layer: the
    forward kernels once a key block (two here), in the forward pass
    only; the backward kernel once a key block. Without ``dsa_lse``
    among the names the layer keeps, the way back would run the forward
    kernels again just to have it."""
    model = get_model("keye_vl2_tiny", dtype=F32, **LANE_WIDE)
    params, batch = _params(model), _batch(252)
    forward = _kernel_calls(jax.make_jaxpr(_loss(model, batch))(params))
    assert forward == {"dsa_attention_forward": 2, "dsa_attention_target": 2}
    both = _kernel_calls(jax.make_jaxpr(
        jax.grad(_loss(model, batch), has_aux=True))(params))
    assert both == {"dsa_attention_forward": 2, "dsa_attention_target": 2,
                    "dsa_attention_backward": 2}


def test_capture_returns_the_same_mask_on_both_paths(monkeypatch):
    kwargs = dict(LANE_WIDE, num_layers=1)
    model = get_model("keye_vl2_tiny", dtype=F32, capture=True, **kwargs)
    params, batch = _params(model), _batch(252, rows=1)
    apply = lambda: jax.jit(lambda p: model.apply({"params": p}, batch))(
        params)
    want = apply()
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    got = apply()
    assert got["masks"].shape == (1, 1, 256, 256)
    np.testing.assert_array_equal(got["masks"], want["masks"])
    np.testing.assert_array_equal(got["experts"], want["experts"])
    assert int(jnp.sum(got["masks"])) == int(got["selected_pairs"][0])


def test_no_process_imports_pallas_for_the_models_alone():
    """``models/__init__`` imports the token model in every process, the
    serving one too; the kernels' module comes with the first call site
    that takes them."""
    code = ("import sys; import deepvision_tpu.models, "
            "deepvision_tpu.serve.engine, deepvision_tpu.serve.models; "
            "bad = [m for m in sys.modules if 'pallas' in m]; "
            "assert not bad, bad; "
            "assert 'deepvision_tpu.models.transformer' in sys.modules")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                               "TF_CPP_MIN_LOG_LEVEL": "2"})
    assert done.returncode == 0, done.stderr[-2000:]


# --------------------------------- the chip's compiler, without the chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("keys", [2048, 8192])
@pytest.mark.parametrize("which", ["forward", "backward"])
def test_the_kernels_compile_for_v5e_at_the_cells_widths(
        one_chip, no_compile_cache, which, keys):
    """512 queries of 32 heads over 4 key/value heads of 128, bf16,
    against a key block's first and last extent: what Mosaic refuses
    (tiling, VMEM) shows here and not on the chip."""
    dsa = _dsa()
    tq, heads, groups = 512, 32, 4
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    q = shape((tq, heads * HD), BF16)
    kv = shape((keys, groups * HD), BF16)
    scores, thr = shape((tq, keys), F32), shape((tq,), F32)
    t0, rows = shape((), jnp.int32), shape((heads, tq), F32)
    if which == "forward":
        fn = lambda q, k, v, s, th, t0: dsa.forward(
            q, k, v, s, th, t0, interpret=False)
        args = (q, kv, kv, scores, thr, t0)
    else:
        sums = shape((8192, groups * HD), F32)
        fn = lambda q, k, v, s, th, t0, lse, di, do, dk, dv: dsa.backward(
            q, k, v, s, th, t0, lse, di, do, dk, dv, interpret=False)
        args = (q, kv, kv, scores, thr, t0, rows, rows, q, sums, sums)
    compiled = jax.jit(fn).lower(*args).compile()
    assert f"dsa_attention_{which}" in compiled.as_text()


@pytest.mark.parametrize("keys", [2048, 8192])
@pytest.mark.parametrize("which", ["forward", "backward"])
def test_the_latent_kernels_compile_for_v5e_at_the_cells_widths(
        one_chip, no_compile_cache, which, keys):
    """The module's latent kernels (their other tests:
    test_mla_attention.py; the compile is here because one process of a
    test run describes the chip): 2 sequences of ``keys`` positions, 32
    heads, 128 + 64 wide for scores (the 64 rotary columns of every
    second head a slice off the lane tiling) and 128 for values, bf16,
    chunks of 512, one call a direction; the backward's float32 ``dq``
    sums of a sequence and head step stay in VMEM."""
    dsa = _dsa()
    b, heads, dn, dr, dv = 2, 32, 128, 64, 128
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    q, q_rope = shape((b, keys, heads * dn), BF16), \
        shape((b, keys, heads * dr), BF16)
    kv = shape((b, keys, heads * (dn + dv)), BF16)
    k_rope = shape((b, keys, dr), BF16)
    if which == "forward":
        fn = lambda *a: dsa.latent_forward(*a, q_chunk=512, interpret=False)
        args = (q, q_rope, kv, k_rope, shape((b, keys // 2048, heads), F32))
    else:
        fn = lambda *a: dsa.latent_backward(*a, q_chunk=512, interpret=False)
        stats = shape((b, heads, keys), F32)
        args = (q, q_rope, kv, k_rope, stats, stats,
                shape((b, keys, heads * dv), BF16))
    compiled = jax.jit(fn).lower(*args).compile()
    assert f"mla_attention_{which}" in compiled.as_text()


@pytest.mark.parametrize("keys", [2048, 8192])
@pytest.mark.parametrize("which", ["forward", "backward"])
def test_the_indexer_kernels_compile_for_v5e_at_the_cells_widths(
        one_chip, no_compile_cache, which, keys):
    """``ops/dsa_indexer.py`` (its other tests: test_dsa_indexer.py; the
    compile is here because one process of a test run describes the
    chip): 512 queries of 16 indexer heads of 64, bf16, against a key
    block's first and last extent. The heads are half a lane row wide:
    every second one is a slice off the lane tiling."""
    from deepvision_tpu.ops import dsa_indexer as dsi

    tq, heads, dim = 512, 16, 64
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    qi, ki = shape((tq, heads * dim), BF16), shape((keys, dim), BF16)
    w, t0 = shape((tq, heads), F32), shape((), jnp.int32)
    if which == "forward":
        fn = lambda qi, ki, w, t0: dsi.forward(qi, ki, w, t0,
                                               interpret=False)
        args = (qi, ki, w, t0)
    else:
        fn = lambda qi, ki, w, t0, ds, dki: dsi.backward(
            qi, ki, w, t0, ds, dki, interpret=False)
        args = (qi, ki, w, t0, shape((tq, keys), F32),
                shape((8192, dim), F32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert f"dsa_indexer_{which}" in compiled.as_text()
