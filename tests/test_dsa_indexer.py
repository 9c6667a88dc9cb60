"""The indexer-score kernels (ops/dsa_indexer.py) in the Pallas
interpreter on the CPU, held to the XLA form they stand in for
(models/transformer.index_scores); that every call site computes the
same bits, so that a threshold selects the same pairs wherever it is
compared; and which path a call site takes. Their compile for a v5e
chip at the benchmark cell's widths is in test_dsa_attention.py, beside
the attention kernels': one process of a test run describes the chip."""

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
SCOPE = "lm/attn/indexer"


def _dsi():
    from deepvision_tpu.ops import dsa_indexer

    return dsa_indexer


def _gap(got, want):
    got, want = (np.asarray(a, np.float64).ravel() for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _sites():
    reg = default_registry()
    return (reg.value_of("indexer_kernel_sites"),
            reg.value_of("indexer_xla_sites"))


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    """The path is chosen from the backend: the test stands in for it.
    The kernels themselves see the CPU and run in the interpreter."""
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)


# ------------------------------------------------- one chunk, the kernels

# name: (queries, keys, first query's position, heads, width)
CHUNKS = {
    "a_blocks_first_chunk": (128, 1024, 512, 4, 64),    # one tile skipped
    "a_blocks_last_chunk": (128, 1024, 896, 4, 64),
    "the_first_block": (128, 512, 0, 2, 64),            # a lone tile
    "lane_tiles_of_keys": (128, 384, 128, 2, 128),      # key tiles of 128
    "the_cells_heads": (128, 512, 384, 16, 64),
}


def _chunk(name, dtype):
    tq, keys, t0, heads, dim = CHUNKS[name]
    ks = jax.random.split(jax.random.key(len(name)), 4)
    qi = jax.random.normal(ks[0], (tq, heads, dim), F32).astype(dtype)
    ki = jax.random.normal(ks[1], (keys, dim), F32).astype(dtype)
    w = jax.random.normal(ks[2], (tq, heads), F32) * 0.125
    causal = T._causal(t0, tq, keys)
    dscores = jnp.where(causal, jax.random.normal(ks[3], (tq, keys), F32),
                        0.0)
    return qi, ki, w, t0, causal, dscores


def _live(tq, keys, t0):
    """Columns of the key tiles at or below the chunk's last query."""
    tk = _dsi().dsa.key_tile(keys)
    return np.arange(keys) // tk * tk <= t0 + tq - 1


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("name", list(CHUNKS))
def test_the_kernels_match_the_xla_form_on_a_chunk(name, dtype, tol):
    """The scores (float32 whatever the operands: the forward agrees to
    rounding in both dtypes) and, by ``jax.vjp`` of the kernel path, the
    gradients to ``qi``, ``ki`` and ``w``."""
    dsi = _dsi()
    dtype = jnp.dtype(dtype)
    qi, ki, w, t0, causal, dscores = _chunk(name, dtype)
    tq, keys = dscores.shape
    live = _live(tq, keys, t0)
    assert np.all(np.asarray(causal)[:, ~live] == 0)
    want, pull = jax.vjp(lambda *a: T.index_scores(*a, dtype), qi, ki, w)
    got, kernel_pull = jax.vjp(
        lambda *a: T.kernel_scores(*a, jnp.int32(t0)), qi, ki, w)
    assert got.dtype == F32 and got.shape == (tq, keys)
    assert _gap(got[:, live], want[:, live]) < 2e-6
    assert not np.any(np.asarray(got)[:, ~live])
    for g, wnt in zip(kernel_pull(dscores), pull(dscores)):
        assert g.dtype == wnt.dtype and g.shape == wnt.shape
        assert _gap(g, wnt) < tol

    # the sum over the chunks so far: this chunk's part is added to the
    # first rows in place, float32, and the rows past its keys stay
    before = jnp.full((keys + 128, ki.shape[1]), 0.5, F32)
    dq, dw, dk = dsi.backward(qi.reshape(tq, -1), ki, w, t0, dscores,
                              before, interpret=True)
    assert dk.dtype == F32 and dw.dtype == F32 and dq.dtype == dtype
    assert _gap(dk[:keys] - 0.5, pull(dscores)[1]) < tol
    assert np.all(np.asarray(dk[keys:]) == 0.5)
    assert np.all(np.asarray(dk[:keys])[~live] == 0.5)


# -------------------------------------- the same bits at every call site

# 512 positions, key blocks of 256, chunks of 128, top-48
SEQ = dict(t=512, heads=2, dim=64, topk=48, key_block=256, q_chunk=128)


def _sequence(dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    t, heads, dim = SEQ["t"], SEQ["heads"], SEQ["dim"]
    # every product positive: no score is an exact 0 (every relu shut),
    # so no two tie and a query keeps exactly ``topk`` keys
    return (jnp.abs(jax.random.normal(ks[0], (t, heads, dim))).astype(dtype),
            jnp.abs(jax.random.normal(ks[1], (t, dim))).astype(dtype),
            jnp.abs(jax.random.normal(ks[2], (t, heads))) * 0.125 + 0.01)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_threshold_selects_the_same_pairs_at_every_call_site(
        as_on_one_tpu, dtype):
    """The thresholds come from the scores of one call site
    (``selection_thresholds``: a mapped chunk) and are compared with
    ``>=`` at two others (``selection_mask``, as the attention's
    forward: a mapped chunk; ``_scores_and_pull``, as its backward: a
    scanned chunk under ``jax.vjp``): every query past the first
    ``topk`` keeps exactly ``topk`` keys at both, which one differing
    bit in a score at the threshold would break."""
    dtype = jnp.dtype(dtype)
    qi, ki, w = _sequence(dtype)
    t, topk = SEQ["t"], SEQ["topk"]
    blocks = dict(key_block=SEQ["key_block"], q_chunk=SEQ["q_chunk"],
                  dtype=dtype)
    before = _sites()
    thr = jax.jit(lambda *a: T.selection_thresholds(
        *a, topk=topk, **blocks))(qi, ki, w)
    mask = jax.jit(lambda *a: T.selection_mask(*a, **blocks))(qi, ki, w, thr)
    assert _sites()[0] > before[0] and _sites()[1] == before[1]
    kept = np.asarray(jnp.sum(mask, -1))
    np.testing.assert_array_equal(kept, np.minimum(np.arange(t) + 1, topk))

    block, chunk = SEQ["key_block"], SEQ["q_chunk"]

    @jax.jit
    def backward_site(qi, ki, w, thr):
        rows = []
        for b0 in range(0, t, block):
            end = b0 + block

            def one(dki, args, end=end):
                (qic, wc, thr_c), t0 = args
                scores, pull = T._scores_and_pull(qic, ki[:end], wc, t0,
                                                  dtype)
                m = T._causal(t0, chunk, end) & (scores >= thr_c[:, None])
                _dqi, _dw, dki = pull(m.astype(F32), dki)
                return dki, (m, scores)

            _, (m, scores) = jax.lax.scan(
                one, jnp.zeros(ki.shape, F32),
                T._chunks_of((qi, w, thr), b0, block, chunk))
            rows.append((m.reshape(block, end), scores.reshape(block, end)))
        return rows

    forward_site = jax.jit(lambda qic, kic, wc, t0: T.chunk_scores(
        qic, kic, wc, t0, dtype))
    for i, (m, scores) in enumerate(backward_site(qi, ki, w, thr)):
        b0, end = i * block, (i + 1) * block
        np.testing.assert_array_equal(m, mask[b0:end, :end])
        for c0 in range(b0, end, chunk):
            np.testing.assert_array_equal(
                scores[c0 - b0:c0 - b0 + chunk],
                forward_site(qi[c0:c0 + chunk], ki[:end], w[c0:c0 + chunk],
                             c0))


# ------------------------------------------------- which path a site takes


@pytest.mark.parametrize("chunk,heads,dim,engages", [
    (512, 16, 64, True),        # the benchmark cell's
    (128, 2, 64, True),
    (128, 4, 128, True),
    (64, 16, 64, False),        # half a lane row of queries
    (1000, 16, 64, False),      # a length taken in one chunk
    (128, 4, 16, False),        # the tiny preset's heads
    (128, 3, 64, False),        # the heads end in half a lane row
    (128, 8, 96, False),
])
def test_shapes_that_do_not_tile_take_the_xla_form(
        as_on_one_tpu, chunk, heads, dim, engages):
    assert T.indexer_engages(chunk, heads, dim) is engages


def test_no_shape_engages_off_the_chip():
    assert not T.indexer_engages(512, 16, 64)


def _pallas_calls(jaxpr) -> list:
    """(kernel name, name stack) of every Pallas call of a traced
    function, loops' bodies and recomputed regions included."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              str(eqn.source_info.name_stack)))
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return found


# the tiny preset with indexer heads the kernels take: 256 positions,
# two key blocks; with lane-wide attention heads the attention's kernels
# engage beside them, without them its XLA form calls the same scores
INDEXER = dict(indexer_heads=2, indexer_dim=64, key_block=128, q_chunk=128,
               topk=48, sample_text_len=252)
MODELS = {
    "beside_the_attention_kernels": dict(
        INDEXER, head_dim=128, heads=8, kv_heads=2,
        mrope_section=(16, 24, 24)),
    "under_the_xla_attention": INDEXER,
}


def _batch(text_len=252, rows=2):
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


def _loss(model, batch):
    from deepvision_tpu.train.steps import _vlm_losses

    def loss(params):
        out = model.apply({"params": params}, batch, train=True)
        return _vlm_losses(out, 1.0)[0], out
    return loss


@pytest.mark.parametrize("name", list(MODELS))
def test_the_model_takes_the_kernels_and_agrees_with_the_xla_form(
        name, monkeypatch):
    """Loss, counts and every parameter's gradient of the two paths,
    the layer recomputed on the way back; both counters count; every
    kernel call, forward and backward, carries the indexer's scope."""
    model = get_model("keye_vl2_tiny", dtype=F32, **MODELS[name])
    params, batch = _params(model), _batch()
    before = _sites()
    step = jax.jit(jax.value_and_grad(_loss(model, batch), has_aux=True))
    (want, want_out), want_grads = step(params)
    assert _sites()[0] == before[0] and _sites()[1] > before[1]

    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    before = _sites()
    grad = jax.value_and_grad(_loss(model, batch), has_aux=True)
    calls = [c for c in _pallas_calls(jax.make_jaxpr(grad)(params))
             if c[0].startswith("dsa_indexer")]
    assert _sites()[0] > before[0] and _sites()[1] == before[1]
    assert {c[0] for c in calls} == {"dsa_indexer_forward",
                                     "dsa_indexer_backward"}
    assert all(SCOPE in stack for _name, stack in calls), calls

    (got, out), grads = jax.jit(grad)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_array_equal(out["selected_pairs"],
                                  want_out["selected_pairs"])
    np.testing.assert_allclose(out["index_kl"], want_out["index_kl"],
                               rtol=1e-5)
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    got, want = flat(grads), flat(want_grads)
    floor = float(np.median([np.linalg.norm(v) for v in want.values()]))
    for leaf, w in want.items():
        gap = np.linalg.norm(got[leaf] - w) / max(np.linalg.norm(w), floor)
        assert gap < 2e-4, (leaf, gap)


def test_capture_returns_the_same_mask_on_both_paths(monkeypatch):
    kwargs = dict(MODELS["beside_the_attention_kernels"], num_layers=1)
    model = get_model("keye_vl2_tiny", dtype=F32, capture=True, **kwargs)
    params, batch = _params(model), _batch(rows=1)
    apply = lambda: jax.jit(lambda p: model.apply({"params": p}, batch))(
        params)
    want = apply()
    monkeypatch.setattr(T, "_on_one_tpu", lambda: True)
    got = apply()
    assert got["masks"].shape == (1, 1, 256, 256)
    np.testing.assert_array_equal(got["masks"], want["masks"])
    assert int(jnp.sum(got["masks"])) == int(got["selected_pairs"][0])
