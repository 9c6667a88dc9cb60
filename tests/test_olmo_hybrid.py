"""The hybrid token model (``models/olmo_hybrid.py``) and its generator
(``serve/generator.py``, ``serve/slot_cache.py``) against the plain
reference (``benchmark/reference/olmo_hybrid.py``), at the CPU preset."""

import json
import math
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepvision_tpu.models import get_model
from deepvision_tpu.models import olmo_hybrid as O
from deepvision_tpu.serve.admission import ShedError
from deepvision_tpu.serve.generator import GenerationEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from benchmark.reference import olmo_hybrid as R  # noqa: E402
from benchmark.reference import plain  # noqa: E402

F32 = jnp.float32
TINY = json.loads((ROOT / "tests" / "benchmark" / "fixtures" / "benchmark"
                   / "configs" / "olmo_hybrid_tiny.json").read_text())


@pytest.fixture(scope="module")
def weights():
    """The reference's seeded weights, as the program stores them."""
    return jax.jit(lambda k: R.make_weights(TINY, k))(plain.seed_key(7))


def _cfg(dtype=F32):
    return get_model("olmo_hybrid_tiny", dtype=dtype).config


def _rule_inputs(t, h=3, dk=8, dv=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = O._l2norm(jax.random.normal(ks[0], (t, h, dk))) / math.sqrt(dk)
    k = O._l2norm(jax.random.normal(ks[1], (t, h, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    # beta over (0, 2): negative eigenvalues of (I - beta k k^T) included
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (t, h)))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (t, h)))
    state = jax.random.normal(ks[5], (h, dk, dv))
    return q, k, v, beta, g, state


def _recurrence(q, k, v, beta, g, state):
    outs, s = [], state[None]
    for t in range(q.shape[0]):
        o, s = O.recurrent_delta_step(q[t][None], k[t][None], v[t][None],
                                      beta[t][None], g[t][None], s)
        outs.append(o[0])
    return jnp.stack(outs), s[0]


@pytest.mark.parametrize("t", [1, 8, 13, 37])
def test_chunk_form_equals_the_recurrence(t):
    """Lengths on and off the sub-chunk of 8, beta up to 2, a state
    carried in."""
    args = _rule_inputs(t)
    o, s = O.chunk_delta_rule(*args, chunk=8)
    o_ref, s_ref = _recurrence(*args)
    assert float(jnp.max(args[3])) > 1.5
    np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s, s_ref, rtol=1e-4, atol=1e-5)


def test_padded_positions_leave_the_state_and_the_outputs_alone():
    q, k, v, beta, g, state = _rule_inputs(21)
    real = jnp.arange(21) < 13
    o, s = O.chunk_delta_rule(q, k, v, jnp.where(real[:, None], beta, 0.0),
                              jnp.where(real[:, None], g, 0.0), state, 8)
    o13, s13 = O.chunk_delta_rule(q[:13], k[:13], v[:13], beta[:13],
                                  g[:13], state, 8)
    np.testing.assert_allclose(s, s13, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[:13], o13, rtol=1e-5, atol=1e-6)


def test_the_reference_recurrence_is_the_programs_rule():
    q, k, v, beta, g, _ = _rule_inputs(19)
    zero = jnp.zeros((3, 8, 16))
    o, _ = O.chunk_delta_rule(q, k, v, beta, g, zero, 8)
    np.testing.assert_allclose(R.delta_rule(q, k, v, beta, g, False), o,
                               rtol=1e-4, atol=1e-5)


def _prefill_then_decode(params, cfg, prompt, new_tokens, chunk=16,
                         slot=1, slots=3, max_len=64):
    cache = O.empty_cache(cfg, slots, max_len)
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        ids = np.zeros(chunk, np.int32)
        ids[:n] = prompt[start:start + n]
        cache, _, logits = O.prefill(params, cache, slot, ids, start, n,
                                     cfg)
    rows = [logits]
    active = np.arange(slots) == slot
    for tok in new_tokens[:-1]:
        ids = np.zeros(slots, np.int32)
        ids[slot] = tok
        cache, _, logits = O.decode(params, cache, ids, active, cfg)
        rows.append(logits[slot])
    return np.stack(rows), cache


def test_prefill_and_decode_equal_the_references_full_forward(weights):
    """Float32 program: a 21-token prompt in two chunks (the second
    padded) and 9 decode steps against the reference's forward over
    prompt and tokens at HIGHEST."""
    cfg = _cfg()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, TINY["vocab_size"], 21).astype(np.int32)
    new = rng.integers(0, TINY["vocab_size"], 10).astype(np.int32)
    w32 = jax.tree.map(lambda a: a.astype(F32), weights)
    got, cache = _prefill_then_decode(w32, cfg, prompt, new)
    seq = np.zeros(64, np.int32)
    seq[:21], seq[21:30] = prompt, new[:-1]
    want = R.logits_at(TINY, weights, seq, np.arange(20, 30))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert int(cache["length"][1]) == 30
    assert int(cache["length"][0]) == int(cache["length"][2]) == 0


def test_a_bf16_program_is_as_near_the_truth_as_the_stated_precision(
        weights):
    """The served numerics: bfloat16 products lie about as far from the
    float32 reference as the reference at the stated precision does, and
    within a few percent."""
    cfg = _cfg(jnp.bfloat16)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, TINY["vocab_size"], 40).astype(np.int32)
    new = rng.integers(0, TINY["vocab_size"], 12).astype(np.int32)
    got, _ = _prefill_then_decode(weights, cfg, prompt, new, chunk=32,
                                  max_len=64)
    seq = np.zeros(64, np.int32)
    seq[:40], seq[40:51] = prompt, new[:-1]
    pos = np.arange(39, 51)
    want = np.asarray(R.logits_at(TINY, weights, seq, pos))
    stated = np.asarray(R.logits_at(TINY, weights, seq, pos, "stated"))
    rms = lambda a: np.sqrt(np.mean(np.square(a), -1))
    gap = rms(got - want) / rms(want)
    assert gap.max() < 0.1
    assert np.median(gap) < 2 * np.median(rms(stated - want) / rms(want))


def test_the_flax_module_scores_a_batch_of_texts():
    model = get_model("olmo_hybrid_tiny", dtype=F32)
    tokens = np.random.default_rng(3).integers(0, 256, (2, 17))
    v = model.init(jax.random.key(0), model.sample_input())
    out = model.apply(v, {"tokens": tokens}, logits=True)
    assert out["nll"].shape == (2, 16) and out["logits"].shape == (2, 16, 256)
    h = O.forward_hidden(v["params"], jnp.asarray(tokens[0, :-1]),
                         model.config)
    np.testing.assert_allclose(out["logits"][0],
                               O._logits(v["params"], h, model.config),
                               rtol=1e-5, atol=1e-5)


def test_the_published_presets_hold_the_references_shapes():
    """Every width as published; the stage preset holds 8 layers, the
    whole vocabulary; a slot holds the state the configuration reckons."""
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "olmo_hybrid_7b.json").read_text())
    for name, layers in (("olmo_hybrid_pp4", 8), ("olmo_hybrid", 32)):
        model = get_model(name)
        params = jax.eval_shape(lambda m=model: m.init(
            jax.random.key(0), m.sample_input()))["params"]
        want = R.param_shapes({**cfg, "layer_types": (
            ["linear_attention"] * 3 + ["full_attention"]) * (layers // 4)})
        assert jax.tree.map(lambda a: tuple(a.shape), params) == want, name
    shapes = O.cache_shapes(get_model("olmo_hybrid_pp4").config, 16, 16512)
    assert shapes["conv"].shape == (2, 3, 16, 3, 11520)
    assert shapes["delta"].shape == (2, 3, 16, 30, 96, 192)
    assert shapes["delta"].dtype == F32
    assert shapes["k"].shape == (2, 16, 30, 16512, 128)
    assert shapes["k"].dtype == jnp.bfloat16


# ------------------------------------------------------------ generator


def _engine(weights, dtype=F32, **kw):
    params = jax.tree.map(lambda a: a.astype(dtype)
                          if a.dtype == jnp.bfloat16 else a, weights)
    cfg = _cfg(dtype)
    opts = dict(slots=3, max_len=64, prefill_chunk=16)
    return GenerationEngine(cfg, params, **{**opts, **kw})


def _prompts(lengths, seed=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def test_requests_batched_equal_each_served_alone(weights):
    """Five prompts of different lengths in three slots at once answer
    as each does alone in a one-slot generator (float32: a row's
    arithmetic does not depend on its neighbours)."""
    prompts = _prompts([5, 17, 33, 40, 9])
    eng = _engine(weights)
    try:
        futs = [eng.submit(p, 6, keep_logits=True) for p in prompts]
        batched = [f.result(timeout=300) for f in futs]
    finally:
        eng.close()
    alone = _engine(weights, slots=1)
    try:
        for p, got in zip(prompts, batched):
            want = alone.submit(p, 6, keep_logits=True).result(timeout=300)
            assert got["tokens"] == want["tokens"]
            np.testing.assert_allclose(got["logits"], want["logits"],
                                       rtol=1e-5, atol=1e-5)
    finally:
        alone.close()


def test_a_reused_slot_carries_nothing_of_its_last_request(weights):
    first, second = _prompts([40, 11], seed=5)
    eng = _engine(weights, slots=1)
    try:
        eng.submit(first, 20).result(timeout=300)
        reused = eng.submit(second, 6, keep_logits=True).result(timeout=300)
    finally:
        eng.close()
    fresh = _engine(weights, slots=1)
    try:
        want = fresh.submit(second, 6, keep_logits=True).result(timeout=300)
    finally:
        fresh.close()
    assert reused["tokens"] == want["tokens"]
    np.testing.assert_array_equal(reused["logits"], want["logits"])


def test_the_generators_counters_and_spans(weights):
    from deepvision_tpu.obs.metrics import Registry
    from deepvision_tpu.obs.trace import get_tracer

    reg = Registry()
    tracer = get_tracer()
    tracer.enable()
    eng = _engine(weights, jnp.bfloat16, registry=reg)
    try:
        futs = [eng.submit(p, 4) for p in _prompts([20, 7])]
        outs = [f.result(timeout=300) for f in futs]
    finally:
        eng.close()
        spans = {e["name"] for e in tracer.chrome_events()
                 if e.get("cat") == "gen"}
        tracer.disable()
    assert [len(o["tokens"]) for o in outs] == [4, 4]
    snap = reg.snapshot()
    assert snap["gen_prefill_tokens"] == 27
    assert snap["gen_decode_tokens"] == 8
    assert snap["gen_decode_rows"] == 6
    assert 3 <= snap["gen_decode_steps"] <= 6
    assert snap["gen_ttft"]["count"] == 2
    assert {"admit", "prefill", "decode"} <= spans


def test_a_full_queue_sheds_and_a_slot_that_does_not_fit_is_refused(
        weights):
    eng = _engine(weights, slots=1, max_queue=1)
    gate = threading.Event()
    try:
        with pytest.raises(ValueError, match="do not fit"):
            eng.submit(np.zeros(60, np.int32), 8)
        with pytest.raises(ValueError, match="vocabulary"):
            eng.submit(np.array([1, 2 ** 33]), 2)
        # one request held in the slot, one queued: the next is shed
        real = eng._prefill_step

        def held(req):
            gate.wait(60)
            return real(req)

        eng._prefill_step = held
        running = eng.submit(np.ones(4, np.int32), 2)
        deadline = time.perf_counter() + 30
        while eng.stats()["queued"] and time.perf_counter() < deadline:
            time.sleep(0.01)
        queued = eng.submit(np.ones(4, np.int32), 2, timeout_s=0.0)
        with pytest.raises(ShedError):
            eng.submit(np.ones(4, np.int32), 2)
        gate.set()
        assert len(running.result(timeout=120)["tokens"]) == 2
        with pytest.raises(TimeoutError):
            queued.result(timeout=120)
    finally:
        gate.set()
        eng.close()


def test_serve_py_generates_over_stdin_jsonl(weights):
    """``serve.py -m olmo_hybrid*``'s loop: one response line a request
    line, in order, each with the generator's own greedy tokens; a
    malformed line and a prompt that does not fit a slot are answered
    with an error and the loop goes on."""
    import io
    import types

    import serve

    prompts = _prompts([12, 30])
    lines = [json.dumps({"id": 1, "tokens": prompts[0].tolist(),
                         "max_new_tokens": 5}),
             "{not json",
             json.dumps({"id": 2, "tokens": [1] * 500,
                         "max_new_tokens": 2}),
             json.dumps({"id": 3, "tokens": prompts[1].tolist(),
                         "max_new_tokens": 3})]
    out = io.StringIO()
    eng = _engine(weights)
    try:
        serve.run_generate(eng, types.SimpleNamespace(timeout_s=300.0),
                           stdin=io.StringIO("\n".join(lines) + "\n"),
                           stdout=out)
        want = [eng.submit(p, n).result(timeout=300)["tokens"]
                for p, n in zip(prompts, (5, 3))]
    finally:
        eng.close()
    got = [json.loads(line) for line in out.getvalue().splitlines()]
    assert "bad request" in got[0]["error"]
    assert got[1]["id"] == 2 and "do not fit" in got[1]["error"]
    assert [g["id"] for g in got[2:]] == [1, 3]
    assert [g["result"]["tokens"] for g in got[2:]] == want
