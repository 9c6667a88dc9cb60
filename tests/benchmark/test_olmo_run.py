"""The whole of a generation run but the look for a chip, on the CPU at
the CPU preset: the result line, the controls judged as a run judges,
the timed path broken underneath, and a checkout without the generator."""

import functools
import time
from concurrent.futures import Future

import numpy as np
import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import serve_generate
from bench_helpers import FIXTURES, LINE_KEYS, execute, tiny_cell

E2E = [("serve_p95_ms", "ms"), ("serve_goodput", "img/s/chip"),
       ("setup_s", "s")]


@pytest.fixture(scope="module")
def cell():
    return tiny_cell("olmo_hybrid_tiny", FIXTURES / "benchmark" / "traffic"
                     / "serve_generate_tiny.json", E2E)


@pytest.fixture(scope="module")
def sound(cell):
    return execute(cell, seconds=2.0)


def test_the_result_line_has_the_contracts_keys(sound):
    assert list(sound) == LINE_KEYS
    assert sound["correct"] is True, sound["checks"]
    assert sound["attempted"] == 8 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"serve_p95_ms", "serve_goodput",
                                     "setup_s"}
    notes = sound["notes"]
    assert notes["checked"] == 3 and notes["refused"] == 0
    assert notes["gen_decode_tokens"] == 8 * 12
    assert notes["gen_decode_rows"] == 8 * 11
    assert set(sound["checks"]) == {"precision_excess", "compared_short",
                                    "unanswered"}
    assert notes["decode_gap"] < 0.2 and notes["prefill_gap"] < 0.2


def test_each_control_in_the_programs_place_is_not_correct(cell):
    """Both controls of the configuration, the reference in that
    numerics answering in the program's place, fail the run's checks;
    the program's own answers pass them (``calibrate``, one seed)."""
    readings = {r["reading"]: r for r in serve_generate.calibrate(
        cell, [2 ** 31 + 17], control=True, seconds=2.0)}
    assert readings["program"]["correct"] is True, readings["program"]
    for name in cell.config["controls"]:
        assert readings[f"control:{name}"]["correct"] is False, name
    assert readings["control:bf16_state"]["precision_excess"] > 0.4
    assert readings["control:conv_reset"]["precision_excess"] > 100
    assert readings["control:conv_reset"]["decode_gap"] > 1.0


def _with_engine(monkeypatch, wrap):
    real = serve_generate.build_engine

    def build(cfg, traffic, weights):
        engine = real(cfg, traffic, weights)
        wrap(engine)
        return engine

    monkeypatch.setattr(serve_generate, "build_engine", build)


def test_a_conv_state_lost_after_each_prefill_call_is_not_correct(
        cell, monkeypatch):
    def lose_conv(engine):
        prefill = engine._prefill

        def wrapped(*args):
            cache, token, logits = prefill(*args)
            return {**cache, "conv": cache["conv"] * 0}, token, logits

        engine._prefill = wrapped

    _with_engine(monkeypatch, lose_conv)
    result = execute(cell, seconds=2.0)
    assert result["correct"] is False
    assert not result["checks"]["precision_excess"]["ok"]
    assert result["notes"]["decode_gap"] > 0.5


def test_an_admitted_request_that_is_never_answered_is_not_correct(
        cell, monkeypatch):
    def swallow_the_third(engine):
        submit, calls = engine.submit, []

        def wrapped(tokens, max_new, **kw):
            calls.append(1)
            # two warm-up requests come first
            return Future() if len(calls) == 5 else submit(tokens, max_new,
                                                           **kw)

        engine.submit = wrapped

    _with_engine(monkeypatch, swallow_the_third)
    monkeypatch.setattr(serve_generate, "window", functools.partial(
        serve_generate.window, grace_s=1.0))
    result = execute(cell, seconds=2.0)
    assert result["correct"] is False and result["failed"] == 1
    assert not result["checks"]["unanswered"]["ok"]


def test_a_checkout_without_the_generator_fails_at_once(cell, monkeypatch):
    """The parent of the change has no generator: the run ends with an
    error before it makes a weight."""
    import sys

    monkeypatch.setitem(sys.modules, "deepvision_tpu.serve.generator", None)
    t0 = time.perf_counter()
    with pytest.raises(ImportError):
        execute(cell, seconds=2.0)
    assert time.perf_counter() - t0 < 10


def test_a_traced_window_counts_the_work_of_the_traced_span_apart(
        cell, tmp_path):
    """The roofline reader's work and time cover one span: ``traced``
    holds the counters from the profiler's start to the last answer,
    ``counters`` those before it, and the two add up to the window's."""
    from benchmark.drivers.serve_open_loop import schedule
    from benchmark.harness import cells

    t, seed, seconds = cell.traffic, 2 ** 31 + 17, 2.0
    ref = cells.reference_for(cell.config, cell.config_name)
    n = len(schedule(t["rate"], seconds, t["trace_seed"]))
    engine, _, prompts = serve_generate.bring_up(cell.config, t, ref, seed, n)
    try:
        before = serve_generate._counters(engine)
        w = serve_generate.window(engine, prompts, t, t["rate"], seconds,
                                  seed, t["checked_requests"],
                                  trace_after=1.0, trace_dir=str(tmp_path))
        total = serve_generate._counters(engine)
    finally:
        engine.close()
    assert w["answered"].all()
    for k in total:
        assert w["counters"][k] + w["traced"][k] == total[k] - before[k], k
    assert w["traced"]["gen_decode_rows"] > 0
    assert w["counters"]["gen_prefill_tokens"] > 0


def test_precision_excess_is_the_variance_over_the_stated_noise():
    stated = np.full(50, 0.01)
    assert serve_generate.precision_excess(stated, stated) == 0.0
    assert serve_generate.precision_excess(2 * stated, stated) == \
        pytest.approx(3.0)


def test_a_gap_is_relative_to_the_references_rms():
    want = np.array([[3.0, -4.0], [1.0, 1.0]])
    got = want + np.array([[0.5, 0.5], [0.0, 0.0]])
    np.testing.assert_allclose(serve_generate.gap(got, want),
                               [0.5 / np.sqrt(12.5), 0.0])
