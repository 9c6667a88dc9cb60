"""The whole of a serving run but the look for a chip, on the CPU at a toy
image size: the result line, and the timed path broken underneath."""

import functools
import itertools
from concurrent.futures import Future

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import serve_open_loop
from bench_helpers import LINE_KEYS, execute



@pytest.fixture(scope="module")
def sound_serve():
    from bench_helpers import FIXTURES, tiny_cell

    cell = tiny_cell("yolov3_tiny",
                     FIXTURES / "benchmark" / "traffic" / "serve_tiny.json",
                     [("serve_p95_ms", "ms"),
                      ("serve_goodput", "img/s/chip"), ("setup_s", "s")])
    return cell, execute(cell, seconds=1.0)


def test_serve_result_line_has_the_contracts_keys(sound_serve):
    _cell, result = sound_serve
    assert list(result) == LINE_KEYS
    assert result["correct"] is True
    assert result["attempted"] == 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_p95_ms", "serve_goodput",
                                      "setup_s"}
    notes = result["notes"]
    assert notes["served_detections"] > 0
    assert notes["refused"] == notes["offered_again"] == 0
    assert notes["offers_refused"] == 0 and notes["held_ms_max"] == 0.0
    assert {"gc_ms_max", "gc_gen2_count", "gc_gen2_ms_max",
            "stall_in_gc"} <= set(notes)


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        sound_serve, monkeypatch):
    cell, _ = sound_serve
    real = serve_open_loop.build_engine

    def build(cfg, traffic, weights):
        engine, served = real(cfg, traffic, weights)
        post = served.postprocess

        def altered(host, i):
            out = post(host, i)
            if out["scores"]:
                out["boxes"][0][0] += 0.01      # one corner, one box
            return out

        served.postprocess = altered
        return engine, served

    monkeypatch.setattr(serve_open_loop, "build_engine", build)
    result = execute(cell, seconds=1.0)
    assert result["correct"] is False
    assert not result["checks"]["det_gap_p99"]["ok"]


def _with_submit(monkeypatch, wrap):
    """``build_engine`` whose engine's ``submit`` is ``wrap(submit)``
    once the warm-up is over (one request a row of every bucket); the
    wrapped one takes the offer's number, from 1."""
    real = serve_open_loop.build_engine

    def build(cfg, traffic, weights):
        engine, served = real(cfg, traffic, weights)
        submit, calls = engine.submit, itertools.count(1)
        wrapped, offers = wrap(submit), itertools.count(1)

        def counted(x):
            warm = next(calls) <= sum(traffic["buckets"])
            return submit(x) if warm else wrapped(x, next(offers))

        engine.submit = counted
        return engine, served

    monkeypatch.setattr(serve_open_loop, "build_engine", build)


def test_a_request_refused_and_admitted_later_is_no_failed_operation(
        sound_serve, monkeypatch):
    from deepvision_tpu.serve.engine import ShedError

    cell, _ = sound_serve

    def refuse_every_other(submit):
        def offer(x, n):
            if n % 2:
                raise ShedError("planted", 0.01)
            return submit(x)
        return offer

    _with_submit(monkeypatch, refuse_every_other)
    result = execute(cell, seconds=1.0)
    assert result["correct"] is True
    assert result["attempted"] == 20 and result["failed"] == 0
    notes = result["notes"]
    assert notes["offered_again"] == notes["offers_refused"] == 20
    assert notes["refused"] == 0 and notes["held_ms_max"] > 0


def test_an_admitted_request_that_is_never_answered_is_not_correct(
        sound_serve, monkeypatch):
    cell, _ = sound_serve

    def swallow_the_third(submit):
        return lambda x, n: Future() if n == 3 else submit(x)

    _with_submit(monkeypatch, swallow_the_third)
    monkeypatch.setattr(serve_open_loop, "window", functools.partial(
        serve_open_loop.window, grace_s=1.0))
    result = execute(cell, seconds=1.0)
    assert result["correct"] is False and result["failed"] == 1
    assert not result["checks"]["unanswered"]["ok"]
    assert result["notes"]["refused"] == 0


def test_the_bf16_control_in_the_programs_place_is_not_correct(sound_serve):
    """The reference in the control numerics answers in the program's
    place and is judged as a run judges; the reference at the stated
    precision, likewise, passes."""
    import jax

    from benchmark.harness import checks
    from benchmark.reference import plain, yolov3

    cell, _ = sound_serve
    cfg = cell.config
    v = jax.jit(lambda k: yolov3.make_weights(cfg, k))(plain.seed_key(9))
    picks = list(range(8))
    images = yolov3.make_images(cfg, 9, len(picks))
    verdicts = {}
    for name in (cfg["control"], cfg["stated_numerics"]):
        answers = serve_open_loop.reference_answers(
            cfg, yolov3, v, images, picks, plain.NUMERICS[name])
        got = serve_open_loop.judge(cfg, yolov3, v, images, picks, answers)
        verdicts[name] = (got["precision_excess"], checks.verdict(
            [c for c in serve_open_loop.serve_checks(cfg, got)
             if c.name == "precision_excess"]))
    assert verdicts[cfg["control"]][1] is False, verdicts
    assert verdicts[cfg["stated_numerics"]] == (0.0, True), verdicts


def test_precision_excess_is_the_variance_over_the_stated_noise():
    q = serve_open_loop.EXCESS_QUANTILES
    stated = dict.fromkeys(q, 0.01)
    same = serve_open_loop.precision_excess(stated, stated)
    double = serve_open_loop.precision_excess(dict.fromkeys(q, 0.02), stated)
    assert same == 0.0 and double == pytest.approx(3.0)
