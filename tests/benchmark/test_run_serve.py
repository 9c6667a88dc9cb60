"""The whole of a serving run but the look for a chip, on the CPU at a toy
image size: the result line, and the timed path broken underneath."""

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
    assert result["notes"]["served_detections"] > 0


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
