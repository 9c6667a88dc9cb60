"""The whole of a run but the look for a chip, on the CPU at toy sizes:
the result line, and the timed path broken underneath."""

import json
import subprocess
import sys

import numpy as np
import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import train_resident
from benchmark.harness import cells

from bench_helpers import LINE_KEYS, execute



def test_run_py_refuses_to_measure_on_a_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "resnet50.train_resident", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cells.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "a result was printed without a TPU"
    assert "no TPU" in proc.stderr


@pytest.fixture(scope="module")
def sound_train():
    from bench_helpers import ROOT, tiny_cell

    cell = tiny_cell("resnet_tiny",
                     ROOT / "benchmark" / "traffic" / "train_resident.json",
                     [("train_img_per_s", "img/s/chip"), ("setup_s", "s")])
    return cell, execute(cell)


def test_train_result_line_has_the_contracts_keys(sound_train):
    _cell, result = sound_train
    assert list(result) == LINE_KEYS            # checks come last
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_img_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["checks"]) >= {"loss_gap", "grad_gap", "update_gap"}
    json.dumps(result)


def _broken_train(monkeypatch, wrap):
    real = train_resident.build_program

    def build(cfg, mesh, weights):
        step, state = real(cfg, mesh, weights)
        return wrap(step), state

    monkeypatch.setattr(train_resident, "build_program", build)


class _Lowered:
    """Stands where ``jit(...).lower(...).compile()`` is called."""

    def __init__(self, fn):
        self.fn = fn

    def lower(self, *a):
        return self

    def compile(self):
        return self.fn


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        sound_train, monkeypatch):
    cell, _ = sound_train

    def wrap(step):
        def unchanged(state, batch, key):
            import jax

            _new, metrics = step(jax.tree.map(lambda a: a.copy(), state),
                                 batch, key)
            return state, metrics
        return _Lowered(unchanged)

    _broken_train(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False
    assert result["checks"]["update_gap"]["value"] == pytest.approx(1.0)
    assert not result["checks"]["grad_gap"]["ok"]


def test_half_of_the_batch_left_out_is_not_correct(sound_train, monkeypatch):
    cell, _ = sound_train

    def wrap(step):
        def half(state, batch, key):
            import jax

            rows = batch["label"].shape[0] // 2
            return step(state, jax.tree.map(lambda a: a[:rows], batch), key)
        return _Lowered(half)

    _broken_train(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False


def test_the_fp8_control_in_the_programs_place_is_not_correct(sound_train):
    import jax

    from benchmark.reference import plain, resnet50

    cell, _ = sound_train
    cfg = cell.config
    weights, batch = train_resident.seeded(cfg, resnet50, 5,
                                           cfg["batch_per_chip"])
    p0 = jax.tree.map(np.asarray, weights)
    truth = train_resident.reference_steps(cfg, resnet50, plain.HIGHEST,
                                           batch, p0, 3)
    control = train_resident.reference_steps(
        cfg, resnet50, plain.NUMERICS[cfg["control"]], batch, p0, 3)
    got = train_resident.compare(control, truth, p0)
    limits = cfg["limits"]["train"]
    assert any(got[k] > limits[k] for k in limits), got
