"""A whole run of the traffic kind ``train_resident_mtp`` on the CPU at
the toy configuration of the hyper-connected token model: the result
line, and the timed path broken underneath (state unchanged, half of the
batch left out, the MTP block's bias never moved) and the control in the
program's place, each judged as a run judges it."""

import json

import numpy as np
import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import train_resident_mtp

from bench_helpers import LINE_KEYS, ROOT, execute, tiny_cell
from test_kanana_run import _Lowered, _Once

MTP_BIAS = ("mtp", "block", "moe", "bias")


@pytest.fixture(scope="module", autouse=True)
def one_program():
    """Every ``build_program`` of this file's cells hands out the one
    program built first for that configuration."""
    real, built = train_resident_mtp.build_program, {}

    def build(cfg, mesh, weights):
        key = json.dumps(cfg, sort_keys=True)
        if key not in built:
            step, make_state = real(cfg, mesh, weights)
            built[key] = _Once(step), make_state
        return built[key]

    train_resident_mtp.build_program = build
    yield
    train_resident_mtp.build_program = real


@pytest.fixture(scope="module")
def sound():
    cell = tiny_cell(
        "xing4_tiny", ROOT / "benchmark" / "traffic" / "train_mtp.json",
        [("train_img_per_s", "img/s/chip"), ("setup_s", "s")],
        [("step_mfu_pct.train", "%")])
    return cell, execute(cell)


def test_result_line_has_the_contracts_keys(sound):
    _cell, result = sound
    assert list(result) == LINE_KEYS            # checks come last
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_img_per_s", "setup_s"}
    assert set(result["checks"]) == {
        "loss_gap", "grad_gap", "update_gap", "bias_gap", "moe_dropped",
        "last_loss_not_finite"}
    notes = result["notes"]
    assert notes["moe_dropped"] == 0 and notes["steps"] >= 1
    # the two losses and the Sinkhorn error of the window's last step
    assert notes["last_loss"] == pytest.approx(
        notes["lm_loss"] + 0.3 * notes["mtp_loss"], rel=1e-5)
    assert 0 <= notes["mhc_sinkhorn_err"] < 1e-4
    # 4 blocks of 64 positions a document, 2 documents
    assert notes["attn_causal_pairs"] == 4 * 4 * 64 * 65 // 2
    json.dumps(result)


def _broken(monkeypatch, wrap):
    real = train_resident_mtp.build_program

    def build(cfg, mesh, weights):
        step, make_state = real(cfg, mesh, weights)
        return wrap(step), make_state

    monkeypatch.setattr(train_resident_mtp, "build_program", build)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        sound, monkeypatch):
    cell, _ = sound

    def wrap(step):
        def unchanged(state, batch, key):
            import jax

            _new, metrics = step(jax.tree.map(lambda a: a.copy(), state),
                                 batch, key)
            return state, metrics
        return _Lowered(unchanged)

    _broken(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False
    assert result["checks"]["update_gap"]["value"] == pytest.approx(1.0)
    assert not result["checks"]["bias_gap"]["ok"]


def test_an_mtp_bias_that_is_never_moved_is_not_correct(sound, monkeypatch):
    """The MTP block's bias is a leaf of its own that the rule moves from
    the last row of the counts: a step that leaves it alone fails
    ``bias_gap``."""
    cell, _ = sound

    def wrap(step):
        def no_rule(state, batch, key):
            new, metrics = step(state, batch, key)
            params = dict(new.params, mtp={**new.params["mtp"], "block": {
                **new.params["mtp"]["block"], "moe": {
                    **new.params["mtp"]["block"]["moe"],
                    "bias": state.params["mtp"]["block"]["moe"]["bias"]}}})
            return new.replace(params=params), metrics
        return _Lowered(no_rule)

    _broken(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False
    assert not result["checks"]["bias_gap"]["ok"]
    for name in ("loss_gap", "grad_gap", "moe_dropped"):
        assert result["checks"][name]["ok"], name


def test_half_of_the_batch_left_out_is_not_correct(sound, monkeypatch):
    cell, _ = sound

    def wrap(step):
        def half(state, batch, key):
            import jax

            rows = batch["tokens"].shape[0] // 2
            return step(state, jax.tree.map(lambda a: a[:rows], batch), key)
        return _Lowered(half)

    _broken(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False
    assert not result["checks"]["grad_gap"]["ok"]


def test_calibrate_judges_program_control_and_fault_by_the_runs_checks(sound):
    cell, _ = sound
    readings = {r["reading"]: r for r in train_resident_mtp.calibrate(
        cell, [5], control=True, faults=True)}
    assert list(readings) == ["program", "control:fp8_operands",
                              "fault:half_batch"]
    program = readings["program"]
    assert program["correct"] is True and program["moe_dropped"] == 0
    assert 0 <= program["mhc_sinkhorn_err"] < 1e-4
    assert readings["control:fp8_operands"]["correct"] is False
    assert readings["fault:half_batch"]["correct"] is False


def test_bias_gap_is_the_largest_leafs_mean_move():
    cfg = {"bias_update_rate": 1e-3}
    traffic = {"checked_steps": 2}
    truth = {("layers", "moe", "bias"): np.asarray([[2e-3, -2e-3, 0.0]]),
             MTP_BIAS: np.asarray([2e-3])}
    gap = lambda after: train_resident_mtp.bias_gap(cfg, traffic, after,
                                                    truth)
    assert gap(truth) == 0.0
    # the MTP leaf never moved reads a whole move, whatever the others do
    assert gap({**truth, MTP_BIAS: np.zeros(1)}) == pytest.approx(1.0)
    # one sign of the three stacked entries turned
    stacked = {**truth, ("layers", "moe", "bias"): np.asarray(
        [[2e-3, 2e-3, 0.0]])}
    assert gap(stacked) == pytest.approx(2 / 3)
