"""A whole run of the traffic kind ``train_resident_lm`` on the CPU at
the toy configuration of the text token model: the result line, and the
timed path broken underneath (state unchanged, half of the batch left
out, a bias never moved, a dropped assignment) and the control in the
program's place, each judged as a run judges it."""

import json

import numpy as np
import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import train_resident_lm
from benchmark.harness import cells

from bench_helpers import LINE_KEYS, ROOT, execute, tiny_cell

BIAS = train_resident_lm.BIAS


class _Lowered:
    """Stands where ``jit(...).lower(...).compile()`` is called."""

    def __init__(self, fn):
        self.fn = fn

    def lower(self, *a):
        return self

    def compile(self):
        return self.fn


class _Once:
    """The program's jitted step, compiled ahead of time once for all
    the runs of this file that ask for the same shapes (a run compiles
    its step anew; here that is the same program seven times over, and
    the suite's timing-bound drills run beside this file)."""

    def __init__(self, step):
        self.step, self.compiled = step, {}

    def lower(self, *args):
        import jax

        shapes = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), args))
        if shapes not in self.compiled:
            self.compiled[shapes] = self.step.lower(*args).compile()
        return _Lowered(self.compiled[shapes])

    def __call__(self, *args):
        return self.lower(*args).compile()(*args)


@pytest.fixture(scope="module", autouse=True)
def one_program():
    """Every ``build_program`` of this file's cells hands out the one
    program built first for that configuration."""
    real, built = train_resident_lm.build_program, {}

    def build(cfg, mesh, weights):
        key = json.dumps(cfg, sort_keys=True)
        if key not in built:
            step, make_state = real(cfg, mesh, weights)
            built[key] = _Once(step), make_state
        return built[key]

    train_resident_lm.build_program = build
    yield
    train_resident_lm.build_program = real


@pytest.fixture(scope="module")
def sound():
    cell = tiny_cell(
        "kanana2_tiny", ROOT / "benchmark" / "traffic" / "train_text8k.json",
        [("train_img_per_s", "img/s/chip"), ("setup_s", "s")],
        [("moe_bias_load_skew.train", "ratio"),
         ("step_mfu_pct.train", "%")])
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
    # the program emits no alignment loss to please the sibling's driver
    assert "index_loss" not in notes and "dsa_selected_pairs" not in notes
    # what only the setting of the limits needs is not paid by a run
    assert "route_flip_share" not in notes
    # the drift is read from the start: assignments after the checked
    # steps and at the window's last step, and how far the bias has gone
    assert notes["moe_local_assignments_before_window"] > 0
    assert notes["moe_local_assignments"] > 0
    steps = notes["steps"] + 2
    assert 0 < notes["moe_bias_abs_mean"] <= 1e-3 * steps * 1.0001
    assert notes["attn_causal_pairs"] == 4 * 3 * 64 * 65 // 2
    json.dumps(result)


def test_the_counts_reach_the_readers_and_the_registry(sound):
    from deepvision_tpu.obs import default_registry

    _cell, result = sound
    spec = cells.metric_file("moe_bias_load_skew.train")
    facts = {"train": {k: result["notes"][k] for k in
                       ("moe_expert_tokens_max", "moe_expert_tokens_mean")}}
    assert cells.reader_for(spec).read(facts, spec) >= 1.0
    assert default_registry().value_of("moe_bias_abs_mean") \
        == result["notes"]["moe_bias_abs_mean"]
    assert default_registry().value_of("attn_causal_pairs") > 0


def _broken(monkeypatch, wrap):
    real = train_resident_lm.build_program

    def build(cfg, mesh, weights):
        step, make_state = real(cfg, mesh, weights)
        return wrap(step), make_state

    monkeypatch.setattr(train_resident_lm, "build_program", build)


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
    assert not result["checks"]["grad_gap"]["ok"]
    assert not result["checks"]["bias_gap"]["ok"]


def test_a_bias_that_is_never_moved_is_not_correct(sound, monkeypatch):
    """The leaf ``update_gap`` cannot see (its gradient is 0): a step
    that trains every weight and leaves the rule out reads ``bias_gap``
    near 1 and passes everything else."""
    cell, _ = sound

    def wrap(step):
        def no_rule(state, batch, key):
            import jax
            import jax.numpy as jnp

            from deepvision_tpu.models.latent_moe import is_router_bias

            new, metrics = step(state, batch, key)
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: jnp.zeros_like(a) if is_router_bias(path)
                else a, new.params)
            return new.replace(params=params), metrics
        return _Lowered(no_rule)

    _broken(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False
    assert result["checks"]["bias_gap"]["value"] > 0.8
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


def test_a_dropped_assignment_is_not_correct(sound, monkeypatch):
    cell, _ = sound

    def wrap(step):
        def dropping(state, batch, key):
            new, metrics = step(state, batch, key)
            return new, dict(metrics, moe_dropped=metrics["moe_dropped"] + 1)
        return _Lowered(dropping)

    _broken(monkeypatch, wrap)
    result = execute(cell)
    assert result["correct"] is False
    assert not result["checks"]["moe_dropped"]["ok"]
    assert result["checks"]["grad_gap"]["ok"]


def test_calibrate_judges_program_control_and_fault_by_the_runs_checks(sound):
    cell, _ = sound
    readings = {r["reading"]: r for r in train_resident_lm.calibrate(
        cell, [5], control=True, faults=True)}
    assert list(readings) == ["program", "control:fp8_operands",
                              "fault:half_batch"]
    program = readings["program"]
    assert program["correct"] is True and program["moe_dropped"] == 0
    assert 0 <= program["bias_gap"] < cell.config["limits"]["train"][
        "bias_gap"]
    assert "route_flip_share" not in program    # a call of its own
    assert readings["control:fp8_operands"]["correct"] is False
    assert readings["fault:half_batch"]["correct"] is False
    assert readings["fault:half_batch"]["grad_gap"] \
        > cell.config["limits"]["train"]["grad_gap"]


def test_calibrate_asked_for_no_upper_reading_reads_the_flips(sound):
    cell, _ = sound
    (program,) = train_resident_lm.calibrate(cell, [5], control=False,
                                             faults=False)
    assert program["reading"] == "program" and program["correct"] is True
    assert 0 <= program["route_flip_share"] < 0.1


def test_bias_gap_is_the_mean_move_over_the_rules_largest():
    cfg = {"bias_update_rate": 1e-3}
    traffic = {"checked_steps": 2}
    truth = {BIAS: np.asarray([[2e-3, -2e-3, 0.0, 2e-3]])}
    gap = lambda after: train_resident_lm.bias_gap(
        cfg, traffic, {BIAS: np.asarray(after)}, truth)
    assert gap(truth[BIAS]) == 0.0
    assert gap([[0.0, 0.0, 0.0, 0.0]]) == pytest.approx(0.75)   # never moved
    assert gap([[2e-3, -2e-3, 0.0, 0.0]]) == pytest.approx(0.25)  # one sign
    assert gap([[-2e-3, 2e-3, 0.0, -2e-3]]) == pytest.approx(1.5)
