"""A whole run of the traffic kind ``train_resident_seq`` on the CPU at
the toy configuration of the token model: the result line, and the timed
path broken underneath (state unchanged, half of the batch left out) and
the control in the program's place, each judged as a run judges it."""

import json

import numpy as np
import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.drivers import train_resident_seq
from benchmark.harness import cells

from bench_helpers import LINE_KEYS, ROOT, execute, tiny_cell


@pytest.fixture(scope="module")
def sound():
    cell = tiny_cell(
        "keye_vl2_tiny", ROOT / "benchmark" / "traffic" / "train_seq8k.json",
        [("train_img_per_s", "img/s/chip"), ("setup_s", "s")],
        [("moe_load_skew.train", "ratio"), ("step_mfu_pct.train", "%")])
    return cell, execute(cell)


def test_the_cell_in_benchmark_json_loads_with_its_files():
    cell = cells.load_cell("keye_vl2_30b_a3b.train_seq8k")
    assert cell.chips == 1 and cell.traffic["kind"] == "train_resident_seq"
    assert cells.driver_for(cell) is train_resident_seq
    assert cells.reference_for(cell.config, cell.config_name).__name__ \
        .endswith("keye_vl2")
    assert [m["name"] for m in cell.end_to_end] == ["train_img_per_s",
                                                    "setup_s"]
    reported = {m["name"] for m in cell.per_layer}
    assert reported >= {     # a later metric of this cell adds a name
        "compile_s", "step_mfu_pct.train", "conv_time_pct.train",
        "device_idle_pct.train", "indexer_time_pct.train",
        "select_time_pct.train", "attn_time_pct.train",
        "moe_time_pct.train", "vision_time_pct.train",
        "moe_load_skew.train"}
    assert set(cell.config["limits"]["train"]) == {
        "loss_gap", "grad_gap", "update_gap", "moe_dropped"}


def test_result_line_has_the_contracts_keys(sound):
    _cell, result = sound
    assert list(result) == LINE_KEYS            # checks come last
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_img_per_s", "setup_s"}
    assert set(result["checks"]) == {"loss_gap", "grad_gap", "update_gap",
                                     "moe_dropped", "last_loss_not_finite"}
    notes = result["notes"]
    assert notes["moe_dropped"] == 0 and notes["steps"] >= 1
    # what only the setting of the limits needs is not paid by a run
    assert "route_flip_share" not in notes
    assert notes["dsa_selected_pairs"] >= 4 * 2 * 904   # rows x layers
    json.dumps(result)


def test_the_counts_reach_the_readers_and_the_registry(sound):
    from deepvision_tpu.obs import default_registry

    cell, result = sound
    spec = cells.metric_file("moe_load_skew.train")
    facts = {"train": {"moe_expert_tokens_max":
                       result["notes"]["moe_expert_tokens_max"],
                       "moe_expert_tokens_mean":
                       result["notes"]["moe_expert_tokens_mean"]}}
    assert cells.reader_for(spec).read(facts, spec) >= 1.0
    assert default_registry().value_of("moe_expert_tokens_max") \
        == result["notes"]["moe_expert_tokens_max"]
    assert default_registry().value_of("dsa_selected_pairs") > 0


def _broken(monkeypatch, wrap):
    real = train_resident_seq.build_program

    def build(cfg, mesh, weights):
        step, make_state = real(cfg, mesh, weights)
        return wrap(step), make_state

    monkeypatch.setattr(train_resident_seq, "build_program", build)


class _Lowered:
    """Stands where ``jit(...).lower(...).compile()`` is called."""

    def __init__(self, fn):
        self.fn = fn

    def lower(self, *a):
        return self

    def compile(self):
        return self.fn


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


def test_the_fp8_control_in_the_programs_place_is_not_correct(sound):
    import jax

    from benchmark.reference import keye_vl2, plain

    cell, _ = sound
    cfg = cell.config
    weights, batch = train_resident_seq.seeded(cfg, keye_vl2, 5,
                                               cfg["batch_per_chip"])
    p0 = jax.tree.map(np.asarray, weights)
    truth = train_resident_seq.reference_steps(
        cfg, keye_vl2, plain.HIGHEST, batch, p0, 3)
    control = train_resident_seq.reference_steps(
        cfg, keye_vl2, plain.NUMERICS[cfg["control"]], batch, p0, 3)
    got = train_resident_seq.compare(control, truth, p0)
    limits = {k: v for k, v in cfg["limits"]["train"].items()
              if k != "moe_dropped"}
    assert any(got[k] > limits[k] for k in limits), got
    # calibrate's comparison from per-leaf norms reads the same numbers
    light = train_resident_seq.compare_norms(
        train_resident_seq.norms_of(control, p0),
        train_resident_seq.norms_of(truth, p0))
    for key in ("loss_gap", "grad_gap", "update_gap"):
        assert light[key] == pytest.approx(got[key], rel=1e-9)
    assert (light["grad_leaf"], light["update_leaf"],
            light["skipped_leaves"]) == (
        got["grad_leaf"], got["update_leaf"], got["skipped_leaves"])


def test_calibrate_judges_program_control_and_fault_by_the_runs_checks(sound):
    cell, _ = sound
    readings = {r["reading"]: r for r in train_resident_seq.calibrate(
        cell, [5], control=True, faults=True)}
    assert list(readings) == ["program", "control:fp8_operands",
                              "fault:half_batch"]
    program = readings["program"]
    assert program["correct"] is True and program["moe_dropped"] == 0
    assert "route_flip_share" not in program    # a call of its own
    assert readings["control:fp8_operands"]["correct"] is False
    assert readings["fault:half_batch"]["correct"] is False
    assert readings["fault:half_batch"]["grad_gap"] \
        > cell.config["limits"]["train"]["grad_gap"]


def test_calibrate_asked_for_no_upper_reading_reads_the_flips(sound):
    cell, _ = sound
    (program,) = train_resident_seq.calibrate(cell, [5], control=False,
                                              faults=False)
    assert program["reading"] == "program" and program["correct"] is True
    assert 0 <= program["route_flip_share"] < 0.1
    assert 0 <= program["select_flip_share"] < 0.1


def test_the_programs_warm_up_is_the_references(sound):
    """Update ``n`` (from 1) takes ``lr * n / warmup_steps``: the
    program's schedule and the reference's, over the peak's first step
    too, in float32 so that only the rule could differ."""
    import jax

    from benchmark.reference import keye_vl2, plain
    from deepvision_tpu.core import create_mesh

    cell, _ = sound
    cfg = dict(cell.config, compute_dtype="float32", optimizer={
        **cell.config["optimizer"], "lr": 1e-3, "warmup_steps": 3})
    weights, batch = train_resident_seq.seeded(cfg, keye_vl2, 7, 2)
    p0 = jax.tree.map(np.asarray, weights)
    step, make_state = train_resident_seq.build_program(
        cfg, create_mesh(1, 1), weights)
    moved = []
    state = make_state(weights)
    for _ in range(4):
        before = jax.tree.map(np.asarray, state.params)
        state, _metrics = step(state, batch, jax.random.key(0))
        moved.append(max(float(np.max(np.abs(np.asarray(a) - b)))
                         for a, b in zip(jax.tree.leaves(state.params),
                                         jax.tree.leaves(before))))
    # Adam's first update is the rate x sign(gradient)
    assert moved[0] == pytest.approx(1e-3 / 3, rel=0.02)
    assert moved[0] < moved[1] < moved[2] and moved[3] < 1.3 * moved[2]
    truth = train_resident_seq.reference_steps(
        cfg, keye_vl2, plain.HIGHEST, batch, p0, 4)
    after = plain.tree_paths(jax.tree.map(np.asarray, state.params))
    got = train_resident_seq.compare(dict(truth, params_after=after),
                                     truth, p0)
    assert got["update_gap"] < 2e-3, got
