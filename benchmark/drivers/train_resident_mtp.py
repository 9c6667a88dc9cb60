"""Traffic kind ``train_resident_mtp``: ``train_resident_lm`` for a text
token model with a multi-token-prediction module and hyper-connected
residual streams (``models/hyper_latent.py``). One seeded batch of
documents on the device, the program's compiled train step driven back
to back for the whole window.

What differs from ``train_resident_lm``: the loss the step takes and the
reference computes is the next-token loss plus the configuration's
``mtp_loss_weight`` times the MTP module's; the bias rule moves more
than one leaf (the stacked expert blocks' and the MTP block's), and
``bias_gap`` is the largest of their readings. ``notes`` and
``facts["train"]`` add the window's last step's ``lm_loss``,
``mtp_loss`` and ``mhc_sinkhorn_err`` (the largest ``|row or column sum
- 1|`` of any hyper-connection's mixing matrix).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark.drivers.train_resident import (
    compare,
    reference_steps,
    seeded,
    train_checks,
)
from benchmark.drivers.train_resident_lm import COUNTS, flip_reader
from benchmark.drivers.train_resident_seq import (
    UPPER_SEEDS,
    _model,
    checked_steps,
    compare_norms,
    norms_of,
)
from benchmark.harness import checks
from benchmark.harness.device import memory_peak_bytes, peaks
from benchmark.reference import plain

# the leaves the rule moves: the stacked expert blocks', the MTP block's
BIASES = (("layers", "moe", "bias"), ("mtp", "block", "moe", "bias"))
MTP_COUNTS = ("lm_loss", "mtp_loss", "mhc_sinkhorn_err")


def build_program(cfg: dict, mesh, weights):
    """The program's objects: -> (jitted step, a function from weights to
    the TrainState that holds them), as ``train_resident_lm``'s, the
    step told the MTP loss's weight."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp

    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.train.configs import TRAINING_CONFIG
    from deepvision_tpu.train.optimizers import make_optimizer
    from deepvision_tpu.train.state import TrainState

    prog, opt = cfg["program"], cfg["optimizer"]
    model = _model(cfg)
    tcfg = dict(TRAINING_CONFIG[prog["training_config"]])
    tcfg["optimizer_params"] = {
        **tcfg.get("optimizer_params", {}),
        **{k: opt[k] for k in ("lr", "beta1", "beta2", "eps")}}
    tcfg["scheduler"] = "warmup"
    tcfg["scheduler_params"] = {"warmup_steps": opt["warmup_steps"]}
    tx, _ = make_optimizer(tcfg, 1000)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model.sample_input()))
    checks.require_same_tree(shapes["params"], weights, "parameter")
    init = jax.jit(tx.init)

    def make_state(params):
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          batch_stats={}, opt_state=init(params),
                          apply_fn=model.apply, tx=tx)

    step_fn = functools.partial(
        getattr(importlib.import_module("deepvision_tpu.train.steps"),
                prog["train_step"]),
        bias_rate=cfg["bias_update_rate"], mtp_weight=cfg["mtp_loss_weight"])
    return compile_train_step(step_fn, mesh), make_state


def bias_gap(cfg, traffic, after, truth_after) -> float:
    """Of the leaves the rule moves, the largest mean ``|b -
    b_reference|`` over the rule's largest possible move, from two flat
    ``params_after``: a leaf the rule left alone reads about 1 whatever
    the others do."""
    return max(float(np.mean(np.abs(
        np.asarray(after[k], np.float64)
        - np.asarray(truth_after[k], np.float64))))
        / (cfg["bias_update_rate"] * traffic["checked_steps"])
        for k in BIASES)


def run(run) -> dict:
    import jax

    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.obs.metrics import record_token_step

    cfg, traffic, ref = run.cell.config, run.cell.traffic, run.reference
    chips = run.cell.chips
    mesh = create_mesh(chips, 1)
    rows = cfg["batch_per_chip"] * chips

    weights, batch = seeded(cfg, ref, run.seed, rows)
    batch = shard_batch(mesh, batch)
    p0 = jax.tree.map(np.asarray, weights)
    step, make_state = build_program(cfg, mesh, weights)
    state = make_state(weights)
    key = jax.random.key(0)
    compiled = step.lower(state, batch, key).compile()
    state, kept, metrics = checked_steps(compiled, state, batch, key,
                                         traffic["checked_steps"])
    jax.block_until_ready(state)
    local_before = float(metrics["moe_local_assignments"])

    # ---- the window (as train_resident's)
    in_flight = traffic["in_flight"]
    tracing = False
    trace_at = max(0.0, run.seconds - traffic["trace_seconds"])
    compiles_before = run.compiles.count
    compile_s = run.compiles.seconds
    setup_s = run.setup_seconds()
    pending = []
    steps = 0
    t0 = last = time.perf_counter()
    deadline = t0 + run.seconds
    step_gap = 0.0
    while True:
        now = time.perf_counter()
        step_gap, last = max(step_gap, now - last), now
        if now >= deadline:
            break
        if run.trace and not tracing and now - t0 >= trace_at:
            jax.profiler.start_trace(run.trace_dir)
            tracing = True
        state, metrics = compiled(state, batch, key)
        steps += 1
        pending.append(metrics["loss"])
        if len(pending) > in_flight:
            pending.pop(0).block_until_ready()
    jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    if tracing:
        jax.profiler.stop_trace()
    compiles_in_window = run.compiles.count - compiles_before
    last = {k: float(v) for k, v in metrics.items()}
    record_token_step(last)

    memory = memory_peak_bytes(jax.devices()[:chips])
    del state, compiled, metrics, pending, weights

    truth = reference_steps(cfg, ref, plain.HIGHEST, batch, p0,
                            traffic["checked_steps"])
    got = compare(kept, truth, p0)
    got["bias_gap"] = bias_gap(cfg, traffic, kept["params_after"],
                               truth["params_after"])
    got["moe_dropped"] = last["moe_dropped"]
    result_checks = train_checks(cfg, got)
    result_checks.append(checks.Check(
        "last_loss_not_finite", 0.0 if np.isfinite(last["loss"]) else 1.0,
        0.0))

    samples = steps * rows
    counts = {"moe_local_assignments_before_window": local_before,
              **{k: last[k] for k in COUNTS + MTP_COUNTS}}
    print(f"[train_resident_mtp] steps={steps} window_s={window_s:.3f} "
          f"samples_per_s={samples / window_s:.4f} "
          f"memory_peak_bytes={memory}", file=sys.stderr, flush=True)
    return {
        "end_to_end": {"train_img_per_s": samples / window_s / chips,
                       "setup_s": setup_s},
        "attempted": steps, "failed": 0,
        "checks": result_checks,
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles_in_window,
        "train": {"images": samples, "window_s": window_s, "chips": chips,
                  "flops_per_image": ref.train_flops_per_image(cfg),
                  "peak_flops": peaks(run.device["kind"])["bf16_flops_per_s"]
                  if run.device["platform"] == "tpu" else None,
                  **counts},
        "compile_s": compile_s,
        "notes": {"steps": steps, "window_s": window_s,
                  "step_gap_ms_max": step_gap * 1e3,
                  "losses": kept["losses"], "ref_losses": got["ref_losses"],
                  "loss_gaps": got["loss_gaps"],
                  "grad_leaf": got["grad_leaf"],
                  "update_leaf": got["update_leaf"],
                  "skipped_leaves": got["skipped_leaves"],
                  "last_loss": last["loss"], **counts},
    }


def calibrate(cell, seeds, *, control: bool, faults: bool, **_):
    """Readings for the limits (``benchmark/calibrate.py``), as
    ``train_resident_lm.calibrate`` takes them, every bias leaf the rule
    moves kept for ``bias_gap``."""
    import jax

    from benchmark.harness import cells
    from deepvision_tpu.core import create_mesh, shard_batch

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference_for(cfg, cell.config_name)
    mesh = create_mesh(cell.chips, 1)
    rows = cfg["batch_per_chip"] * cell.chips
    n = traffic["checked_steps"]
    key = jax.random.key(0)
    compiled = make_state = None
    flips = None if control or faults else flip_reader(cfg, ref)

    def light(steps, p0):
        return {**norms_of(steps, p0), "params_after": {
            k: steps["params_after"][k] for k in BIASES}}

    for i, seed in enumerate(seeds):
        weights, batch = seeded(cfg, ref, seed, rows)
        batch = shard_batch(mesh, batch)
        p0 = jax.tree.map(np.asarray, weights)
        if compiled is None:    # one program for all seeds
            step, make_state = build_program(cfg, mesh, weights)
        state = make_state(weights)
        if compiled is None:
            compiled = step.lower(state, batch, key).compile()
        state, kept, metrics = checked_steps(compiled, state, batch, key, n)
        dropped = float(metrics["moe_dropped"])
        sinkhorn_err = float(metrics["mhc_sinkhorn_err"])
        del state, weights, metrics
        kept = light(kept, p0)
        truth = light(reference_steps(cfg, ref, plain.HIGHEST, batch, p0, n),
                      p0)

        def judged(reading, norms):
            got = {**compare_norms(norms, truth), "moe_dropped": dropped,
                   "bias_gap": bias_gap(cfg, traffic, norms["params_after"],
                                        truth["params_after"])}
            return {"seed": seed, "reading": reading, **got,
                    "correct": checks.verdict(train_checks(cfg, got))}

        yield {**judged("program", kept), "mhc_sinkhorn_err": sinkhorn_err,
               **(flips(p0, batch) if flips and i == 0 else {})}
        if i >= UPPER_SEEDS:
            continue
        if control:
            nm = plain.NUMERICS[cfg["control"]]
            yield judged(f"control:{nm.name}", light(reference_steps(
                cfg, ref, nm, batch, p0, n), p0))
        if faults:
            yield judged("fault:half_batch", light(reference_steps(
                cfg, ref, plain.HIGHEST, batch, p0, n,
                rows=(0, rows // 2)), p0))
