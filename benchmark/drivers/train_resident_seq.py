"""Traffic kind ``train_resident_seq``: ``train_resident`` for a token
model. One seeded batch of samples (an image at the head of a token
sequence) on the device, the program's compiled train step driven back
to back for the whole window.

What differs from ``train_resident``: the program is built from the
model's own sample input (a dict of image and tokens, not an NHWC
image), the optimiser is Adam behind a linear warm-up, whose first
moment after step 1 is the gradient times ``1 - beta1`` (the reference
returns the same), nothing
that is as large as the parameters is held twice on the device (the
state is 16 bytes a parameter and fills most of the chip), and the
comparison also holds the step to ``moe_dropped`` = 0. The routing and
selection counts of the window's last step go to the registry's
counters and under ``facts["train"]``. Routing and selection are
discrete, so :func:`calibrate` also reads the share of routing choices
and of selected keys in which the program's first forward differs from
the reference's: a limit widened by flips is seen as such where the
limits are set (a run of the benchmark does not pay for it).
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
from benchmark.harness import checks
from benchmark.harness.device import memory_peak_bytes, peaks
from benchmark.reference import plain

UPPER_SEEDS = 2

COUNTS = ("moe_local_assignments", "moe_expert_tokens_max",
          "moe_expert_tokens_mean", "moe_dropped", "dsa_selected_pairs")


def _model(cfg, **kwargs):
    import jax.numpy as jnp

    from deepvision_tpu.models import get_model

    prog = cfg["program"]
    return get_model(prog["model"], dtype=jnp.dtype(cfg["compute_dtype"]),
                     **prog.get("model_kwargs", {}), **kwargs)


def build_program(cfg: dict, mesh, weights):
    """The program's objects: -> (jitted step, a function from weights to
    the TrainState that holds them). Tests plant their faults by
    wrapping what this returns."""
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
        index_loss_weight=cfg["index_loss_weight"])
    return compile_train_step(step_fn, mesh), make_state


def first_moment_of(opt_state):
    import optax

    return optax.tree_utils.tree_get(opt_state, "mu")


def checked_steps(step, state, batch, key, n: int):
    """Drive ``n`` steps through the window's own call and keep what the
    comparison needs, on the host. -> (state, kept, last metrics)."""
    import jax

    flat = lambda t: plain.tree_paths(jax.tree.map(np.asarray, t))
    losses, first, metrics = [], None, None
    for i in range(n):
        state, metrics = step(state, batch, key)
        losses.append(metrics["loss"])
        if i == 0:      # to the host before the next step donates it
            first = flat(first_moment_of(state.opt_state))
    kept = {"losses": [float(v) for v in losses], "first_grad": first,
            "params_after": flat(state.params)}
    return state, kept, metrics


def flip_reader(cfg, ref):
    """-> a function of (``p0``, batch): of the first sample's first
    forward from ``p0``, the share of the reference's routing choices,
    and of its selected keys, that the program did not make. Two
    programs, compiled once for all seeds."""
    import jax

    model = _model(cfg, capture=True)
    discrete = lambda out: {k: out[k] for k in ("experts", "masks")}
    program = jax.jit(lambda p, b: jax.tree.map(
        lambda a: a[0], discrete(model.apply({"params": p}, b, train=True))))
    reference = jax.jit(lambda p, b: discrete(ref.forward_sample(
        cfg, p, b["image"][0], b["tokens"][0], plain.HIGHEST, capture=True)))

    def read(p0, batch) -> dict:
        sample = jax.tree.map(lambda a: a[:1], batch)
        params = jax.device_put(p0)
        got = jax.tree.map(np.asarray, program(params, sample))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.map(np.asarray, reference(params, sample))
        chosen = lambda e: np.sort(e, -1)
        return {
            "route_flip_share": float(np.mean(
                chosen(got["experts"]) != chosen(want["experts"]))),
            "select_flip_share": float(
                np.sum(want["masks"] & ~got["masks"])
                / np.sum(want["masks"])),
        }

    return read


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
    # beside the window's last step's: a router that drifts on the one
    # resident batch changes the expert layer's work through the window
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
    got["moe_dropped"] = last["moe_dropped"]
    result_checks = train_checks(cfg, got)
    result_checks.append(checks.Check(
        "last_loss_not_finite", 0.0 if np.isfinite(last["loss"]) else 1.0,
        0.0))

    samples = steps * rows
    # the window's own numbers, should a later stage end the run
    print(f"[train_resident_seq] steps={steps} window_s={window_s:.3f} "
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
                  "moe_local_assignments_before_window": local_before,
                  **{k: last[k] for k in COUNTS}},
        "compile_s": compile_s,
        "notes": {"steps": steps, "window_s": window_s,
                  "step_gap_ms_max": step_gap * 1e3,
                  "losses": kept["losses"], "ref_losses": got["ref_losses"],
                  "loss_gaps": got["loss_gaps"],
                  "grad_leaf": got["grad_leaf"],
                  "update_leaf": got["update_leaf"],
                  "skipped_leaves": got["skipped_leaves"],
                  "last_loss": last["loss"],
                  "lm_loss": last["lm_loss"],
                  "index_loss": last["index_loss"],
                  "moe_local_assignments_before_window": local_before,
                  **{k: last[k] for k in COUNTS}},
    }


def norms_of(steps: dict, p0: dict) -> dict:
    """What :func:`train_resident.compare` takes of a reading, leaf by
    leaf as the reading arrives, so that the trees themselves need not
    stay: the losses, each leaf's norm of the first gradient and of the
    parameters' change."""
    f_p0 = plain.tree_paths(p0)
    return {"losses": steps["losses"],
            "grad": checks.leaf_norms(steps["first_grad"]),
            "moved": {k: float(np.linalg.norm(
                (np.asarray(steps["params_after"][k], np.float64)
                 - f_p0[k]).ravel())) for k in f_p0}}


def compare_norms(kept: dict, truth: dict) -> dict:
    """:func:`train_resident.compare`'s numbers from two
    :func:`norms_of` (``tests/benchmark/test_run_keye.py`` holds the two
    to each other)."""
    median = float(np.median(list(truth["grad"].values())))
    still = {k for k, v in truth["grad"].items() if v < 1e-3 * median}
    grad_gap, grad_leaf = checks.worst_leaf_gap(kept["grad"], truth["grad"])
    upd_gap, upd_leaf = checks.worst_leaf_gap(kept["moved"], truth["moved"],
                                              skip=still)
    loss_gaps = [checks.rel_gap(a, b)
                 for a, b in zip(kept["losses"], truth["losses"])]
    return {"loss_gap": max(loss_gaps), "loss_gaps": loss_gaps,
            "grad_gap": grad_gap, "grad_leaf": "/".join(grad_leaf or ()),
            "update_gap": upd_gap, "update_leaf": "/".join(upd_leaf or ()),
            "skipped_leaves": len(still)}


def calibrate(cell, seeds, *, control: bool, faults: bool, **_):
    """Readings for the limits (``benchmark/calibrate.py``): per seed the
    program against the float32 reference; with ``control`` the
    reference in the configuration's control numerics, with ``faults``
    the reference with half of the batch left out, each put in the
    program's place and judged by the same checks, which it has to
    fail. The lower readings have to be many and the upper ones need
    not be: the control (a compile of its own) and the fault are read on
    the first ``UPPER_SEEDS`` seeds. A call that asks for neither reads
    the flips on its first seed (two more programs to compile). One
    thing at a time is on the device, and of every reading the host
    keeps the per-leaf norms only: a machine with one chip has 40 GiB, a
    reading's two trees are 5.4 GB, and compiling the reference takes
    gigabytes beside them."""
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
        del state, weights, metrics
        kept = norms_of(kept, p0)
        truth = norms_of(reference_steps(cfg, ref, plain.HIGHEST, batch, p0,
                                         n), p0)

        def judged(reading, norms):
            got = {**compare_norms(norms, truth), "moe_dropped": dropped}
            return {"seed": seed, "reading": reading, **got,
                    "correct": checks.verdict(train_checks(cfg, got))}

        yield {**judged("program", kept),
               **(flips(p0, batch) if flips and i == 0 else {})}
        if i >= UPPER_SEEDS:
            continue
        if control:
            nm = plain.NUMERICS[cfg["control"]]
            yield judged(f"control:{nm.name}", norms_of(reference_steps(
                cfg, ref, nm, batch, p0, n), p0))
        if faults:
            yield judged("fault:half_batch", norms_of(reference_steps(
                cfg, ref, plain.HIGHEST, batch, p0, n,
                rows=(0, rows // 2)), p0))
