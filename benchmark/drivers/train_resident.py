"""Traffic kind ``train_resident``: one seeded batch on the device, the
program's compiled train step driven back to back for the whole window.

The system under test is the program's own ``compile_train_step`` over
its own step function, model, optimiser and ``TrainState``; the
benchmark brings the weights and the batch (from ``--seed``, made on the
device by the configuration's plain reference) and the clock.

Set-up builds one compiled step with its state, drives it through its
first ``checked_steps`` steps through the same call the window uses,
keeps what the comparison needs (each loss, the optimiser's momentum
after step 1, the parameters before and after), and hands that same
object to the window. After the window the peak memory is read, the
state is dropped, and the reference follows those steps in float32.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import checks
from benchmark.harness.device import memory_peak_bytes, peaks
from benchmark.reference import plain


def build_program(cfg: dict, mesh, weights):
    """The program's objects: -> (jitted step, TrainState holding
    ``weights``). Tests plant their faults by wrapping what this
    returns."""
    import importlib

    import jax
    import jax.numpy as jnp

    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.configs import TRAINING_CONFIG
    from deepvision_tpu.train.optimizers import make_optimizer
    from deepvision_tpu.train.state import TrainState

    prog = cfg["program"]
    dtype = jnp.dtype(cfg["compute_dtype"])
    model = get_model(prog["model"], dtype=dtype,
                      num_classes=cfg["num_classes"],
                      **prog.get("model_kwargs", {}))
    tcfg = dict(TRAINING_CONFIG[prog["training_config"]])
    opt = cfg["optimizer"]
    tcfg["optimizer_params"] = {
        **tcfg.get("optimizer_params", {}), "lr": opt["lr"],
        "momentum": opt["momentum"], "weight_decay": opt["weight_decay"]}
    tx, _ = make_optimizer(tcfg, 1000)

    size = cfg["input_size"]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros(
            (1, size, size, cfg["channels"]), jnp.float32), train=True))
    checks.require_same_tree(shapes["params"], weights, "parameter")
    stats = jax.tree_util.tree_map_with_path(
        lambda path, a: (jnp.ones if path[-1].key == "var" else jnp.zeros)(
            a.shape, a.dtype), shapes.get("batch_stats", {}))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=weights,
                       batch_stats=stats, opt_state=jax.jit(tx.init)(weights),
                       apply_fn=model.apply, tx=tx)
    step_fn = getattr(importlib.import_module("deepvision_tpu.train.steps"),
                      prog["train_step"])
    return compile_train_step(step_fn, mesh), state


def seeded(cfg, ref, seed: int, rows: int):
    """Weights and batch from the seed, made on the device. The seed's
    key is an argument of the two jitted makers, so that every seed runs
    the same two cached programs."""
    import jax

    key = plain.seed_key(seed)
    return (jax.jit(lambda k: ref.make_weights(cfg, k))(key),
            jax.jit(lambda k: ref.make_batch(cfg, k, rows))(key))


def momentum_of(opt_state):
    """The momentum buffer inside the program's optimiser state."""
    import optax

    return optax.tree_utils.tree_get(opt_state, "trace")


def reference_steps(cfg, ref, nm, batch, p0, n: int, rows=None) -> dict:
    """The reference follows ``n`` steps from ``p0`` on ``batch`` in
    numerics ``nm``; what it yields has the shape of what
    :func:`checked_steps` keeps of the program."""
    import jax

    flat = lambda t: plain.tree_paths(jax.tree.map(np.asarray, t))
    losses, grad, p = ref.train_steps(cfg, jax.device_put(p0), batch, n, nm,
                                      rows=rows)
    return {"losses": np.asarray(losses, np.float64).tolist(),
            "first_grad": flat(grad), "params_after": flat(p)}


def compare(kept: dict, truth: dict, p0) -> dict:
    """The numbers compared, ``kept`` (the timed path, or a control put
    in its place) against ``truth`` (the float32 reference)."""
    f_p0 = plain.tree_paths(p0)
    g_ref = checks.leaf_norms(truth["first_grad"])
    # a leaf whose gradient is nought to rounding moves by round-off alone
    median = float(np.median(list(g_ref.values())))
    still = {k for k, v in g_ref.items() if v < 1e-3 * median}
    moved = lambda after: checks.leaf_norms(
        {k: np.asarray(after[k], np.float64) - f_p0[k] for k in f_p0})
    grad_gap, grad_leaf = checks.worst_leaf_gap(
        checks.leaf_norms(kept["first_grad"]), g_ref)
    upd_gap, upd_leaf = checks.worst_leaf_gap(
        moved(kept["params_after"]), moved(truth["params_after"]),
        skip=still)
    loss_gaps = [checks.rel_gap(a, b)
                 for a, b in zip(kept["losses"], truth["losses"])]
    return {"loss_gap": max(loss_gaps), "loss_gaps": loss_gaps,
            "grad_gap": grad_gap, "grad_leaf": "/".join(grad_leaf or ()),
            "update_gap": upd_gap, "update_leaf": "/".join(upd_leaf or ()),
            "skipped_leaves": len(still), "ref_losses": truth["losses"]}


def checked_steps(step, state, batch, key, n: int):
    """Drive ``n`` steps through the window's own call and keep what the
    comparison needs, on the host. -> (state, kept)."""
    import jax
    import jax.numpy as jnp

    flat = lambda t: plain.tree_paths(jax.tree.map(np.asarray, t))
    losses, first = [], None
    for i in range(n):
        state, metrics = step(state, batch, key)
        losses.append(metrics["loss"])
        if i == 0:      # copied before the next step donates the buffer
            first = jax.tree.map(jnp.copy, momentum_of(state.opt_state))
    kept = {"losses": [float(v) for v in losses],
            "first_grad": flat(first),
            "params_after": flat(state.params)}
    return state, kept


def run(run) -> dict:
    import jax

    from deepvision_tpu.core import create_mesh, shard_batch

    cfg, traffic, ref = run.cell.config, run.cell.traffic, run.reference
    chips = run.cell.chips
    mesh = create_mesh(chips, 1)
    rows = cfg["batch_per_chip"] * chips

    weights, batch = seeded(cfg, ref, run.seed, rows)
    batch = shard_batch(mesh, batch)
    p0 = jax.tree.map(np.asarray, weights)
    step, state = build_program(cfg, mesh, weights)
    key = jax.random.key(0)
    compiled = step.lower(state, batch, key).compile()
    state, kept = checked_steps(compiled, state, batch, key,
                                traffic["checked_steps"])
    jax.block_until_ready(state)

    # ---- the window
    in_flight = traffic["in_flight"]
    # the traced run traces the window's last ``trace_seconds`` and
    # stops after the close: writing the profile out stalls the host for
    # seconds, which inside the window the rate (and step_mfu) would pay
    tracing = False
    trace_at = max(0.0, run.seconds - traffic["trace_seconds"])
    compiles_before = run.compiles.count
    compile_s = run.compiles.seconds
    setup_s = run.setup_seconds()
    pending = []
    steps = 0
    t0 = last = time.perf_counter()
    deadline = t0 + run.seconds
    # the longest the loop waited for one step: a sound run's is one
    # step, a stalled run's seconds (PERF.md section 2)
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
    last_loss = float(metrics["loss"])

    memory = memory_peak_bytes(jax.devices()[:chips])
    del state, compiled, metrics, pending, weights

    truth = reference_steps(cfg, ref, plain.HIGHEST, batch, p0,
                            traffic["checked_steps"])
    got = compare(kept, truth, p0)
    result_checks = train_checks(cfg, got)
    result_checks.append(checks.Check(
        "last_loss_not_finite", 0.0 if np.isfinite(last_loss) else 1.0, 0.0))

    images = steps * rows
    return {
        "end_to_end": {"train_img_per_s": images / window_s / chips,
                       "setup_s": setup_s},
        "attempted": steps, "failed": 0,
        "checks": result_checks,
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles_in_window,
        "train": {"images": images, "window_s": window_s, "chips": chips,
                  "flops_per_image": ref.train_flops_per_image(cfg),
                  "peak_flops": peaks(run.device["kind"])["bf16_flops_per_s"]
                  if run.device["platform"] == "tpu" else None},
        "compile_s": compile_s,
        "notes": {"steps": steps, "window_s": window_s,
                  "step_gap_ms_max": step_gap * 1e3,
                  "losses": kept["losses"], "ref_losses": got["ref_losses"],
                  "loss_gaps": got["loss_gaps"],
                  "grad_leaf": got["grad_leaf"],
                  "update_leaf": got["update_leaf"],
                  "skipped_leaves": got["skipped_leaves"],
                  "last_loss": last_loss},
    }


def train_checks(cfg, got: dict) -> list:
    """Each compared number beside its limit."""
    limits = cfg["limits"]["train"]
    return [checks.Check(k, got[k], limits[k]) for k in limits]


def calibrate(cell, seeds, *, control: bool, faults: bool, **_):
    """Readings for the limits (``benchmark/calibrate.py``): per seed the
    program against the float32 reference; with ``control`` the
    reference in the configuration's control numerics, with ``faults``
    the reference with half of the batch left out, each put in the
    program's place and judged by the same checks, which it has to
    fail."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import cells
    from deepvision_tpu.core import create_mesh, shard_batch

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference_for(cfg, cell.config_name)
    mesh = create_mesh(cell.chips, 1)
    rows = cfg["batch_per_chip"] * cell.chips
    n = traffic["checked_steps"]
    key = jax.random.key(0)
    compiled = fresh = None
    for seed in seeds:
        weights, batch = seeded(cfg, ref, seed, rows)
        batch = shard_batch(mesh, batch)
        p0 = jax.tree.map(np.asarray, weights)
        if compiled is None:    # one program for all seeds
            step, fresh = build_program(cfg, mesh, weights)
            compiled = step.lower(fresh, batch, key).compile()
            init = jax.jit(fresh.tx.init)
        state = fresh.replace(step=jnp.zeros((), jnp.int32),
                              params=weights, opt_state=init(weights),
                              batch_stats=jax.tree.map(jnp.copy,
                                                       fresh.batch_stats))
        state, kept = checked_steps(compiled, state, batch, key, n)
        del state, weights
        truth = reference_steps(cfg, ref, plain.HIGHEST, batch, p0, n)
        readings = {"program": kept}
        if control:
            readings[f"control:{cfg['control']}"] = reference_steps(
                cfg, ref, plain.NUMERICS[cfg["control"]], batch, p0, n)
        if faults:
            readings["fault:half_batch"] = reference_steps(
                cfg, ref, plain.HIGHEST, batch, p0, n, rows=(0, rows // 2))
        for reading, steps in readings.items():
            got = compare(steps, truth, p0)
            yield {"seed": seed, "reading": reading,
                   **{k: v for k, v in got.items() if k != "ref_losses"},
                   "correct": checks.verdict(train_checks(cfg, got))}
