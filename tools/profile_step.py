"""Capture + summarize a TPU profiler trace of one model's train step.

Usage: python tools/profile_step.py [model] [batch_per_chip] [steps]

Captures a ``jax.profiler`` trace of the compiled train step running
device-resident synthetic batches, reduces it with the benchmark's own
trace reduction (``benchmark/reduce/xplane.py``; no TensorBoard, no
TensorFlow) and prints the top operations by device time per step — the
per-op breakdown VERDICT r2 asked for. Also prints the step's XLA cost
analysis (flops, HBM bytes) and the arithmetic intensity so compute- vs
memory-bound is attributable at a glance.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def build(model_name: str, batch: int):
    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.state import create_train_state
    from deepvision_tpu.train.steps import classification_train_step

    n = len(jax.devices())
    mesh = create_mesh(n, 1)
    from deepvision_tpu.train.configs import get_config

    # profile the SHIPPED config (e.g. the resnet s2d stem) so traces
    # match what bench.py measures; BENCH_S2D=0 reverts like bench.py
    kwargs = dict(get_config(model_name).get("model_kwargs", {}))
    if os.environ.get("BENCH_S2D") == "0":
        kwargs.pop("s2d_stem", None)
    model = get_model(model_name, dtype=jnp.bfloat16, **kwargs)
    rng = np.random.default_rng(0)
    b = {
        "image": rng.normal(size=(batch * n, 224, 224, 3)).astype(np.float32),
        "label": rng.integers(0, 1000, size=(batch * n,)).astype(np.int32),
    }
    tx = optax.sgd(0.1, momentum=0.9)
    state = create_train_state(model, tx, b["image"][:1])
    step = compile_train_step(classification_train_step, mesh)
    db = shard_batch(mesh, b)
    compiled = step.lower(state, db, jax.random.key(0)).compile()
    return state, db, compiled


def print_device_ops(trace_dir: str, steps: int, top: int = 25) -> None:
    """The trace through the benchmark's own reduction
    (``benchmark/reduce/xplane.py``: busy time as the union of the
    ``XLA Ops`` intervals, nothing after ``stop_trace``; no TensorFlow):
    one JSON line of busy/window/matrix-unit seconds, then the ``top``
    operations by summed device time, per step."""
    from benchmark.reduce import xplane

    path = xplane.newest_xplane(trace_dir)
    reduced = xplane.reduce(path, top=top) if path else {"chips": 0}
    if not reduced["chips"]:
        print("no device operation in a trace under", trace_dir)
        return
    print(json.dumps({k: reduced[k] for k in
                      ("chips", "busy_s", "window_s", "conv_s")}))
    for name, seconds in reduced["device_ops"]:
        print(f"  {seconds * 1e3 / steps:9.3f} ms/step  {name}")


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 10
    trace_dir = f"/tmp/profile_{model}_b{batch}"

    state, db, compiled = build(model, batch)
    from tools.hbm_budget import device_peaks

    peak, _ = device_peaks(jax.devices()[0].device_kind)
    ca = compiled.cost_analysis()
    flops = ca["flops"]
    hbm = ca["bytes accessed"]
    print(json.dumps({
        "model": model, "batch_per_chip": batch,
        "flops_per_step": flops, "hbm_bytes_per_step": hbm,
        "arith_intensity": round(flops / hbm, 1) if hbm else None,
    }))

    key = jax.random.key(0)
    for _ in range(3):
        key, sub = jax.random.split(key)
        state, _ = compiled(state, db, sub)
    jax.block_until_ready(state)

    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        key, sub = jax.random.split(key)
        state, _ = compiled(state, db, sub)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()

    n = len(jax.devices())
    print(json.dumps({
        "sec_per_step": dt / steps,
        "img_per_sec_per_chip": batch * n * steps / dt / n,
        "mfu": round(flops * steps / dt / peak, 4),
    }))
    print_device_ops(trace_dir, steps)


if __name__ == "__main__":
    main()
