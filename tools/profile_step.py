"""Capture + summarize a TPU profiler trace of one model's train step.

Usage: python tools/profile_step.py [model] [batch_per_chip] [steps]

Captures a ``jax.profiler`` trace of the compiled train step running
device-resident synthetic batches, then parses the XPlane protobuf
directly (no TensorBoard needed) and prints the top ops by self time on
the TPU op plane — the per-op breakdown VERDICT r2 asked for. Also prints
the step's XLA cost analysis (flops, HBM bytes) and the arithmetic
intensity so compute- vs memory-bound is attributable at a glance.
"""

from __future__ import annotations

import glob
import os
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

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


def parse_xplane(trace_dir: str, top: int = 25):
    """Aggregate self-times per op on the TPU xplanes."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        from tensorflow.core.profiler.protobuf import xplane_pb2

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        print("no xplane.pb found under", trace_dir)
        return
    xspace = xplane_pb2.XSpace()
    xspace.ParseFromString(Path(sorted(paths)[-1]).read_bytes())
    for plane in xspace.planes:
        if "TPU" not in plane.name and "/device:" not in plane.name:
            continue
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        by_line = defaultdict(lambda: (defaultdict(float), defaultdict(int)))
        for line in plane.lines:
            totals, counts = by_line[line.name]
            for ev in line.events:
                name = ev_meta.get(ev.metadata_id, "?")
                totals[name] += ev.duration_ps / 1e6  # -> us
                counts[name] += 1
        for lname, (totals, counts) in by_line.items():
            if not totals:
                continue
            print(f"\n== plane: {plane.name} line: {lname!r} "
                  f"(total {sum(totals.values())/1e3:.2f} ms) ==")
            for name, us in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
                print(f"  {us/1e3:9.3f} ms  x{counts[name]:<5d}  {name[:140]}")


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
    parse_xplane(trace_dir)


if __name__ == "__main__":
    main()
