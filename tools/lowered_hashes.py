"""sha256 of the lowered StableHLO of the programs the benchmark's
other cells run, on the CPU:

- the ``resnet50`` train step at batch 8, built as
  ``benchmark/drivers/train_resident.py`` builds it;
- ``yolov3``'s served forward, the executable ``jit_served_forward`` of
  ``serve/pipeline.py``, at 64 px and bucket 4;
- the ``keye_vl2_ep8`` train step at the cell's own size (2 samples of
  8,192 positions; lowered from shapes, nothing is allocated), built as
  ``benchmark/drivers/train_resident_seq.py`` builds it. On the CPU the
  attention and the indexer lower to their XLA forms, not to the chip's
  kernels: the hash covers everything else of the step, the expert
  layer and its router among it.

A PR that must not move those cells runs this from the root of the
parent's archive and from its own tree: equal hashes mean neither cell
runs one changed line of the traced program (PERF.md, PR 28 and PR 30).
Also says whether a Pallas module was imported on the way.

    JAX_PLATFORMS=cpu python tools/lowered_hashes.py
"""

import hashlib
import json
import os
import sys
import warnings


def main() -> int:
    sys.path.insert(0, os.getcwd())
    import jax

    from benchmark.drivers import train_resident
    from benchmark.reference import resnet50 as reference
    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.core.mesh import data_sharding, replicated_sharding
    from deepvision_tpu.serve.models import load_served

    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()
    with open("benchmark/configs/resnet50.json") as f:
        cfg = json.load(f)
    mesh = create_mesh(1, 1)
    weights, batch = train_resident.seeded(cfg, reference, 7, 8)
    step, state = train_resident.build_program(cfg, mesh, weights)
    text = step.lower(state, shard_batch(mesh, batch),
                      jax.random.key(0)).as_text()
    print("resnet50_train_step_b8", digest(text))

    served = load_served("yolov3", None, input_size=64)

    def served_forward(variables, x):
        return served.forward(variables, x)

    fn = jax.jit(served_forward, in_shardings=(
        replicated_sharding(mesh),
        data_sharding(mesh, 1 + len(served.input_shape))),
        donate_argnums=(1,))
    x = jax.ShapeDtypeStruct((4, *served.input_shape), served.input_dtype)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        text = fn.lower(served.variables, x).as_text()
    print("yolov3_served_forward_b4_64px", digest(text))
    print("pallas_modules", sorted(m for m in sys.modules if "pallas" in m))
    print("keye_vl2_ep8_train_step_b2", digest(_keye_step_text()))
    return 0


def _keye_step_text() -> str:
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_resident_seq
    from benchmark.reference import keye_vl2 as reference
    from deepvision_tpu.core import create_mesh

    with open("benchmark/configs/keye_vl2_30b_a3b.json") as f:
        cfg = json.load(f)
    rows = cfg["batch_per_chip"]
    weights, batch = jax.eval_shape(
        lambda k: (reference.make_weights(cfg, k),
                   reference.make_batch(cfg, k, rows)), jax.random.key(0))
    step, make_state = train_resident_seq.build_program(
        cfg, create_mesh(1, 1), weights)
    state = jax.eval_shape(make_state, weights)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    return step.lower(state, batch, key).as_text()


if __name__ == "__main__":
    sys.exit(main())
