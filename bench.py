"""Benchmark: ResNet-50 ImageNet training throughput + MFU on the chip.

Measures the full compiled train step (forward + backward + SGD update,
bf16 compute / f32 params, donated state) on the locally attached TPU
chip(s), twice:

1. device-resident synthetic batches (pure step throughput — the
   headline ``value``), with MFU computed from the compiled executable's
   XLA cost analysis against the chip's peak bf16 FLOP/s;
2. fed by the real tf.data ImageNet pipeline over synthetic TFRecords
   (JPEG decode + ResNet preprocessing on the host), proving the input
   pipeline sustains the device rate (SURVEY §7 hard part #1).

Prints ONE JSON line — and fails, printing none, when JAX finds no TPU:
this mode measures the chip and has no CPU fallback. Baseline for
``vs_baseline``: the reference trained ResNet-50 on P100-class GPUs (ref: ResNet/pytorch/README.md:67,
AlexNet/pytorch/README.md:24); it publishes no throughput number
(BASELINE.json "published" is empty), so we use the widely reported ~220
images/sec for fp32 ResNet-50 training on one P100 as the per-chip
baseline.

Set ``BENCH_PROFILE=1`` to capture a ``jax.profiler`` trace of the
measured steps into ``/tmp/deepvision_bench_trace`` (view in
TensorBoard's profile plugin).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from concurrent.futures import Future
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax

BASELINE_IMG_PER_SEC_PER_CHIP = 220.0  # fp32 ResNet-50 on the ref's P100
BATCH_PER_CHIP = 256
WARMUP, MEASURE = 3, 20
PIPELINE_IMAGES = 4096  # synthetic TFRecord set size for the fed bench
# median-of-5 fed figure: 5 interleaved reps make the median robust to
# one outlier rep per path, and the spread is reported against the
# median.
FED_WARMUP, FED_STEPS, FED_REPEATS = 3, 12, 5
# warmup/pacing: each rep builds a FRESH tf.data pipeline, so its first
# next() pays the full shuffle-buffer fill + tf autotune ramp. Discard
# FED_DISCARD host batches before the measured region so every rep
# starts from a filled, paced pipeline.
FED_DISCARD = 4
# f32 reference-parity comparator reps: enough to measure the wire
# ratio honestly, few enough not to double the fed-bench wall time
F32_REPEATS = 2
# pipeline_fed's host decode stage runs over this many spawned loader
# processes (data/loader.py) — the shipped answer to a decode-bound
# host; 1 disables. The 1-worker decode ceiling is still reported
# alongside so the host win stays attributable.
LOADER_WORKERS = int(os.environ.get("BENCH_LOADER_WORKERS",
                                    str(min(2, os.cpu_count() or 1))))
# host-ceiling sample size (batches per drain): big enough to ride out
# per-second throughput drift, small enough not to dominate wall time
HOST_CEIL_BATCHES = int(os.environ.get("BENCH_HOST_CEIL_BATCHES", "16"))



def _flops_per_step(compiled) -> float:
    """XLA's own FLOP count for one compiled step (per-device: cost
    analysis runs on the post-SPMD-partitioned executable)."""
    return float(compiled.cost_analysis()["flops"])


def _write_synthetic_tfrecords(root: Path, n: int) -> None:
    """JPEG-encoded 256² noise-with-structure records in the ImageNet
    schema (image/encoded + image/class/label), 8 shards."""
    import tensorflow as tf

    tf.config.set_visible_devices([], "GPU")
    from deepvision_tpu.data.tfrecord import encode_example, write_records

    rng = np.random.default_rng(0)
    shards = 8
    per = n // shards
    for s in range(shards):
        records = []
        for _ in range(per):
            img = rng.integers(0, 255, (256, 256, 3), np.uint8)
            data = tf.io.encode_jpeg(tf.constant(img)).numpy()
            records.append(encode_example({
                "image/encoded": [data],
                "image/class/label": [int(rng.integers(1, 1001))],
            }))
        write_records(root / f"train-{s:05d}-of-{shards:05d}", records)


def main() -> None:
    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.models import get_model
    from deepvision_tpu.startup import init_runtime
    from deepvision_tpu.train.state import create_train_state
    from deepvision_tpu.train.steps import classification_train_step
    from tools.hbm_budget import device_peaks, strip_layouts
    from tools.jaxlint.shardcheck import parse_collective_bytes

    # the headline cell measures the chip: no TPU, no number
    device = init_runtime(require_tpu=True)
    n_chips, kind = device["count"], device["kind"]
    peak, _ = device_peaks(kind)
    mesh = create_mesh(n_chips, 1)
    batch_size = BATCH_PER_CHIP * n_chips

    # The space-to-depth stem is the shipped resnet50 config (identical
    # parameter pytree — see models/resnet._Conv7S2D; measured +2.6%
    # img/s, MFU 0.2905→0.2999 on v5e). BENCH_S2D=0 measures the plain
    # 7x7/2 stem; BENCH_NO_FED=1 skips the pipeline-fed benches for
    # quick device-only A/Bs.
    s2d = os.environ.get("BENCH_S2D", "1") != "0"
    # BENCH_REMAT: "" (XLA default), "block", or "conv" — see
    # models/resnet.ResNet.remat
    remat = os.environ.get("BENCH_REMAT", "") or None
    model = get_model("resnet50", dtype=jnp.bfloat16, s2d_stem=s2d,
                      remat=remat)
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.normal(size=(batch_size, 224, 224, 3)).astype(np.float32),
        "label": rng.integers(0, 1000, size=(batch_size,)).astype(np.int32),
    }
    tx = optax.sgd(optax.warmup_cosine_decay_schedule(0, 0.1, 500, 10_000),
                   momentum=0.9, nesterov=False)
    state = create_train_state(model, tx, batch["image"][:1])
    step = compile_train_step(classification_train_step, mesh)

    device_batch = shard_batch(mesh, batch)
    key = jax.random.key(0)
    # Lower+compile once (AOT); the measured loops run the SAME compiled
    # executable (jit's cache is separate — calling `step` here would
    # compile the identical program a second time), and its cost analysis
    # feeds the MFU figure.
    compiled = step.lower(state, device_batch, key).compile()
    flops_step = _flops_per_step(compiled)

    # Collective-traffic ledger of the SAME executable the measured
    # loop runs (shardcheck's HLO parser): per-participant interconnect
    # bytes/step, attributed per opcode. Zero on a single-chip mesh by
    # construction; on a real slice this is the number the
    # [[shardcheck.comms]] ratchets track over time.
    colls = parse_collective_bytes(strip_layouts(compiled.as_text()))
    comms = {
        "coll_gb_per_step": round(
            sum(r["bytes"] for r in colls.values()) / 1e9, 3),
        "collectives": {op: r["count"]
                        for op, r in sorted(colls.items())},
    }

    for _ in range(WARMUP):
        key, sub = jax.random.split(key)
        state, metrics = compiled(state, device_batch, sub)
    jax.block_until_ready(state)

    profile_dir = None
    if os.environ.get("BENCH_PROFILE"):
        profile_dir = "/tmp/deepvision_bench_trace"
        jax.profiler.start_trace(profile_dir)
    t0 = time.perf_counter()
    for _ in range(MEASURE):
        key, sub = jax.random.split(key)
        state, metrics = compiled(state, device_batch, sub)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    if profile_dir:
        jax.profiler.stop_trace()

    img_per_sec = MEASURE * batch_size / dt
    per_chip = img_per_sec / n_chips
    # flops_step is already per-device (see _flops_per_step)
    mfu = flops_step * MEASURE / dt / peak

    # ---- pipeline-fed benches -------------------------------------------
    # Stabilized per VERDICT r2: fixed warm-up + step count, median of
    # FED_REPEATS runs (+ spread), the pure-host decode ceiling printed
    # alongside so the bottleneck is attributable at a glance, and the
    # pre-decoded raw-crop fast path (data/builders/raw_crops.py) that
    # bypasses the JPEG bound entirely.
    fed = {}
    if not os.environ.get("BENCH_NO_FED"):
        fed = _pipeline_benches(state, step, mesh, key, batch_size,
                                n_chips)

    # per-family flagship matrix (VERDICT r4 #5): a family that fails
    # fails the run — a missing key is not a measurement
    zoo = {}
    if not os.environ.get("BENCH_NO_ZOO"):
        zoo = _zoo_bench(mesh, n_chips, kind)

    out = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 2),
        "mfu": round(mfu, 4),
        "hbm_gb_per_step": round(
            float(compiled.cost_analysis()["bytes accessed"]) / 1e9, 1),
        "device": device,
        "device_kind": kind,
        "s2d_stem": s2d,
        "comms": comms,
        **({"remat": remat} if remat else {}),
        **({"zoo": zoo} if zoo else {}),
        **fed,
        "obs": _obs_snapshot(),
    }
    print(json.dumps(out))


def _obs_snapshot() -> dict:
    """The merged obs registry view embedded in the bench record: the
    input_* feed histograms the fed reps just exercised (per-batch
    stage quantiles, not only the means in *_input_wait) + mem_* device
    gauges sampled here (empty on CPU)."""
    from deepvision_tpu.obs.metrics import default_registry
    from deepvision_tpu.obs.profiler import sample_memory_gauges

    sample_memory_gauges()
    return default_registry().snapshot()


# ---- per-family zoo sweep (VERDICT r4 #5) -------------------------------
# One flagship per family: img/s/chip + MFU + roofline attribution.
# Kept small (few measured steps) so a bench run stays bounded.


def _zoo_case(name):
    """-> (model, batch dict, step_fn, state_factory) per family."""
    import jax.numpy as jnp

    from deepvision_tpu.models import get_model
    from deepvision_tpu.train import steps as S
    from deepvision_tpu.train.state import create_train_state

    rng = np.random.default_rng(0)

    def cls(model_name, bs, size, dtype=jnp.bfloat16, **kw):
        model = get_model(model_name, dtype=dtype, **kw)
        batch = {
            "image": rng.normal(size=(bs, size, size, 3)).astype(np.float32),
            "label": rng.integers(0, 1000, size=(bs,)).astype(np.int32),
        }
        tx = optax.sgd(0.1, momentum=0.9)
        state = create_train_state(model, tx, batch["image"][:1])
        return state, batch, S.classification_train_step

    def det_batch(bs, size, max_boxes=20):
        # the {'image','boxes','label'} contract shared by the YOLO and
        # CenterNet steps: -1 labels are padding, first two are real
        batch = {
            "image": rng.normal(size=(bs, size, size, 3)).astype(np.float32),
            "boxes": np.tile(np.array([0.5, 0.5, 0.3, 0.3], np.float32),
                             (bs, max_boxes, 1)),
            "label": np.full((bs, max_boxes), -1, np.int32),
        }
        batch["label"][:, :2] = 1
        return batch

    if name == "mobilenet1":
        return cls("mobilenet1", 256, 224)
    if name == "shufflenet1":
        return cls("shufflenet1", 256, 224)
    if name == "inception3":
        return cls("inception3", 128, 299)
    if name == "yolov3":
        model = get_model("yolov3", num_classes=20, dtype=jnp.bfloat16)
        batch = det_batch(16, 416)
        tx = optax.sgd(1e-3, momentum=0.9)
        state = create_train_state(model, tx, batch["image"][:1])
        return state, batch, S.yolo_train_step
    if name == "centernet":
        # trained gate config (train/configs.py "centernet"): bf16,
        # batch 16 @ 256², detection batch format shared with YOLO
        model = get_model("centernet", num_classes=80, dtype=jnp.bfloat16)
        batch = det_batch(16, 256)
        tx = optax.adam(1e-3)
        state = create_train_state(model, tx, batch["image"][:1])
        return state, batch, S.centernet_train_step
    if name == "hourglass104":
        import jax.numpy as jnp

        from deepvision_tpu.core.precision import get_policy

        # the shipped policy since ISSUE 15: bf16_scaled (f32 residual
        # carrier + MixedBatchNorm + loss scaling) with stack remat —
        # the r4 f32 pin is superseded by the structural fix
        policy = get_policy("bf16_scaled")
        model = get_model("hourglass104", num_heatmaps=16,
                          dtype=policy.compute_dtype, remat="stack")
        bs = 8
        batch = {
            "image": rng.normal(size=(bs, 256, 256, 3)).astype(np.float32),
            "kx": rng.uniform(4, 60, size=(bs, 16)).astype(np.float32),
            "ky": rng.uniform(4, 60, size=(bs, 16)).astype(np.float32),
            "v": np.ones((bs, 16), np.float32),
        }
        tx = optax.rmsprop(2.5e-4)
        state = create_train_state(model, tx, batch["image"][:1],
                                   policy=policy)
        return state, batch, S.pose_train_step
    if name == "dcgan":
        # the zoo's one non-classification-step family: the full
        # simultaneous G+D update (two Adams, one shared forward) is the
        # compiled program, exactly what fit_gan runs at the trained
        # config (batch 256, 28x28x1, train/configs.py "dcgan")
        from deepvision_tpu.train.gan import (
            create_dcgan_state,
            dcgan_train_step,
        )

        bs = 256
        batch = {
            "image": rng.normal(size=(bs, 28, 28, 1)).astype(np.float32)
        }
        state = create_dcgan_state(
            get_model("dcgan_generator", dtype=jnp.bfloat16),
            get_model("dcgan_discriminator", dtype=jnp.bfloat16),
        )
        return state, batch, dcgan_train_step
    if name == "cyclegan":
        # trained config (train/configs.py "cyclegan"): batch 4 @ 256²,
        # full two-phase step (both G updates + both pooled D updates)
        from deepvision_tpu.train.gan import (
            create_cyclegan_state,
            cyclegan_train_step,
        )

        bs = max(4, jax.device_count())  # 4 per trained config; divisible
        batch = {                        # by the data axis on multi-chip
            "a": rng.normal(size=(bs, 256, 256, 3)).astype(np.float32),
            "b": rng.normal(size=(bs, 256, 256, 3)).astype(np.float32),
        }
        state = create_cyclegan_state(
            get_model("cyclegan_generator", dtype=jnp.bfloat16),
            get_model("cyclegan_discriminator", dtype=jnp.bfloat16),
        )
        return state, batch, cyclegan_train_step
    raise KeyError(name)


def _zoo_bench(mesh, n_chips, kind) -> dict:
    from deepvision_tpu.core import shard_batch
    from deepvision_tpu.core.step import compile_train_step
    from tools.hbm_budget import device_peaks

    peak_bf16, bw_gbps = device_peaks(kind)
    bw = bw_gbps * 1e9
    out = {}
    for fam, f32 in (("mobilenet1", False), ("inception3", False),
                     ("yolov3", False), ("hourglass104", True),
                     ("dcgan", False), ("shufflenet1", False),
                     ("centernet", False), ("cyclegan", False)):
        state, batch, step_fn = _zoo_case(fam)
        step = compile_train_step(step_fn, mesh)
        db = shard_batch(mesh, batch)
        key = jax.random.key(0)
        compiled = step.lower(state, db, key).compile()
        ca = compiled.cost_analysis()
        flops, bytes_ = float(ca["flops"]), float(ca["bytes accessed"])
        for _ in range(2):
            key, sub = jax.random.split(key)
            state, _m = compiled(state, db, sub)
        jax.block_until_ready(state)
        n = 8
        t0 = time.perf_counter()
        for _ in range(n):
            key, sub = jax.random.split(key)
            state, _m = compiled(state, db, sub)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        # images consumed per step: the "image" tensor, or — for
        # image-only batches like cyclegan's {'a','b'} — every
        # domain's reals, matching the other families' convention
        bs = (len(batch["image"]) if "image" in batch
              else sum(len(v) for v in batch.values()))
        step_t = dt / n
        # f32 MACs run at half the bf16 MXU rate
        peak = peak_bf16 / (2.0 if f32 else 1.0)
        flops_t, hbm_t = flops / peak, bytes_ / bw
        bound = ("MXU" if flops_t > 0.8 * step_t else
                 "HBM" if hbm_t > 0.8 * step_t else
                 "mixed/dispatch")
        out[fam] = {
            "images_per_sec_per_chip": round(bs * n / dt / n_chips, 1),
            "mfu": round(flops / peak / step_t, 4),
            "hbm_gb_per_step": round(bytes_ / 1e9, 2),
            "bound": bound,
        }
        del state, compiled
    return out


def _median_spread(vals):
    med = float(np.median(vals))
    spread = (max(vals) - min(vals)) / med * 100 if med else 0.0
    return round(med, 1), round(spread, 1)


def _tel_median(summaries):
    """Median of each per-stage telemetry field across fed reps (+ the
    wire accounting — bytes/image is batch geometry, identical across
    reps; the dtype is a string, carried from the first rep)."""
    keys = ("host_wait_ms", "shard_ms", "h2d_wait_ms", "step_ms",
            "input_wait_frac", "h2d_bytes_per_image")
    out = {k: round(float(np.median([s[k] for s in summaries])), 3)
           for k in keys}
    out["wire_dtype"] = summaries[0]["wire_dtype"]
    return out


def _run_fed_once(state, step, mesh, key, batch_size, n_chips,
                  make_batches, seed):
    """One fed-throughput repetition for one host-batch factory
    (``make_batches(seed) -> iterator of {'image','label'} dicts``).

    Returns ``(rate, state, telemetry)`` — the step donates its input
    state, so the caller MUST thread the returned state into any further
    step calls (reusing the donated original raises InvalidArgument);
    ``telemetry`` is the steady-state ``FeedTelemetry.summary()`` of the
    measured steps (host-wait / H2D-wait / step-compute split + the wire
    accounting: measured ``h2d_bytes_per_image`` and ``wire_dtype``)."""
    from deepvision_tpu.data.prefetch import DevicePrefetcher, FeedTelemetry

    it = make_batches(seed)
    # pacing: exclude the fresh pipeline's shuffle-buffer fill / autotune
    # ramp (and any loader-worker spawn) from the measurement
    for _ in range(FED_DISCARD):
        next(it)

    def host_batches():
        for _ in range(FED_WARMUP + FED_STEPS):
            yield next(it)

    # async feed (data/prefetch.py): producer-thread sharding keeps the
    # H2D transfers in flight ahead of the running step — the measured
    # configuration IS the training configuration
    tel = FeedTelemetry()
    feed = DevicePrefetcher(host_batches(), mesh, telemetry=tel)
    t0, base = None, None
    try:
        for i, dbatch in enumerate(feed):
            if i == FED_WARMUP:
                # one deliberate sync at the warmup boundary, so the
                # measured window starts on an idle device
                jax.block_until_ready(state)  # jaxlint: disable=JX109
                # steady-state telemetry scope: snapshot-delta (not
                # reset — a live producer's += races a reset write),
                # and restart the step clock so the warmup drain above
                # is not charged to the first measured step interval
                feed.restart_clock()
                base = tel.snapshot()
                t0 = time.perf_counter()
            key, sub = jax.random.split(key)
            state, _ = step(state, dbatch, sub)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
    finally:
        feed.close()
        close = getattr(it, "close", None)
        if close:  # stop a loader-worker pool with the rep
            close()
    # batches=FED_STEPS: exactly FED_STEPS step/H2D intervals land after
    # the snapshot (the boundary batch's fetch preceded it), so pin the
    # divisor to the true measured-step count
    return (FED_STEPS * batch_size / dt / n_chips, state,
            tel.summary(since=base, batches=FED_STEPS))


def _host_only_rate(it, n_batches, batch_size):
    """Pure host-pipeline drain — the host ceiling, no device in the
    loop. Discards the same FED_DISCARD ramp batches as the fed reps so
    the ceiling and the fed rates compare steady state to steady state
    (and any loader-worker spawn cost stays out of the measurement)."""
    try:
        for _ in range(FED_DISCARD):  # buffer fill / autotune ramp
            next(it)
        t0 = time.perf_counter()
        for _ in range(n_batches):
            next(it)
        return n_batches * batch_size / (time.perf_counter() - t0)
    finally:
        close = getattr(it, "close", None)
        if close:
            close()


def _pipeline_benches(state, step, mesh, key, batch_size, n_chips) -> dict:
    """Fed-throughput matrix (ISSUE 7). Four variants isolate where the
    input wall moved:

    - ``pipeline_fed`` — the SHIPPED training configuration: host decode
      + resize + uint8 crop over ``LOADER_WORKERS`` spawned processes
      (``data/loader.py``), flip + normalize fused into the compiled
      step (``data/device_aug.py``). The headline fed number.
    - ``uint8_fed`` — uint8 wire but FULL host augmentation on one
      process (r04's pipeline_fed configuration): pipeline_fed minus
      the host win, so pipeline_fed − uint8_fed attributes the
      device-aug/loader offload and uint8_fed − f32_fed the wire win.
    - ``f32_fed`` — full host f32 reference-parity path (4-byte pixels
      on the wire; ``F32_REPEATS`` reps — it exists to pin the measured
      ``h2d_bytes_per_image`` ratio, not to win).
    - ``raw_record_fed`` — pre-decoded raw-frame shards (no JPEG bound).

    Every variant reports measured ``h2d_bytes_per_image`` + wire dtype
    from the prefetcher's wire accounting, and
    ``h2d_bytes_reduction_vs_f32`` gates the 4x byte win with measured
    numbers (uint8 224² + int32 label vs f32: 3.9998x)."""
    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.data.device_aug import DeviceAugment, augment_step
    from deepvision_tpu.data.imagenet import (
        _TrainShardFactory,
        make_dataset,
        make_raw_dataset,
    )
    from deepvision_tpu.data.loader import mp_batches
    from deepvision_tpu.train.steps import classification_train_step

    root = Path("/tmp/deepvision_bench_tfrecords")
    done = root / "COMPLETE"
    if not done.exists():  # all-or-nothing cache marker
        root.mkdir(parents=True, exist_ok=True)
        _write_synthetic_tfrecords(root, PIPELINE_IMAGES)
        done.touch()
    # v2: full-frame raw records (r4 builder rework); old cache is stale
    raw_done = root / "RAW_COMPLETE_v2"
    if not raw_done.exists():
        from deepvision_tpu.data.builders.raw_crops import build_raw_crops

        # num_workers=1: forking an mp.Pool after the TPU client and TF
        # runtime initialized in-process is a known deadlock mode; the
        # bench set is small and the result is cached anyway
        build_raw_crops(root, root, split="train", stored=256,
                        num_shards=8, num_workers=1)
        raw_done.touch()

    def _tf_batches(make_ds):
        def factory(seed):
            it = make_ds(seed).as_numpy_iterator()
            return ({"image": img, "label": lbl} for img, lbl in it)

        return factory

    uint8_batches = _tf_batches(lambda seed: make_dataset(
        str(root / "train-*"), batch_size, 224,
        is_training=True, as_uint8=True, seed=seed))
    f32_batches = _tf_batches(lambda seed: make_dataset(
        str(root / "train-*"), batch_size, 224,
        is_training=True, as_uint8=False, seed=seed))
    raw_batches = _tf_batches(lambda seed: make_raw_dataset(
        str(root / "raw-train-*"), batch_size, 224,
        is_training=True, seed=seed))
    split_host = _tf_batches(lambda seed: make_dataset(
        str(root / "train-*"), batch_size, 224,
        is_training=True, seed=seed, host_stage="crop"))

    def split_factory(seed, bs, threads=None):
        # ONE definition of the split-pipeline host-stage config: the
        # fed measurement and the controlled-width mp probe must read
        # the SAME pipeline or the speedup attributes a config skew
        return _TrainShardFactory(
            kind="jpeg", pattern=str(root / "train-*"),
            batch_size=bs, size=224, augment="tf", seed=seed,
            base_shards=1, base_index=0, host_stage="crop",
            as_uint8=True, private_threads=threads)

    def split_batches(seed):
        # the shipped config: decode stage over LOADER_WORKERS spawned
        # processes; 1 keeps it in-process (same host stage either way)
        if LOADER_WORKERS > 1:
            return mp_batches(split_factory(seed, batch_size),
                              LOADER_WORKERS)
        return split_host(seed)

    # the pipeline_fed step carries the DEVICE STAGE fused in: flip (tf
    # lineage has no jitter) + the uint8 normalize already in the step
    aug_step = compile_train_step(
        augment_step(classification_train_step,
                     DeviceAugment("classification", flip=True)),
        mesh)

    # INTERLEAVED rounds (P,U,R[,F] per rep): cycling the variants
    # inside each rep keeps the comparison difference-in-rounds honest
    # if throughput drifts over a bench run, and per-rep rates are
    # reported raw so drift is visible instead of folded into a median.
    variants = {
        "pipeline_fed": (aug_step, split_batches, FED_REPEATS),
        "uint8_fed": (step, uint8_batches, FED_REPEATS),
        "raw_record_fed": (step, raw_batches, FED_REPEATS),
        "f32_fed": (step, f32_batches, F32_REPEATS),
    }
    rates = {v: [] for v in variants}
    tels = {v: [] for v in variants}
    for rep in range(FED_REPEATS):
        for name, (vstep, factory, reps) in variants.items():
            if rep >= reps:
                continue
            r, state, t = _run_fed_once(state, vstep, mesh, key,
                                        batch_size, n_chips, factory,
                                        seed=rep)
            rates[name].append(r)
            tels[name].append(t)

    out = {}
    for name in variants:
        med, spread = _median_spread(rates[name])
        out[f"{name}_images_per_sec_per_chip"] = med
        out[f"{name}_spread_pct"] = spread
        out[f"{name}_rates"] = [round(r, 1) for r in rates[name]]
        # per-stage input-wait telemetry (median across reps): host_wait
        # = producer blocked on the host pipeline, h2d_wait = consumer
        # blocked on a ready device batch, step = consumer between-batch
        # time; + measured wire bytes/dtype. The frac says at a glance
        # whether a fed-vs-synthetic gap is input-bound or
        # scheduling-bound.
        out[f"{name}_input_wait"] = _tel_median(tels[name])
        out[f"{name}_h2d_bytes_per_image"] = \
            tels[name][0]["h2d_bytes_per_image"]
        out[f"{name}_wire_dtype"] = tels[name][0]["wire_dtype"]
    # the ISSUE 7 acceptance ratio, from MEASURED wire bytes
    out["h2d_bytes_reduction_vs_f32"] = round(
        out["f32_fed_h2d_bytes_per_image"]
        / max(1.0, out["pipeline_fed_h2d_bytes_per_image"]), 2)
    out["loader_workers"] = LOADER_WORKERS

    # host ceilings: the decode wall and how far the spawned loaders
    # push it
    host_jpeg = _host_only_rate(uint8_batches(99), HOST_CEIL_BATCHES,
                                batch_size)
    host_raw = _host_only_rate(raw_batches(99), HOST_CEIL_BATCHES,
                               batch_size)
    out["host_decode_ceiling_images_per_sec"] = round(host_jpeg, 1)
    out["host_raw_ceiling_images_per_sec"] = round(host_raw, 1)
    if LOADER_WORKERS > 1:
        # The mp speedup is measured at CONTROLLED width: both sides of
        # the same host stage (split pipeline, host_stage="crop") pin
        # each tf.data pipeline to a 1-thread private pool, so the
        # ratio isolates what data/loader.py adds — N decode PROCESSES
        # — from tf.data's own AUTOTUNE thread fan-out. On a host whose
        # cores AUTOTUNE already saturates (the 2-core dev box), the
        # free-running A/B measures oversubscription, not the loader;
        # the SHIPPED config stays free-running and its ceiling is
        # reported alongside (host_decode_mp_ceiling). Interleaved
        # rounds + median: this class of host drifts on the seconds
        # scale, and a sequential A-then-B read folds the drift into
        # the ratio. Drain batches are >=64 images regardless of the
        # (possibly CPU-shrunk) train batch: at tiny batches the
        # per-batch Python/IPC hop dominates the per-image decode and
        # the ratio measures the hop, not the loader.
        hc_bs = max(batch_size, 64)

        def one_w1(seed):
            it = make_dataset(str(root / "train-*"), hc_bs, 224,
                              is_training=True, seed=seed,
                              host_stage="crop",
                              private_threads=1).as_numpy_iterator()
            return ({"image": img, "label": lbl} for img, lbl in it)

        def mp_stage(seed, threads):
            return mp_batches(split_factory(seed, hc_bs, threads),
                              LOADER_WORKERS)

        ones, mps, frees = [], [], []
        for r in range(2):
            ones.append(_host_only_rate(one_w1(99 + r),
                                        HOST_CEIL_BATCHES, hc_bs))
            mps.append(_host_only_rate(mp_stage(99 + r, 1),
                                       HOST_CEIL_BATCHES, hc_bs))
            frees.append(_host_only_rate(mp_stage(99 + r, None),
                                         HOST_CEIL_BATCHES, hc_bs))
        one_rate = float(np.median(ones))
        mp_rate = float(np.median(mps))
        out["host_split_1thread_images_per_sec"] = round(one_rate, 1)
        out["host_decode_mp_1thread_images_per_sec"] = round(mp_rate, 1)
        out["host_decode_mp_speedup"] = round(mp_rate / one_rate, 2)
        out["host_decode_mp_ceiling_images_per_sec"] = round(
            float(np.median(frees)), 1)

    # Raw host→device link rate: when the fed numbers sit far below BOTH
    # the host ceiling and the device step rate, this is the culprit.
    from deepvision_tpu.core.mesh import data_sharding

    payload = np.zeros((batch_size, 224, 224, 3), np.uint8)
    sharding = data_sharding(mesh, payload.ndim)
    jax.block_until_ready(jax.device_put(payload, sharding))  # warm
    t0 = time.perf_counter()
    h2d_reps = 3
    for _ in range(h2d_reps):
        jax.block_until_ready(jax.device_put(payload, sharding))
    h2d_gbps = payload.nbytes * h2d_reps / (time.perf_counter() - t0) / 1e9
    h2d_img_rate = h2d_gbps * 1e9 / (224 * 224 * 3)
    out["h2d_link_gbytes_per_sec"] = round(h2d_gbps, 3)
    out["h2d_link_images_per_sec"] = round(h2d_img_rate, 1)
    return out


# ---- serving bench (`python bench.py serve`) ----------------------------
# Offered load vs achieved throughput + tail latency for the batched
# inference engine (deepvision_tpu/serve/), against the sequential
# batch-1 closed loop that predict.py-style calls amount to. Kept on
# lenet5 so the whole thing (4 bucket compiles + 2 measured phases)
# stays seconds-cheap even on a CPU-only container.
SERVE_REQUESTS = 512
SERVE_SEQ_CALLS = 64


PRECISION_MODEL = os.environ.get("BENCH_PRECISION_MODEL", "resnet50")
PRECISION_BATCH = int(os.environ.get("BENCH_PRECISION_BATCH", "0")) \
    or None  # None = BATCH_PER_CHIP * n_chips
PRECISION_WARMUP = 2
PRECISION_STEPS = int(os.environ.get("BENCH_PRECISION_STEPS", "8"))
PRECISION_REPS = int(os.environ.get("BENCH_PRECISION_REPS", "3"))


def precision_bench() -> dict:
    """``bench.py precision`` — the ISSUE 15 diet as ONE JSON row:
    the flagship model's shipped mixed-precision policy vs its f32
    twin, INTERLEAVED rep-by-rep (thermal/noise decorrelation),
    reporting img/s/chip, cost-analysis ``hbm_gb_per_step``, the
    backend-neutral ``wire_gb_per_step`` (tools/jaxlint/ircheck.
    jaxpr_wire_bytes — the dtype-faithful number on backends whose
    float normalization hides bf16 from cost analysis, like this dev
    box's cpu), and MFU side by side. ``BENCH_PRECISION_MODEL`` /
    ``_BATCH`` / ``_STEPS`` / ``_REPS`` override the defaults; the
    driver's on-chip r05 run records the real-silicon row."""
    from functools import partial

    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.core.precision import get_policy
    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.configs import get_config
    from deepvision_tpu.train.optimizers import make_optimizer
    from deepvision_tpu.train.state import create_train_state
    from deepvision_tpu.train.steps import classification_train_step
    from tools.hbm_budget import device_peaks, hbm_gb_per_step
    from tools.jaxlint.ircheck import jaxpr_wire_bytes

    n_chips = len(jax.devices())
    mesh = create_mesh(n_chips, 1)
    cfg = get_config(PRECISION_MODEL)
    batch_size = PRECISION_BATCH or BATCH_PER_CHIP * n_chips
    size, ch = cfg["input_size"], cfg["channels"]
    kind = jax.devices()[0].device_kind
    peak, _ = device_peaks(kind)
    rng = np.random.default_rng(0)
    batch = {
        "image": rng.integers(0, 255, (batch_size, size, size, ch)
                              ).astype(np.uint8),
        "label": rng.integers(0, cfg["num_classes"],
                              size=(batch_size,)).astype(np.int32),
    }
    norm = "torch" if cfg.get("augment") == "pt" else "imagenet"
    step_fn = partial(classification_train_step, normalize_kind=norm)
    device_batch = shard_batch(mesh, batch)

    arms = {}
    for arm_name in (cfg["precision"], "f32"):
        policy = get_policy(arm_name)
        model = get_model(PRECISION_MODEL,
                          num_classes=cfg["num_classes"],
                          dtype=policy.compute_dtype,
                          **cfg.get("model_kwargs", {}))
        tx, _ = make_optimizer(cfg, steps_per_epoch=100)
        state = create_train_state(model, tx, batch["image"][:1],
                                   policy=policy)
        step = compile_train_step(step_fn, mesh)
        key = jax.random.key(0)
        wire_gb = jaxpr_wire_bytes(
            jax.make_jaxpr(step_fn)(
                jax.eval_shape(lambda: state), device_batch, key
            ).jaxpr) / 1e9
        compiled = step.lower(state, device_batch, key).compile()
        arms[arm_name] = {
            "state": state, "compiled": compiled, "key": key,
            "hbm_gb_per_step": round(hbm_gb_per_step(compiled), 3),
            "wire_gb_per_step": round(wire_gb, 3),
            "flops_per_step": _flops_per_step(compiled),
            "times": [],
        }
        for _ in range(PRECISION_WARMUP):
            k, sub = jax.random.split(arms[arm_name]["key"])
            arms[arm_name]["key"] = k
            arms[arm_name]["state"], _m = compiled(
                arms[arm_name]["state"], device_batch, sub)
        jax.block_until_ready(arms[arm_name]["state"])

    for _rep in range(PRECISION_REPS):  # interleaved A/B chunks
        for arm in arms.values():
            t0 = time.perf_counter()
            for _ in range(PRECISION_STEPS):
                k, sub = jax.random.split(arm["key"])
                arm["key"] = k
                arm["state"], _m = arm["compiled"](
                    arm["state"], device_batch, sub)
            jax.block_until_ready(arm["state"])
            arm["times"].append(time.perf_counter() - t0)

    out = {"metric": f"precision_ab_{PRECISION_MODEL}",
           "batch": batch_size, "device_kind": kind,
           "steps_per_rep": PRECISION_STEPS, "reps": PRECISION_REPS}
    for arm_name, arm in arms.items():
        dt = float(np.median(arm["times"]))
        rate = PRECISION_STEPS * batch_size / dt / n_chips
        mfu = arm["flops_per_step"] * PRECISION_STEPS / dt / peak
        out[arm_name] = {
            "img_per_sec_per_chip": round(rate, 1),
            "mfu": round(mfu, 4),
            "hbm_gb_per_step": arm["hbm_gb_per_step"],
            "wire_gb_per_step": arm["wire_gb_per_step"],
        }
    policy_name, f32 = cfg["precision"], "f32"
    if policy_name != f32:
        a, b = out[policy_name], out[f32]
        out["throughput_ratio"] = round(
            a["img_per_sec_per_chip"] / b["img_per_sec_per_chip"], 3)
        out["wire_reduction"] = round(
            1 - a["wire_gb_per_step"] / b["wire_gb_per_step"], 4)
        out["hbm_reduction"] = round(
            1 - a["hbm_gb_per_step"] / b["hbm_gb_per_step"], 4)
    return out


# ---- ZeRO-1 A/B (`python bench.py zero1`) -------------------------------
# Fast-set models compiled replicated vs under the sharding engine's
# ZeRO-1 specs, at both lint-tier grids; residency is MEASURED from the
# stepped state's addressable shards, then reconciled against the
# shardcheck zero1_residency prediction — the lint tier's worklist
# numbers and the hardware must tell the same story.
ZERO1_MODELS = [m for m in os.environ.get(
    "BENCH_ZERO1_MODELS", "lenet5,dcgan").split(",") if m]
ZERO1_MESHES = ((2, 1), (2, 2))
ZERO1_STEPS = 2  # enough to materialize a stepped opt state per arm


def _zero1_case(name):
    """(state, batch, step_fn) for one A/B case — CONCRETE arrays (the
    residency numbers come from real device shards, not shape math) at
    the shipped config's geometry, batch pinned small: the measurement
    is placement, not throughput."""
    from functools import partial

    import jax.numpy as jnp

    from deepvision_tpu.core.precision import get_policy
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train import steps as S
    from deepvision_tpu.train.configs import get_config
    from deepvision_tpu.train.optimizers import make_optimizer
    from deepvision_tpu.train.state import create_train_state

    rng = np.random.default_rng(0)
    if name == "dcgan":
        # the non-TrainState family: both GAN subtrees shard through
        # the same Zero1Plan (train/gan.py)
        from deepvision_tpu.train.gan import (
            create_dcgan_state,
            dcgan_train_step,
        )

        batch = {"image": rng.normal(size=(64, 28, 28, 1))
                 .astype(np.float32)}
        state = create_dcgan_state(
            get_model("dcgan_generator", dtype=jnp.bfloat16),
            get_model("dcgan_discriminator", dtype=jnp.bfloat16))
        return state, batch, dcgan_train_step

    cfg = get_config(name)
    policy = get_policy(cfg["precision"])
    size, ch = cfg["input_size"], cfg["channels"]
    model = get_model(name, num_classes=cfg["num_classes"],
                      dtype=policy.compute_dtype,
                      **cfg.get("model_kwargs", {}))
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    batch = {
        "image": rng.normal(size=(64, size, size, ch)).astype(np.float32),
        "label": rng.integers(0, cfg["num_classes"],
                              size=(64,)).astype(np.int32),
    }
    norm = "torch" if cfg.get("augment") == "pt" else "imagenet"
    state = create_train_state(model, tx, batch["image"][:1],
                               policy=policy)
    return state, batch, partial(S.classification_train_step,
                                 normalize_kind=norm)


def _zero1_arm(name, mesh_shape, *, zero1: bool, rules):
    """Build fresh, compile (under the engine's ZeRO-1 state specs when
    asked), run ZERO1_STEPS, then read the truth off the devices:
    per-device opt-state bytes from the stepped state's addressable
    shards, per-device HBM traffic from the executable's cost analysis,
    collective bytes from its HLO. Returns (report, raw opt bytes on
    device 0)."""
    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.core.sharding import (
        state_partition_specs,
        zero1_plan as make_zero1_plan,
    )
    from deepvision_tpu.core.step import compile_train_step
    from tools.hbm_budget import strip_layouts
    from tools.jaxlint.shardcheck import parse_collective_bytes

    state, batch, step_fn = _zero1_case(name)
    mesh = create_mesh(*mesh_shape)
    state_spec = None
    if zero1:
        plan = make_zero1_plan(mesh, rules=rules)
        if plan is None:
            raise RuntimeError(
                "the [[shardcheck.rule]] opt_state row does not "
                "prescribe largest(...) — nothing to A/B")
        state = state.replace(zero1_plan=plan)
        state_spec = state_partition_specs(state, mesh, zero1=True,
                                           rules=rules)
    step = compile_train_step(step_fn, mesh, state_spec=state_spec)
    db = shard_batch(mesh, batch)
    key = jax.random.key(0)
    compiled = step.lower(state, db, key).compile()
    for _ in range(ZERO1_STEPS):
        key, sub = jax.random.split(key)
        state, _metrics = compiled(state, db, sub)
    jax.block_until_ready(state)

    dev = jax.devices()[0]
    opt_b = 0
    for leaf in jax.tree.leaves(state.opt_state):
        for sh in leaf.addressable_shards:
            if sh.device == dev:  # dev0's resident bytes for this leaf
                opt_b += sh.data.nbytes
                break
    colls = parse_collective_bytes(strip_layouts(compiled.as_text()))
    return {
        "hbm_gb_per_step": round(
            float(compiled.cost_analysis()["bytes accessed"]) / 1e9, 3),
        "opt_gb_per_device": round(opt_b / 1e9, 4),
        "coll_gb_per_step": round(
            sum(r["bytes"] for r in colls.values()) / 1e9, 3),
    }, opt_b


def zero1_bench() -> dict:
    """``bench.py zero1`` — the ISSUE 17 acceptance A/B as ONE JSON
    row: each fast-set model compiled replicated vs under the engine's
    ZeRO-1 specs at 2x1 and 2x2, reporting cost-analysis
    ``hbm_gb_per_step``, measured per-device opt-state residency and
    collective bytes side by side, and reconciling the measured ZeRO-1
    residency against shardcheck's ``zero1_residency`` prediction
    within ±5% (floored at 1 MB — the ledger's rounding quantum, which
    dominates at lenet scale). ``BENCH_ZERO1_MODELS`` overrides the
    model set for on-chip runs."""
    from deepvision_tpu.core import create_mesh
    from deepvision_tpu.core.sharding import load_partition_rules
    from tools.jaxlint.shardcheck import zero1_residency

    rules = load_partition_rules()
    n_dev = len(jax.devices())
    models: dict = {}
    all_ok = True
    for name in ZERO1_MODELS:
        per_mesh: dict = {}
        for mesh_shape in ZERO1_MESHES:
            mesh_str = f"{mesh_shape[0]}x{mesh_shape[1]}"
            need = mesh_shape[0] * mesh_shape[1]
            if need > n_dev:
                per_mesh[mesh_str] = {
                    "skipped": f"needs {need} devices, have {n_dev}"}
                continue
            state, _b, _s = _zero1_case(name)
            pred = zero1_residency(state, create_mesh(*mesh_shape))
            del state
            repl, repl_b = _zero1_arm(name, mesh_shape, zero1=False,
                                      rules=rules)
            z1, z1_b = _zero1_arm(name, mesh_shape, zero1=True,
                                  rules=rules)
            pred_b = pred["resid_gb"] * 1e9
            ok = abs(z1_b - pred_b) <= max(0.05 * pred_b, 1e6)
            all_ok = all_ok and ok
            per_mesh[mesh_str] = {
                "replicated": repl,
                "zero1": z1,
                "opt_freed_gb_per_device": round(
                    (repl_b - z1_b) / 1e9, 4),
                "shardcheck_residency": pred,
                "resid_reconciled_5pct": ok,
            }
        models[name] = per_mesh
    return {
        "metric": "zero1_ab",
        "models": models,
        "steps_per_arm": ZERO1_STEPS,
        "device_kind": jax.devices()[0].device_kind,
        "gates": {"resid_reconciled_5pct": all_ok},
        "obs": _obs_snapshot(),
    }


def cluster_bench() -> dict:
    """Distributed-resilience chaos drill (ISSUE 9 acceptance): a
    2-host supervised lenet cluster with a ``host_preempt`` notice
    mid-job versus its FAULT-FREE TWIN on identical flags. Gates:

    - the faulted run exits 0 with exactly ``preemptions=1 resumes=1``
      (coordinated save or epoch-boundary exit, then elastic resume on
      the surviving host);
    - its final train/val losses land within 5% of the twin's — the
      recovery claim as a measured number, not a log line. (The resumed
      generation replays the SAME global batches and KeySeq draws; the
      residual gap is 2-host vs 1-host collective reduction order.)

    Subprocess-driven (the supervisor relaunches worker generations),
    so this runs identically on the CPU dev box and an on-chip host.
    """
    import re
    import shutil
    import subprocess
    import tempfile

    repo = Path(__file__).resolve().parent
    flags = ["-m", "lenet5", "--epochs", "2", "--synthetic-size",
             "1024", "--batch-size", "64", "--steps-per-epoch", "12"]

    def run(workdir: Path, faults: str | None) -> tuple[str, int]:
        cmd = [sys.executable, "-u", str(repo / "train_dist.py"),
               "--supervise", "2", "--platform", "cpu",
               "--barrier-lead", "3", "--barrier-timeout-s", "60",
               "--straggler-after-s", "60",
               "--heartbeat-timeout-s", "300",
               "--init-timeout-s", "120"]
        if faults:
            cmd += ["--faults", faults]
        cmd += [*flags, "--workdir", str(workdir)]
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # 1 CPU device per worker process
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
        p = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                           stderr=subprocess.STDOUT, text=True,
                           timeout=1800)
        return p.stdout, p.returncode

    def final_losses(log: str) -> dict:
        out: dict = {}
        for line in log.splitlines():
            m = re.search(r"\[epoch (\d+)\]", line)
            if not m:
                continue
            for key in ("train_loss", "val_loss"):
                v = re.search(rf"{key}=([0-9.eE+-]+)", line)
                if v:
                    out[key] = float(v.group(1))  # last epoch wins
        return out

    root = Path(tempfile.mkdtemp(prefix="dvt_cluster_bench_"))
    try:
        twin_log, twin_rc = run(root / "twin", None)
        drill_log, drill_rc = run(root / "drill", "host_preempt@14")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    twin, drill = final_losses(twin_log), final_losses(drill_log)
    counters = re.search(
        r"\[cluster\] preemptions=(\d+) resumes=(\d+) "
        r"stragglers=(\d+) host_deaths=(\d+)", drill_log)
    preempts, resumes = ((int(counters.group(1)), int(counters.group(2)))
                         if counters else (-1, -1))
    gap = (abs(drill.get("val_loss", 1e9) - twin.get("val_loss", 0.0))
           / max(abs(twin.get("val_loss", 0.0)), 1e-9))
    mid_epoch = "coordinated save committed by all 2 hosts" in drill_log
    report = {
        "bench": "cluster",
        "twin_final": twin,
        "drill_final": drill,
        "final_loss_gap_frac": round(gap, 4),
        "preemptions": preempts,
        "resumes": resumes,
        "mid_epoch_coordinated_save": mid_epoch,
        "drill_exit": drill_rc,
        "twin_exit": twin_rc,
        "gates": {
            "exit_0": drill_rc == 0 and twin_rc == 0,
            "counters_exact": (preempts, resumes) == (1, 1),
            "loss_within_5pct": gap <= 0.05,
            # the tentpole mechanism must actually run: a drill that
            # quietly degrades to the epoch-boundary path would pass
            # the other gates without exercising the mid-epoch commit
            "mid_epoch_coordinated_save": mid_epoch,
        },
        "obs": _obs_snapshot(),
    }
    if not all(report["gates"].values()):  # evidence for the log
        print("# cluster drill tail:\n"
              + "\n".join(drill_log.splitlines()[-40:]),
              file=sys.stderr)
    return report


def sentinel_bench() -> dict:
    """Silent-failure-defense gates (ISSUE 12 acceptance):

    **Overhead** — the in-graph sentinel scalars must be ~free: the
    same lenet train step compiled with and without
    ``sentinel_step`` is timed (median of reps) and its cost-analysis
    HBM traffic compared. Gates: step-time regression < 2% and
    bytes-accessed ratio within the ±5% ircheck ledger band (the
    sentinels must not break donation or add an HBM round-trip).
    CPU-box numbers are noisy at lenet scale — the driver re-runs
    this on-chip for the recorded gate.

    **Twin drill** — a 2-host supervised run with a SILENT
    ``sdc_grad@20:host1`` versus its fault-free twin on identical
    ``--sentinel`` flags. Gates: divergence detected within K,
    exactly one replay, host 1 quarantined, drill completes on the
    survivor with final val_loss within 5% of the twin, and the
    false-positive guard (twin trips == 0, divergences == 0).
    """
    import re
    import shutil
    import subprocess
    import tempfile

    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.core.step import compile_train_step
    from deepvision_tpu.core import shard_batch
    from deepvision_tpu.models import get_model
    from deepvision_tpu.resilience.sentinel import sentinel_step
    from deepvision_tpu.train import steps as S
    from deepvision_tpu.train.state import create_train_state

    # ---- overhead: sentinels-on vs sentinels-off, same step --------
    mesh = create_mesh()
    rng = np.random.default_rng(0)
    bs = 256
    batch = {
        "image": rng.normal(size=(bs, 32, 32, 1)).astype(np.float32),
        "label": rng.integers(0, 10, size=(bs,)).astype(np.int32),
    }
    model = get_model("lenet5", num_classes=10)
    key = jax.random.key(0)

    def measure(step_fn):
        tx = optax.sgd(0.05)
        state = create_train_state(model, tx, batch["image"][:1])
        step = compile_train_step(step_fn, mesh)
        db = shard_batch(mesh, batch)
        compiled = step.lower(state, db, key).compile()
        ca = compiled.cost_analysis()
        k = key
        for _ in range(3):  # warmup
            k, sub = jax.random.split(k)
            state, _ = compiled(state, db, sub)
        jax.block_until_ready(state)
        reps = []
        for _ in range(5):
            n = 20
            t0 = time.perf_counter()
            for _ in range(n):
                k, sub = jax.random.split(k)
                state, _ = compiled(state, db, sub)
            jax.block_until_ready(state)
            reps.append((time.perf_counter() - t0) / n)
        return float(np.median(reps)), float(ca["bytes accessed"])

    t_off, bytes_off = measure(S.classification_train_step)
    t_on, bytes_on = measure(sentinel_step(S.classification_train_step))
    overhead_pct = (t_on - t_off) / t_off * 100.0
    hbm_ratio = bytes_on / bytes_off if bytes_off else 1.0

    # ---- twin drill ------------------------------------------------
    repo = Path(__file__).resolve().parent
    flags = ["-m", "lenet5", "--epochs", "2", "--synthetic-size",
             "2048", "--batch-size", "64", "--steps-per-epoch", "16",
             "--sentinel", "--audit-every", "8"]

    def run(workdir: Path, faults: str | None) -> tuple[str, int]:
        cmd = [sys.executable, "-u", str(repo / "train_dist.py"),
               "--supervise", "2", "--platform", "cpu",
               "--barrier-lead", "3", "--barrier-timeout-s", "60",
               "--straggler-after-s", "60",
               "--heartbeat-timeout-s", "300",
               "--init-timeout-s", "120"]
        if faults:
            cmd += ["--faults", faults]
        cmd += [*flags, "--workdir", str(workdir)]
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # 1 CPU device per worker process
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
        p = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                           stderr=subprocess.STDOUT, text=True,
                           timeout=1800)
        return p.stdout, p.returncode

    def final_val_loss(log: str) -> float:
        out = None
        for line in log.splitlines():
            m = re.search(r"val_loss=([0-9.eE+-]+)", line)
            if m and "[epoch" in line:
                out = float(m.group(1))  # last epoch wins
        return out if out is not None else 1e9

    def sentinel_counters(log: str) -> dict:
        m = re.search(r"\[sentinel\] trips=(\d+) audits=(\d+) "
                      r"divergences=(\d+) quarantined=(\d+)", log)
        keys = ("trips", "audits", "divergences", "quarantined")
        return (dict(zip(keys, map(int, m.groups()))) if m
                else dict.fromkeys(keys, -1))

    root = Path(tempfile.mkdtemp(prefix="dvt_sentinel_bench_"))
    try:
        twin_log, twin_rc = run(root / "twin", None)
        drill_log, drill_rc = run(root / "drill", "sdc_grad@20:host1")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    twin_c = sentinel_counters(twin_log)
    drill_c = sentinel_counters(drill_log)
    twin_val = final_val_loss(twin_log)
    drill_val = final_val_loss(drill_log)
    gap = abs(drill_val - twin_val) / max(abs(twin_val), 1e-9)
    detect = re.search(r"fingerprints disagree at audit step (\d+)",
                       drill_log)
    detect_latency = (int(detect.group(1)) - 20) if detect else -1

    report = {
        "bench": "sentinel",
        "overhead": {
            "step_ms_off": round(t_off * 1e3, 3),
            "step_ms_on": round(t_on * 1e3, 3),
            "overhead_pct": round(overhead_pct, 2),
            "hbm_bytes_off": bytes_off,
            "hbm_bytes_on": bytes_on,
            "hbm_ratio": round(hbm_ratio, 4),
        },
        "twin_final_val_loss": twin_val,
        "drill_final_val_loss": drill_val,
        "final_loss_gap_frac": round(gap, 4),
        "detect_latency_steps": detect_latency,
        "twin_counters": twin_c,
        "drill_counters": drill_c,
        "drill_exit": drill_rc,
        "twin_exit": twin_rc,
        "gates": {
            "exit_0": drill_rc == 0 and twin_rc == 0,
            # the acceptance wording: detected within K=16 (this drill
            # audits every 8, so latency must come in at <= 8)
            "detected_within_k": 0 <= detect_latency <= 16,
            "quarantined_host1": "QUARANTINED host 1" in drill_log
            and drill_c["quarantined"] == 1,
            "one_replay": "replay 1:" in drill_log
            and "replay 2:" not in drill_log,
            "loss_within_5pct": gap <= 0.05,
            # false-positive guard: sentinels-on fault-free run is
            # completely quiet
            "false_positive_guard": twin_c["trips"] == 0
            and twin_c["divergences"] == 0,
            "overhead_under_2pct": overhead_pct < 2.0,
            "hbm_within_5pct": 0.95 <= hbm_ratio <= 1.05,
        },
        "obs": _obs_snapshot(),
    }
    if not all(report["gates"].values()):  # evidence for the log
        print("# sentinel drill tail:\n"
              + "\n".join(drill_log.splitlines()[-40:]),
              file=sys.stderr)
    return report


def serve_bench(n_requests: int = SERVE_REQUESTS) -> dict:
    import contextlib

    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.serve import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    rng = np.random.default_rng(0)
    # restore chatter to stderr: stdout is the one-JSON-line contract
    with contextlib.redirect_stdout(sys.stderr):
        served = load_served("lenet5", None, num_classes=10)
    engine = InferenceEngine(
        [served], mesh=create_mesh(1, 1), buckets=(1, 4, 16, 64),
        max_queue=max(1024, 2 * n_requests),
    )
    xs = rng.normal(size=(n_requests, 32, 32, 1)).astype(np.float32)
    try:
        # pace both paths past first-dispatch jitter (all executables
        # are already compiled — warmup ran in the constructor)
        for i in range(8):
            engine.submit(xs[i]).result(timeout=60)
        misses_warm = engine.stats()["cache"]["misses"]

        # 1) sequential closed loop: submit → wait, one at a time — the
        # predict.py batch-1 pattern every request pays without batching
        t0 = time.perf_counter()
        for i in range(SERVE_SEQ_CALLS):
            engine.submit(xs[i % n_requests]).result(timeout=60)
        seq_rate = SERVE_SEQ_CALLS / (time.perf_counter() - t0)

        # 2) saturation burst: offer everything at once; the dispatcher
        # drains the backlog through the biggest buckets
        t0 = time.perf_counter()
        futures = [engine.submit(x) for x in xs]
        t_offered = time.perf_counter() - t0
        for f in futures:
            f.result(timeout=120)
        dt = time.perf_counter() - t0
        sat_rate = n_requests / dt

        stats = engine.stats()
        tel = stats["telemetry"]
        return {
            "metric": "serve_lenet5_requests_per_sec",
            "value": round(sat_rate, 1),
            "unit": "requests/sec",
            "sequential_batch1_per_sec": round(seq_rate, 1),
            "speedup_vs_sequential": round(sat_rate / seq_rate, 2),
            "offered_load_per_sec": round(n_requests / t_offered, 1),
            "achieved_frac_of_offered": round(
                sat_rate * t_offered / n_requests, 4),
            "e2e_latency": tel["e2e_latency"],
            "queue_wait": tel["queue_wait"],
            "device_time": tel["device_time"],
            "pad_overhead_frac": tel["pad_overhead_frac"],
            "mean_batch_rows": tel["mean_batch_rows"],
            "warmup_s": stats["warmup_s"],
            "cache": stats["cache"],
            # acceptance tripwire: no request after warmup may compile
            "no_retrace_after_warmup": (
                stats["cache"]["misses"] == misses_warm),
            # wire accounting (same contract as the train bench's
            # *_h2d_bytes_per_image): what one request input ships H2D
            "input_h2d_bytes_per_image": int(xs[0].nbytes),
            "input_wire_dtype": str(xs.dtype),
            "device_kind": jax.devices()[0].device_kind,
            "obs": _obs_snapshot(),
        }
    finally:
        engine.close()


# ---- pipeline serving bench (`python bench.py pipeline`) ----------------
# e2e detect -> crop -> pose through the device-resident DAG
# (serve/pipeline.py) vs the two-sequential-/v1/predict client it
# replaces: detect round-trip, HOST-side top-k + crop, then one pose
# round-trip per crop. Interleaved A/B closed-loop pairs (alternating
# order, same images) so scheduler/cache drift lands on both arms;
# p50/p95 per arm + the speedup ratio in one JSON row. Real task heads
# at reduced geometry (the tests/test_serve.py slow-tier pairing) so
# the measured win is the serving path, not model FLOPs.
PIPELINE_REQUESTS = int(os.environ.get("BENCH_PIPELINE_REQUESTS", "8"))
PIPELINE_FANOUT_K = int(os.environ.get("BENCH_PIPELINE_K", "2"))
PIPELINE_SIZE = 64  # yolov3/hourglass geometry AND the crop size


def pipeline_bench() -> dict:
    import contextlib

    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.ops.crop_resize import crop_and_resize
    from deepvision_tpu.serve import (
        InferenceEngine,
        Pipeline,
        PipelineSpec,
    )
    from deepvision_tpu.serve.models import load_served

    k, size = PIPELINE_FANOUT_K, PIPELINE_SIZE
    # restore chatter to stderr: stdout is the one-JSON-line contract
    with contextlib.redirect_stdout(sys.stderr):
        detect = load_served("yolov3", None, task="detect",
                             input_size=size, num_classes=5,
                             score_thresh=0.0)
        pose = load_served("hourglass104", None, task="pose",
                           input_size=size, num_heatmaps=4)
    spec = PipelineSpec.from_json({
        "name": "detpose",
        "buckets": [1, 4],
        "nodes": [
            {"name": "det", "model": "yolov3"},
            {"name": "people", "glue": "top_k_boxes",
             "inputs": ["det"], "params": {"k": k}},
            {"name": "crop", "glue": "crop_resize",
             "inputs": ["input", "people"], "params": {"size": size}},
            {"name": "pose", "model": "hourglass104",
             "inputs": ["crop.crops"], "buckets": [k, 4 * k]},
        ],
        "outputs": [{"node": "det"},
                    {"node": "pose", "mask": "crop.valid"}],
    })
    pipe = Pipeline(spec, {"yolov3": detect, "hourglass104": pose})
    engine = InferenceEngine(
        [detect, pose], mesh=create_mesh(1, 1), buckets=(1, 4),
        pipelines=[pipe], freeze_cache=True,
    )
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(PIPELINE_REQUESTS, size, size, 3)).astype(
        np.float32)

    def run_dag(x):
        return engine.submit(x, model="detpose").result(timeout=600)

    def run_sequential(x):
        # the client the DAG replaces: fetch the detect answer, glue on
        # the host, re-submit one predict per crop
        det = engine.submit(x, model="yolov3").result(timeout=600)
        scores = np.asarray(det["scores"], np.float32)
        boxes = np.asarray(det["boxes"], np.float32).reshape(-1, 4)
        order = (np.argsort(-scores, kind="stable")[:k]
                 if scores.size else [])
        sel = np.zeros((k, 4), np.float32)
        for slot, idx in enumerate(order):
            sel[slot] = boxes[idx]
        crops = np.asarray(crop_and_resize(x[None], sel[None], size))[0]
        poses = [engine.submit(c, model="hourglass104").result(
            timeout=600) for c in crops]
        return det, poses

    try:
        # pace both arms past first-dispatch jitter (every executable
        # compiled in the constructor — the cache is frozen)
        run_dag(xs[0])
        run_sequential(xs[0])
        misses_warm = engine.stats()["cache"]["misses"]
        lat = {"pipeline": [], "sequential": []}
        for i in range(PIPELINE_REQUESTS):
            arms = [("pipeline", run_dag),
                    ("sequential", run_sequential)]
            if i % 2:
                arms.reverse()
            for label, fn in arms:
                t0 = time.perf_counter()
                fn(xs[i])
                lat[label].append(time.perf_counter() - t0)

        def pcts(vals):
            v = np.sort(np.asarray(vals))
            return {"p50": round(float(np.percentile(v, 50)) * 1e3, 1),
                    "p95": round(float(np.percentile(v, 95)) * 1e3, 1),
                    "mean": round(float(v.mean()) * 1e3, 1)}

        pipe_ms, seq_ms = pcts(lat["pipeline"]), pcts(lat["sequential"])
        stats = engine.stats()
        return {
            "metric": "pipeline_detpose_sequential_over_dag_p50",
            "value": round(seq_ms["p50"] / pipe_ms["p50"], 2),
            "unit": "x (sequential / pipeline e2e latency, p50)",
            "requests_per_arm": PIPELINE_REQUESTS,
            "fanout_k": k,
            "input_size": size,
            "pipeline_e2e_ms": pipe_ms,
            "sequential_e2e_ms": seq_ms,
            "speedup_p95": round(seq_ms["p95"] / pipe_ms["p95"], 2),
            # acceptance tripwire: frozen cache + flat misses = zero
            # request-time compiles on either arm
            "no_retrace_after_warmup": (
                stats["cache"]["misses"] == misses_warm),
            "cache": stats["cache"],
            "pipelines_served": stats["pipelines"],
            "warmup_s": stats["warmup_s"],
            # CPU row caveat: on this box the DAG's win is host-hop
            # elimination (one submit/fetch/decode instead of 1+k); on
            # TPU the device-resident edges additionally skip the
            # PCIe/H2D round-trip per hop, so treat this number as the
            # floor of the production speedup
            "device_kind": jax.devices()[0].device_kind,
            "obs": _obs_snapshot(),
        }
    finally:
        engine.close()


# ---- serving fleet sweep (`python bench.py serve --sweep`) --------------
# Latency-throughput curve + replica-scaling ratio + SIGKILL chaos drill
# for the fleet router (deepvision_tpu/serve/router.py). Three sections:
#
# 1. *scaling* — FleetRouter over in-process EngineReplicas serving a
#    SIMULATED-DEVICE model (fixed 40ms request latency, ~zero host
#    CPU — how a chip-bound replica behaves), 1 vs 2 replicas,
#    interleaved alternating-order closed-loop burst pairs with a
#    median-of-ratios summary. Why simulated: this container has 2
#    cores behind a syscall-intercepting sandbox that cannot deliver
#    two clean cores to two compute processes (measured ~1.15x for
#    CPU-bound process pairs regardless of topology), so real compute
#    here measures the sandbox; the latency-bound replica isolates
#    what the tier actually claims — the ROUTER's ability to turn N
#    replicas into ~N capacity. Runs LAST: these replicas live in
#    this process, which may take the backend only after the process
#    fleet of sections 2-3 is gone.
# 2. *sweep* — a 2-replica PROCESS fleet (serve.py children, the
#    production topology) under an open-loop offered-rate ladder ->
#    offered vs achieved vs tail-latency curve.
# 3. *chaos* — same process fleet at its peak sustainable offered rate;
#    one replica gets a real SIGKILL mid-load. Clients retry sheds with
#    the Retry-After hint; the gate is failed-requests <= 1% of the
#    offered stream and windowed p95 recovery within 10s of the kill.
#
# The per-request workload is a serial fori_loop matmul chain exported
# to StableHLO (deep-model-like: latency bound by serial depth, ~40ms
# on one CPU core here) so the curve measures fleet scheduling, not
# request-parsing overhead. Knobs via env: SWEEP_D / SWEEP_CHAIN
# (workload), SWEEP_PAIRS, SWEEP_BURST, SWEEP_POINT_S, CHAOS_S.
SWEEP_D = int(os.environ.get("SWEEP_D", "96"))
SWEEP_CHAIN = int(os.environ.get("SWEEP_CHAIN", "65536"))
SWEEP_PAIRS = int(os.environ.get("SWEEP_PAIRS", "8"))
SWEEP_BURST = int(os.environ.get("SWEEP_BURST", "48"))
SWEEP_POINT_S = float(os.environ.get("SWEEP_POINT_S", "4.0"))
CHAOS_S = float(os.environ.get("CHAOS_S", "16.0"))
CHAOS_KILL_AT_S = 5.0
CHAOS_RETRY_AGE_S = 40.0
ERROR_BUDGET_FRAC = 0.01
P95_RECOVERY_S = 10.0


def _export_sweep_artifact(path: str) -> None:
    """Export the serial-chain request workload to StableHLO at
    ``path``. A ``jax.export`` artifact is platform-specific and the
    export initialises the backend, so :func:`serve_sweep_bench` runs
    this in a child process that exits before any replica starts, and
    writes it under the run's own directory every time."""
    from deepvision_tpu.export import export_forward, save_exported
    from deepvision_tpu.startup import init_runtime

    init_runtime()
    rng = np.random.default_rng(0)
    w = (rng.normal(size=(SWEEP_D, SWEEP_D)).astype(np.float32)
         / np.sqrt(SWEEP_D))

    def apply_fn(variables, x):
        def body(_i, h):
            return jnp.tanh(h @ variables["w"])

        return jax.lax.fori_loop(0, SWEEP_CHAIN, body, x)

    sample = rng.normal(size=(1, SWEEP_D)).astype(np.float32)
    save_exported(path, export_forward(apply_fn, {"w": w}, sample,
                                       train_kwarg=False))


SIM_LATENCY_S = float(os.environ.get("SWEEP_SIM_LATENCY_MS", "40")) / 1e3


def _sim_model():
    """Simulated chip-bound served model: fixed device latency, ~zero
    host CPU (the replica's capacity is its serial dispatcher, exactly
    like a one-chip replica at fixed batch latency)."""
    from deepvision_tpu.serve import ServedModel

    def runner(x):
        time.sleep(SIM_LATENCY_S)
        return {"y": x}

    def post(host, i):
        return {"y": float(np.asarray(host["y"][i]).ravel()[0])}

    return ServedModel(
        name="sim", task="classify", forward=lambda v, x: x,
        variables=None, input_shape=(8,), postprocess=post,
        precompiled=runner)


def _sim_fleet(n: int):
    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.obs.metrics import Registry
    from deepvision_tpu.serve import EngineReplica, FleetRouter
    from deepvision_tpu.serve.telemetry import RouterTelemetry

    def factory(sid):
        return EngineReplica(sid, lambda: [_sim_model()],
                             mesh=create_mesh(1, 1), buckets=(1,))

    # private registry: the 1- and 2-replica fleets run SIDE BY SIDE,
    # and router_* registration is latest-wins in a shared registry
    return FleetRouter(factory, replicas=n, models=["sim"],
                       max_queue=1024,
                       telemetry=RouterTelemetry(registry=Registry()))


def _process_fleet(path: str, n: int, devices: dict,
                   max_queue: int = 64):
    from deepvision_tpu.serve import FleetRouter
    from deepvision_tpu.serve.replica import (
        process_replica_factory,
        replica_argv,
    )

    argv = replica_argv([], artifact_specs=[f"load={path}"])
    # on a TPU host each replica is confined to a chip of its own;
    # fewer chips than replicas is an error here, not a startup timeout
    factory = process_replica_factory(lambda sid: argv, replicas=n,
                                      devices=devices)
    return FleetRouter(factory, replicas=n, models=["load"],
                       max_queue=max_queue)


def _burst(router, xs, n_req: int) -> float:
    """Closed-loop saturation burst -> achieved requests/sec."""
    t0 = time.perf_counter()
    futs = [router.submit(xs[i % len(xs)], model="load")
            for i in range(n_req)]
    for f in futs:
        f.result(timeout=600)
    return n_req / (time.perf_counter() - t0)


def _scaling_section() -> dict:
    """1- vs 2-replica fleets of simulated-device replicas:
    alternating-order interleaved burst pairs, median ratio (this
    box's scheduling drifts on the seconds scale — same honesty
    discipline as the fed-bench A/B)."""
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(16, 8)).astype(np.float32)
    fa, fb = _sim_fleet(1), _sim_fleet(2)

    def sim_burst(r, n_req):
        t0 = time.perf_counter()
        futs = [r.submit(xs[i % len(xs)], model="sim")
                for i in range(n_req)]
        for f in futs:
            f.result(timeout=300)
        return n_req / (time.perf_counter() - t0)

    try:
        for r in (fa, fb):  # unrecorded warmup burst per fleet
            sim_burst(r, 12)
        singles, fleets, ratios = [], [], []
        for rep in range(SWEEP_PAIRS):
            if rep % 2 == 0:
                a = sim_burst(fa, SWEEP_BURST)
                b = sim_burst(fb, SWEEP_BURST)
            else:
                b = sim_burst(fb, SWEEP_BURST)
                a = sim_burst(fa, SWEEP_BURST)
            singles.append(round(a, 1))
            fleets.append(round(b, 1))
            ratios.append(b / a)
        return {
            "workload": ("simulated chip-bound replica "
                         f"({SIM_LATENCY_S * 1e3:.0f}ms device latency "
                         "per request, serial per replica)"),
            "single_replica_per_s": singles,
            "two_replica_per_s": fleets,
            "single_replica_median_per_s": round(
                float(np.median(singles)), 1),
            "two_replica_median_per_s": round(
                float(np.median(fleets)), 1),
            "speedup_2x": round(float(np.median(ratios)), 2),
        }
    finally:
        fa.close()
        fb.close()


class _OpenLoopClient:
    """Paced open-loop load generator with optional shed-retry: one
    logical request per schedule slot; a 429/shed resubmits after its
    Retry-After hint (bounded by request age) instead of counting as a
    failure — sheds are the fleet's designed overload response."""

    def __init__(self, router, xs, *, rate: float, duration_s: float,
                 retry_sheds: bool):
        self.router = router
        self.xs = xs
        self.rate = rate
        self.duration_s = duration_s
        self.retry_sheds = retry_sheds
        self.lock = threading.Lock()
        self.completed: list[tuple[float, float]] = []  # (t_first, e2e)
        self.shed = 0
        self.failed = 0
        self.inflight = 0
        self.retry_heap: list = []  # (due, seq, t_first, idx)
        self._seq = 0

    def run(self) -> None:
        import heapq

        from deepvision_tpu.serve import ShedError

        t_start = time.monotonic()
        n_total = int(self.rate * self.duration_s)

        def finish(t_first, idx, fut):
            now = time.monotonic()
            with self.lock:
                self.inflight -= 1
            try:
                fut.result(timeout=0)
                with self.lock:
                    self.completed.append((t_first, now - t_first))
                return
            except ShedError as e:
                with self.lock:
                    self.shed += 1
                    if self.retry_sheds and \
                            now - t_first < CHAOS_RETRY_AGE_S:
                        self._seq += 1
                        heapq.heappush(
                            self.retry_heap,
                            (now + max(0.05, e.retry_after_s),
                             self._seq, t_first, idx))
                        return
            except Exception:
                pass
            with self.lock:
                self.failed += 1

        def launch(t_first, idx):
            with self.lock:
                self.inflight += 1
            try:
                fut = self.router.submit(self.xs[idx % len(self.xs)],
                                         model="load")
            except Exception as e:  # synchronous shed/reject
                fut = Future()
                fut.set_exception(e)
            fut.add_done_callback(
                lambda f, t=t_first, i=idx: finish(t, i, f))

        offered = 0
        while True:
            now = time.monotonic()
            due_retry = None
            with self.lock:
                if self.retry_heap and self.retry_heap[0][0] <= now:
                    due_retry = heapq.heappop(self.retry_heap)
            if due_retry is not None:
                _due, _seq, t_first, idx = due_retry
                launch(t_first, idx)
                continue
            if offered < n_total:
                due_next = t_start + offered / self.rate
                if now >= due_next:
                    launch(now, offered)
                    offered += 1
                    continue
            with self.lock:
                drained = (offered >= n_total and self.inflight == 0
                           and not self.retry_heap)
                next_retry = (self.retry_heap[0][0]
                              if self.retry_heap else None)
            if drained:
                return
            if now - t_start > self.duration_s + 120:
                # hard stop: whatever is still in flight or queued for
                # retry was LOST — count it failed, or a wedged fleet
                # would pass the error-budget gate by hanging
                with self.lock:
                    self.failed += self.inflight + len(self.retry_heap)
                return
            waits = [0.02]
            if offered < n_total:
                waits.append(max(0.0, t_start + offered / self.rate
                                 - now))
            if next_retry is not None:
                waits.append(max(0.0, next_retry - now))
            time.sleep(max(0.001, min(waits)))

    def summary(self, wall_s: float) -> dict:
        lats = np.array([l for _t, l in self.completed]) * 1e3
        return {
            "achieved_per_s": round(len(self.completed) / wall_s, 1),
            "completed": len(self.completed),
            "sheds": self.shed,
            "failed": self.failed,
            "p50_ms": round(float(np.percentile(lats, 50)), 1)
            if len(lats) else None,
            "p95_ms": round(float(np.percentile(lats, 95)), 1)
            if len(lats) else None,
            "p99_ms": round(float(np.percentile(lats, 99)), 1)
            if len(lats) else None,
        }


def _sweep_section(router, xs, capacity: float) -> tuple[list, float]:
    """Offered-rate ladder -> latency-throughput curve; returns the
    curve and the peak sustainable offered rate (highest point with
    achieved >= 0.9 x offered and zero failures)."""
    curve = []
    peak = 0.3 * capacity
    for frac in (0.3, 0.5, 0.7, 0.85, 1.0):
        rate = max(1.0, frac * capacity)
        client = _OpenLoopClient(router, xs, rate=rate,
                                 duration_s=SWEEP_POINT_S,
                                 retry_sheds=False)
        t0 = time.monotonic()
        client.run()
        wall = time.monotonic() - t0
        point = {"offered_per_s": round(rate, 1),
                 **client.summary(wall)}
        curve.append(point)
        if point["failed"] == 0 and \
                point["achieved_per_s"] >= 0.9 * rate:
            peak = max(peak, rate)
    return curve, peak


def _chaos_section(router, xs, rate: float) -> dict:
    """Offered load at the N-1-provisioned rate (the fleet-sizing
    contract: capacity must survive one replica loss, so the drill
    offers what the SURVIVORS can sustain — killing half the fleet at
    full-fleet peak can only re-stabilize when the respawn lands);
    SIGKILL one replica at CHAOS_KILL_AT_S. Gates: failed <= 1% of
    logical requests, and completion-windowed p95 back under the
    recovery threshold within P95_RECOVERY_S of the kill."""
    client = _OpenLoopClient(router, xs, rate=rate, duration_s=CHAOS_S,
                             retry_sheds=True)
    killed = {}

    def killer():
        time.sleep(CHAOS_KILL_AT_S)
        with router._lock:
            ready = [s for s in router._slots if s.state == "ready"]
        if ready:
            victim = ready[0]
            killed["replica"] = victim.sid
            killed["t"] = time.monotonic()
            victim.replica.kill()  # REAL SIGKILL (process replica)

    kt = threading.Thread(target=killer)
    t_start = time.monotonic()
    kt.start()
    client.run()
    kt.join()
    wall = time.monotonic() - t_start
    base = client.summary(wall)
    n_logical = int(rate * CHAOS_S)
    failed_frac = client.failed / max(1, n_logical)
    # p95 per completion-second window (what a latency dashboard
    # shows); per-request latency still includes shed-retry time, the
    # client-visible truth
    t_kill = killed.get("t", t_start + CHAOS_KILL_AT_S) - t_start
    windows: dict[int, list] = {}
    for t_first, lat in client.completed:
        done_s = int(t_first + lat - t_start)
        windows.setdefault(done_s, []).append(lat * 1e3)
    pre = [v for s, vs in windows.items() if 1 <= s < int(t_kill)
           for v in vs]
    pre_p95 = float(np.percentile(pre, 95)) if pre else 0.0
    threshold = max(2.5 * pre_p95, 500.0)
    recovery_s = None
    for s in sorted(w for w in windows if w >= int(t_kill)):
        if windows[s] and float(
                np.percentile(windows[s], 95)) <= threshold:
            recovery_s = round(s + 1 - t_kill, 1)
            break
    return {
        "offered_per_s": round(rate, 1),
        **base,
        "killed_replica": killed.get("replica"),
        "kill_at_s": round(t_kill, 1),
        "failed_frac": round(failed_frac, 4),
        "error_budget_frac": ERROR_BUDGET_FRAC,
        "error_budget_ok": failed_frac <= ERROR_BUDGET_FRAC,
        "pre_kill_p95_ms": round(pre_p95, 1),
        "p95_recovery_threshold_ms": round(threshold, 1),
        "p95_recovered_after_s": recovery_s,
        "p95_recovery_ok": (recovery_s is not None
                            and recovery_s <= P95_RECOVERY_S),
        "router": router.telemetry.snapshot(),
    }


def serve_sweep_bench() -> dict:
    import shutil
    import subprocess
    import tempfile

    from deepvision_tpu.startup import init_runtime, probe_devices

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(16, SWEEP_D)).astype(np.float32)

    # The process fleet's replicas need the chip(s), and a chip belongs
    # to one process at a time: until the fleet is closed this parent
    # stays off the backend. The device probe and the artifact export
    # each run in a child that exits before the first replica starts.
    devices = probe_devices()
    workdir = tempfile.mkdtemp(prefix="dvt_sweep_")
    path = os.path.join(workdir, "load.stablehlo")
    print("# sweep: exporting the request workload (child process)...",
          file=sys.stderr)
    subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; bench._export_sweep_artifact(sys.argv[1])",
         path],
        cwd=str(Path(__file__).resolve().parent), check=True,
        stdout=sys.stderr)
    print("# sweep: booting 2-replica process fleet...", file=sys.stderr)
    router = _process_fleet(path, 2, devices)
    try:
        _burst(router, xs, 12)  # warm both replicas' request paths
        capacity = _burst(router, xs, SWEEP_BURST)
        print(f"# process-fleet capacity ~{capacity:.1f} req/s; "
              "sweeping offered rates...", file=sys.stderr)
        curve, peak = _sweep_section(router, xs, capacity)
        # the drill rate provisions for one replica loss (N-1 rule) and
        # re-measures capacity RIGHT before the kill — this box's
        # throughput drifts on the seconds scale, and a stale estimate
        # turns the drill into a capacity-starvation test instead of a
        # failover test
        fresh = _burst(router, xs, SWEEP_BURST)
        chaos_rate = max(1.0, 0.4 * fresh)
        print(f"# peak sustainable {peak:.1f} req/s (fresh capacity "
              f"{fresh:.1f}); chaos drill at N-1-provisioned "
              f"{chaos_rate:.1f} req/s (SIGKILL at "
              f"t={CHAOS_KILL_AT_S:.0f}s)...", file=sys.stderr)
        chaos = _chaos_section(router, xs, chaos_rate)
        print(f"# chaos: {router.summary_line()}", file=sys.stderr)
    finally:
        router.close()
        shutil.rmtree(workdir, ignore_errors=True)

    # every replica is gone: only now may this process take the backend
    # (the simulated-device replicas run in-process)
    init_runtime()
    print("# sweep: router scaling section (simulated-device "
          "replicas)...", file=sys.stderr)
    scaling = _scaling_section()
    print(f"# scaling: {scaling['speedup_2x']}x "
          f"({scaling['single_replica_median_per_s']} -> "
          f"{scaling['two_replica_median_per_s']} req/s)",
          file=sys.stderr)

    return {
        "metric": "serve_fleet_sweep_requests_per_sec",
        "value": scaling["two_replica_median_per_s"],
        "unit": "requests/sec",
        "process_fleet_workload": {
            "kind": "stablehlo serial matmul chain (batch 1)",
            "dim": SWEEP_D,
            "chain": SWEEP_CHAIN,
        },
        "input_h2d_bytes_per_image": int(xs[0].nbytes),
        "input_wire_dtype": str(xs.dtype),
        "scaling": scaling,
        "process_fleet_capacity_per_s": round(capacity, 1),
        "latency_throughput_curve": curve,
        "peak_sustainable_per_s": round(peak, 1),
        "chaos": chaos,
        "gates": {
            "speedup_2x_ge_1.6": scaling["speedup_2x"] >= 1.6,
            "error_budget_ok": chaos["error_budget_ok"],
            "p95_recovery_ok": chaos["p95_recovery_ok"],
        },
        "device": devices,
        "device_kind": devices["kind"],
        "obs": _obs_snapshot(),
    }


# ------------------------------------------- stateful stream chaos drill

# N synthetic video streams driven through the stateful tracking
# pipeline on a 2-replica fleet; a replica is killed mid-stream and the
# drill gates on the crash-safe session contract: zero stream resets
# (every migrated stream restores from snapshot + replay), per-stream
# frame ordering preserved across the failover, p95 frame latency in
# budget, and a fault-free twin run producing BIT-IDENTICAL outputs
# (the determinism pin: failover must not change results, only move
# where they're computed).
STREAMS_N = int(os.environ.get("STREAMS_N", "4"))
STREAMS_FRAMES = int(os.environ.get("STREAMS_FRAMES", "40"))
STREAM_P95_BUDGET_MS = float(os.environ.get("STREAM_P95_BUDGET_MS",
                                            "2000"))


def _stream_fleet(snap_dir: str, n: int = 2):
    """In-process 2-replica fleet serving the synthetic tracking
    pipeline; replicas SHARE ``snap_dir`` (the cross-replica restore
    path the kill exercises). Single-bucket ladder: batch composition
    can't vary between the fault run and its twin."""
    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.obs.metrics import Registry
    from deepvision_tpu.serve import EngineReplica, FleetRouter
    from deepvision_tpu.serve.sessions import (
        SessionStore,
        TrackingPipeline,
        synthetic_detector,
    )
    from deepvision_tpu.serve.telemetry import RouterTelemetry

    def factory(sid):
        def build():
            det = synthetic_detector()
            store = SessionStore(snapshot_dir=snap_dir, snapshot_every=4)
            return [det, TrackingPipeline("track", det, store,
                                          detect_every=4)]

        return EngineReplica(sid, build, mesh=create_mesh(1, 1),
                             buckets=(4,))

    # private registry: the fault fleet and its twin run in one process
    return FleetRouter(factory, replicas=n, models=["synth", "track"],
                       max_queue=1024, default_deadline_s=60.0,
                       telemetry=RouterTelemetry(registry=Registry()))


def _stream_drill(snap_dir: str, frames: dict,
                  kill_at_frame: int | None = None) -> dict:
    """Drive every stream through its frames in seq order (streams
    interleaved). With ``kill_at_frame``, wait for that frame round to
    complete, then kill the replica holding the most stream pins —
    the remaining frames must flow through migration + snapshot
    restore + windowed replay."""
    import collections

    router = _stream_fleet(snap_dir)
    try:
        streams = sorted(frames)
        n_frames = len(frames[streams[0]])
        lock = threading.Lock()
        order: dict = collections.defaultdict(list)
        lats: list = []

        def mk_cb(s, f, t0):
            def cb(_fut):
                t = time.perf_counter()
                with lock:
                    order[s].append(f)
                    lats.append((t - t0) * 1e3)

            return cb

        futs = []
        for f in range(n_frames):
            round_futs = []
            for s in streams:
                t0 = time.perf_counter()
                fut = router.submit(frames[s][f], model="track",
                                    session=s, seq=f)
                fut.add_done_callback(mk_cb(s, f, t0))
                futs.append((s, f, fut))
                round_futs.append(fut)
            if f == kill_at_frame:
                # let the round land so the victim has real state +
                # cadence snapshots, then SIGKILL-analog it (EngineReplica
                # .kill() abandons sessions without a flush — recovery
                # runs off the cadence snapshots, the crash semantics)
                for fut in round_futs:
                    fut.result(timeout=120)
                pins = router.stats()["sessions"]["pins"]
                with router._lock:
                    ready = {sl.sid: sl for sl in router._slots
                             if sl.state == "ready"}
                counts = collections.Counter(
                    p for p in pins.values() if p in ready)
                victim = ready[counts.most_common(1)[0][0]]
                print(f"# killing {victim.sid} after frame {f} "
                      f"({counts[victim.sid]} pinned stream(s))",
                      file=sys.stderr)
                victim.replica.kill()
        outs = {}
        resets = 0
        for s, f, fut in futs:
            r = fut.result(timeout=180)
            if r.get("state_reset"):
                resets += 1
            outs[(s, f)] = (r["boxes"], r["scores"], r["tracked"])
        tele = router.telemetry
        return {"outs": outs, "order": dict(order), "lats": lats,
                "resets": resets, "migrated": tele.sessions_migrated,
                "declared_resets": tele.session_resets,
                "summary": tele.summary_line()}
    finally:
        router.close()


def streams_bench() -> dict:
    import shutil
    import tempfile

    rng = np.random.default_rng(7)
    streams = [f"cam{i}" for i in range(STREAMS_N)]
    frames = {
        s: [np.asarray(rng.normal(scale=0.3, size=(16, 16, 1)),
                       np.float32)
            for _ in range(STREAMS_FRAMES)]
        for s in streams}
    kill_at = STREAMS_FRAMES // 2

    d1 = tempfile.mkdtemp(prefix="dvtpu-streams-")
    d2 = tempfile.mkdtemp(prefix="dvtpu-streams-twin-")
    try:
        print(f"# streams drill: {STREAMS_N} streams x "
              f"{STREAMS_FRAMES} frames on a 2-replica fleet, killing "
              f"the pinned replica after frame {kill_at}...",
              file=sys.stderr)
        fault = _stream_drill(d1, frames, kill_at_frame=kill_at)
        print(f"# {fault['summary']}", file=sys.stderr)
        print("# fault-free twin (determinism pin)...", file=sys.stderr)
        twin = _stream_drill(d2, frames)
        print(f"# {twin['summary']}", file=sys.stderr)
    finally:
        shutil.rmtree(d1, ignore_errors=True)
        shutil.rmtree(d2, ignore_errors=True)

    ordering_ok = all(
        fault["order"].get(s, []) == list(range(STREAMS_FRAMES))
        for s in streams)
    p95 = float(np.percentile(fault["lats"], 95)) if fault["lats"] else 0.0
    identical = fault["outs"] == twin["outs"]
    gates = {
        # the honesty contract: migration is fine, SILENT or declared
        # state loss is not
        "stream_resets_zero": (fault["resets"] == 0
                               and fault["declared_resets"] == 0),
        "ordering_ok": ordering_ok,
        # the drill must actually have exercised a failover
        "migrated_nonzero": fault["migrated"] >= 1,
        "p95_in_budget": p95 <= STREAM_P95_BUDGET_MS,
        "twin_no_migrations": twin["migrated"] == 0,
        "twin_outputs_identical": identical,
    }
    return {
        "metric": "stream_chaos_p95_ms",
        "value": round(p95, 1),
        "unit": "ms",
        "streams": STREAMS_N,
        "frames_per_stream": STREAMS_FRAMES,
        "kill_after_frame": kill_at,
        "stream_resets": fault["resets"],
        "sessions_migrated": fault["migrated"],
        "p95_ms": round(p95, 1),
        "p95_budget_ms": STREAM_P95_BUDGET_MS,
        "twin": {"sessions_migrated": twin["migrated"],
                 "stream_resets": twin["resets"],
                 "outputs_identical": identical},
        "gates": gates,
        "pass": all(gates.values()),
        "device_kind": jax.devices()[0].device_kind,
    }


# ---------------------- tenancy bench (`python bench.py tenancy`) --------
# Multi-tenant serving economics (ISSUE 20) in one JSON row:
#   cold-start A/B — a fresh replica's warmup when it must TRACE every
#   (model, bucket) executable vs when it warms from a populated
#   --store AOT artifact directory (the PR 6 respawn compile storm vs
#   its fix), gated on the second warm paying zero compile-cache
#   misses;
#   hot-swap drill — closed-loop load on the tenant while its weights
#   hot-swap mid-stream (perturb path: new fingerprint, no second
#   checkpoint), gated on zero dropped requests, and reporting p95
#   during the swap window vs steady-state so the "zero-drop" claim
#   carries its latency cost.
TENANCY_LOAD_THREADS = int(os.environ.get("BENCH_TENANCY_THREADS", "3"))
TENANCY_PHASE_S = float(os.environ.get("BENCH_TENANCY_PHASE_S", "1.5"))


def tenancy_bench() -> dict:
    import contextlib
    import tempfile
    import threading

    from deepvision_tpu.core.mesh import create_mesh
    from deepvision_tpu.serve import InferenceEngine
    from deepvision_tpu.serve.models import load_served

    rng = np.random.default_rng(0)
    store = tempfile.mkdtemp(prefix="dvt-aot-bench-")
    mesh = create_mesh(1, 1)
    buckets = (1, 4, 16)

    def fresh_engine():
        # restore chatter to stderr: stdout is the one-JSON-line
        # contract
        with contextlib.redirect_stdout(sys.stderr):
            served = load_served("lenet5", None, num_classes=10)
        return InferenceEngine([served], mesh=mesh, buckets=buckets,
                               max_queue=1024, store=store)

    # 1) cold-start A/B: trace everything (and populate the store)...
    eng = fresh_engine()
    warm_trace_s = eng.warmup_s
    store_puts = eng.stats()["artifact_store"]["puts"]
    eng.close()
    # ...vs warm the SAME ladder from disk on the respawn
    eng = fresh_engine()
    warm_store_s = eng.warmup_s
    stats = eng.stats()
    warmed_from_store = stats["warmed_from_store"]
    second_warm_misses = stats["cache"]["misses"]

    # 2) hot-swap drill under closed-loop load
    xs = rng.normal(size=(64, 32, 32, 1)).astype(np.float32)
    lat, errors = [], []  # (t_done, seconds) samples
    lock = threading.Lock()
    stop = threading.Event()

    def pound():
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                eng.submit(xs[i % len(xs)]).result(timeout=60)
                t1 = time.perf_counter()
                with lock:
                    lat.append((t1, t1 - t0))
            except Exception as e:  # any drop under swap is the bug
                with lock:
                    errors.append(repr(e))
            i += 1

    threads = [threading.Thread(target=pound)
               for _ in range(TENANCY_LOAD_THREADS)]
    try:
        for t in threads:
            t.start()
        time.sleep(TENANCY_PHASE_S)  # steady state on old weights
        swap_t0 = time.perf_counter()
        swap = eng.hot_swap("lenet5", perturb=0.01)
        swap_t1 = time.perf_counter()
        time.sleep(TENANCY_PHASE_S)  # steady state on new weights
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        tenancy = eng.tenancy.stats()
        eng.close()

    def p95_ms(samples):
        if not samples:
            return None
        return round(float(np.percentile(
            [s * 1e3 for s in samples], 95)), 1)

    steady = [d for t, d in lat if t < swap_t0 or t > swap_t1 + 0.2]
    during = [d for t, d in lat if swap_t0 <= t <= swap_t1 + 0.2]
    speedup = round(warm_trace_s / warm_store_s, 2) \
        if warm_store_s > 0 else None
    return {
        "metric": "tenancy_cold_start_speedup",
        "value": speedup,
        "unit": "x",
        "warm_from_trace_s": warm_trace_s,
        "warm_from_store_s": warm_store_s,
        "store_puts": store_puts,
        "warmed_from_store": warmed_from_store,
        "second_warm_cache_misses": second_warm_misses,
        "hot_swap": {
            "swap_s": round(swap_t1 - swap_t0, 3),
            "dropped_requests": len(errors),
            "errors": errors[:5],
            "requests_completed": len(lat),
            "p95_steady_ms": p95_ms(steady),
            "p95_during_swap_ms": p95_ms(during),
            "swapped_fingerprint": swap["fingerprint"],
            "dropped_executables": swap["dropped_executables"],
            "swaps": tenancy["swaps"],
        },
        "gates": {
            "no_retrace_on_store_warm": second_warm_misses == 0,
            "zero_dropped_during_swap": not errors,
            "exactly_one_swap": tenancy["swaps"] == 1,
        },
        "pass": (second_warm_misses == 0 and not errors
                 and tenancy["swaps"] == 1),
        "device_kind": jax.devices()[0].device_kind,
    }


if __name__ == "__main__":

    # BENCH_TRACE=path: span-trace the bench itself (the feed loops
    # carry fetch/host_next/shard spans) and export Chrome trace JSON.
    # BENCH_TRACE_SPOOL=dir additionally spools spans crash-safe (and
    # picks up the decode workers' host_decode rows), mergeable with a
    # co-running fleet's spools via tools/trace_merge.py
    _trace_path = os.environ.get("BENCH_TRACE")
    _spool = None
    if _trace_path:
        from deepvision_tpu.obs.trace import get_tracer

        get_tracer().enable()
    _spool_dir = os.environ.get("BENCH_TRACE_SPOOL")
    if _spool_dir:
        from deepvision_tpu.obs.distributed import ENV_SPOOL, SpanSpool
        from deepvision_tpu.obs.trace import get_tracer

        get_tracer().set_labels(role="bench")
        _spool = SpanSpool(_spool_dir, role="bench")
        # the mp decode workers inherit this and spool beside us
        os.environ[ENV_SPOOL] = _spool_dir
    # sub-commands whose work runs in THIS process: it places the
    # compile cache and names its device first (init_runtime). cluster
    # and `serve --sweep` are parents of processes that need the device
    # and take the backend late or never; the default mode requires a
    # TPU inside main().
    _in_process = {"precision": precision_bench,
                   "sentinel": sentinel_bench, "zero1": zero1_bench,
                   "pipeline": pipeline_bench, "streams": streams_bench,
                   "tenancy": tenancy_bench, "serve": serve_bench}
    _mode = next((a for a in sys.argv[1:]
                  if a in _in_process or a == "cluster"), None)
    try:
        if _mode == "cluster":
            print(json.dumps(cluster_bench()))
        elif _mode == "serve" and "--sweep" in sys.argv[1:]:
            print(json.dumps(serve_sweep_bench()))
        elif _mode is not None:
            if _mode == "zero1":
                # the 2x2 arm needs 4 devices: land the host-platform
                # device-count flag before the FIRST backend init; a
                # no-op on real accelerator platforms
                _flags = os.environ.get("XLA_FLAGS", "")
                if "xla_force_host_platform_device_count" not in _flags:
                    os.environ["XLA_FLAGS"] = (
                        _flags
                        + " --xla_force_host_platform_device_count=8"
                    ).strip()
            from deepvision_tpu.startup import init_runtime

            init_runtime()
            print(json.dumps(_in_process[_mode]()))
        else:
            main()
    finally:
        # export on EVERY exit (same contract as train.py --trace): a
        # crashed bench's partial trace is the one worth reading
        if _trace_path:
            _n = get_tracer().export(_trace_path)
            _dropped = get_tracer().dropped_spans
            print(f"# wrote {_n} spans to {_trace_path}"
                  + (f" (RING OVERFLOW: {_dropped} spans dropped — "
                     "the trace is truncated; see the export's "
                     "metadata.trace_dropped_spans)"
                     if _dropped else ""),
                  file=sys.stderr)
        if _spool is not None:
            _spool.close()
