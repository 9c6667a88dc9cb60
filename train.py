#!/usr/bin/env python
"""Training CLI — surface parity with the reference:
``python train.py -m resnet50 [-c CKPT_EPOCH]``
(ref: ResNet/pytorch/train.py:541-562).

Extras over the reference:
- ``--data-dir`` points at TFRecords/idx files; with no data dir the run
  uses the synthetic dataset so every config smoke-trains hermetically
  (generalizing the reference's commented-out synthetic path,
  ref: CycleGAN/tensorflow/train.py:338-342).
- ``--epochs`` / ``--batch-size`` / ``--precision`` overrides.
"""

from __future__ import annotations

import argparse

import numpy as np


def parse_args():
    from deepvision_tpu.train.configs import TRAINING_CONFIG

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--model", required=True,
                   choices=sorted(TRAINING_CONFIG))
    p.add_argument("-c", "--checkpoint", type=int, default=None,
                   help="epoch to resume from (default: latest if present)")
    p.add_argument("--resume", action="store_true",
                   help="resume from latest checkpoint")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--workdir", default="runs")
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--num-classes", type=int, default=None,
                   help="override the config's class count (synthetic "
                        "task-metric gates train with few classes)")
    p.add_argument("--lr", type=float, default=None,
                   help="override the config's base learning rate")
    p.add_argument("--input-size", type=int, default=None,
                   help="override the config's train-time crop size "
                        "(small-input smoke runs, launcher tests)")
    p.add_argument("--num-joints", type=int, default=None,
                   help="override the pose configs' joint count (the "
                        "synthetic set is fully learnable at 3 joints — "
                        "one per color channel)")
    p.add_argument("--precision", default=None,
                   choices=["bf16", "bf16_scaled", "f32"],
                   help="numerics policy (core/precision.py): bf16 "
                        "activations/gradients over f32 master weights, "
                        "bf16_scaled adds dynamic loss scaling, f32 is "
                        "the parity/fallback mode. Default: the model "
                        "config's explicit 'precision' declaration — "
                        "the config table is the source of truth, this "
                        "flag the only override")
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. 'cpu' for smoke "
                        "runs); default: whatever JAX finds, named in "
                        "the [device] start-up line")
    p.add_argument("--raw", dest="use_raw", action="store_true",
                   default=None,
                   help="require the pre-decoded raw-frame fast path "
                        "(data/builders/raw_crops.py); error if absent")
    p.add_argument("--no-raw", dest="use_raw", action="store_false",
                   help="read JPEG records even if raw-frame shards exist")
    p.add_argument("--synthetic-size", type=int, default=2048,
                   help="synthetic dataset size when no --data-dir")
    p.add_argument("--steps-per-epoch", type=int, default=None,
                   help="override train steps per epoch (subset runs; "
                        "the ImageNet reader otherwise assumes the full "
                        "1.28M-image epoch)")
    p.add_argument("--output-bucket", default=None,
                   help="GCS bucket to publish the final checkpoint to "
                        "(ref: Hourglass/tensorflow/main.py:50-65)")
    p.add_argument("--output-dir", default=None,
                   help="GCS object prefix within --output-bucket")
    p.add_argument("--check-numerics", action="store_true",
                   help="run the train step under checkify float checks "
                        "(NaN/Inf raise with the failing op; ~2x slower)")
    p.add_argument("--zero1", "--shard-weight-update", dest="zero1",
                   action="store_true", default=None,
                   help="ZeRO-1 cross-replica weight-update sharding "
                        "(arXiv:2004.13336): grads reduce-scattered, "
                        "optimizer state sharded over the data axis, "
                        "params all-gathered — per the "
                        "[[shardcheck.rule]] table (core/sharding.py); "
                        "frees ~(1-1/N) of optimizer memory per chip, "
                        "numerics bit-comparable. train_dist.py turns "
                        "this on by default on multi-host launches")
    p.add_argument("--no-zero1", dest="zero1", action="store_false",
                   help="force the replicated weight update (opt out of "
                        "train_dist.py's multi-host ZeRO-1 default)")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="overlap per-epoch Orbax saves with training "
                        "(save() returns after staging to host)")
    p.add_argument("--keep-best", action="store_true",
                   help="retain the best checkpoints by the plateau "
                        "metric instead of the most recent (the "
                        "reference's save-on-new-best, "
                        "ref: YOLO/tensorflow/train.py:243-257)")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   help="seconds without a completed step before the "
                        "stall watchdog fires (0 = off) — detects "
                        "wedged device/runtime RPCs that block the "
                        "step loop in a C call")
    p.add_argument("--stall-abort", action="store_true",
                   help="on stall, exit 75 (EX_TEMPFAIL) so a "
                        "supervisor restarts into --resume instead of "
                        "hanging forever")
    p.add_argument("--rss-limit-gb", type=float, default=0.0,
                   help="self-preempt (mid-epoch save + exit 143) when "
                        "host RSS crosses this many GB (0 = off); a "
                        "supervisor relaunches into --resume with a "
                        "fresh process")
    p.add_argument("--label-smooth", type=float, default=0.0,
                   help="one-sided label smoothing on the DCGAN "
                        "discriminator's real targets (Salimans et al. "
                        "2016); 0 = reference-parity plain BCE")
    p.add_argument("--recover", action="store_true",
                   help="self-healing mode (resilience/): the NaN/Inf "
                        "tripwire rolls back to the last verified "
                        "checkpoint and skips the offending batch "
                        "window (implies --check-numerics), transient "
                        "data reads retry with backoff, and resume "
                        "quarantines corrupt checkpoints and falls "
                        "back to the newest verified epoch")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="consecutive NaN rollbacks before --recover "
                        "aborts anyway (a persistent divergence must "
                        "still fail loudly)")
    p.add_argument("--lr-rewarm", type=float, default=None,
                   help="multiply the optimizer lr_scale by this "
                        "factor on every rollback (e.g. 0.5) — the "
                        "classic post-blow-up re-warm; default: keep "
                        "the LR")
    p.add_argument("--faults", default=None,
                   help="deterministic fault schedule for chaos drills "
                        "(resilience/faults.py grammar, e.g. "
                        "'nan@14,ckpt@1,io@8x2'); pair with --recover "
                        "to test self-healing, omit it to verify the "
                        "fail-fast paths")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for probabilistic (~) fault specs")
    p.add_argument("--sentinel", action="store_true",
                   help="silent-failure defense (resilience/sentinel"
                        ".py): cheap numeric invariants (update/param "
                        "norms + loss) computed INSIDE the compiled "
                        "step and z-scored on the drain cadence; under "
                        "a cluster (train_dist.py --supervise) adds "
                        "the cross-host state-agreement audit, and "
                        "every checkpoint manifest gains the save-time "
                        "state fingerprint (audited checkpoints)")
    p.add_argument("--audit-every", type=int, default=16,
                   help="run-step cadence of the cross-host state "
                        "fingerprint audit (and the worst-case SDC "
                        "detection latency, in steps); requires "
                        "--sentinel")
    p.add_argument("--sentinel-z", type=float, default=8.0,
                   help="z-score threshold of the sentinel EWMA "
                        "anomaly detector (trips feed the --recover "
                        "rollback, or fail fast without it)")
    p.add_argument("--sentinel-warmup", type=int, default=16,
                   help="observations per sentinel series before the "
                        "z-test arms (a cold variance estimate trips "
                        "on everything)")
    p.add_argument("--no-ckpt-integrity", action="store_true",
                   help="skip the per-save checksum manifest (one "
                        "SHA-256 pass over each committed checkpoint) "
                        "— trades a verified --recover resume for "
                        "save-time seconds on multi-GB states; "
                        "manifest-less epochs restore unverified")
    p.add_argument("--data-echo", type=int, default=1,
                   help="optimizer steps per transferred batch (data "
                        "echoing, arXiv:1907.05550) — multiplies step "
                        "throughput when the input pipeline or H2D "
                        "link, not the chip, is the bottleneck")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="enable obs span tracing (epoch/step/fetch/"
                        "eval/checkpoint + the feed producer's "
                        "host_next/shard) and export a Chrome-trace "
                        "JSON here on exit (chrome://tracing / "
                        "Perfetto; summarize with "
                        "tools/trace_summary.py)")
    p.add_argument("--profile-steps", default=None, metavar="A:B",
                   help="capture a jax.profiler trace over global "
                        "steps A..B (transferred-batch indices, "
                        "0-based) — a bounded window instead of "
                        "gigabytes of whole-run XPlane")
    p.add_argument("--profile-dir", default=None,
                   help="where --profile-steps writes the profiler "
                        "trace (default: WORKDIR/MODEL/profile)")
    p.add_argument("--device-aug", action="store_true",
                   help="split input pipeline (data/device_aug.py): the "
                        "host stops at decode+resize and ships uint8; "
                        "crop/flip/jitter/normalize run INSIDE the "
                        "compiled step, keyed through KeySeq so "
                        "preemption/chaos bit-determinism holds. "
                        "Record-backed runs only (--data-dir imagenet/"
                        "detection/pose/cyclegan)")
    p.add_argument("--mixup", type=float, default=0.0, metavar="ALPHA",
                   help="device-side mixup (Zhang et al. 2018) with "
                        "Beta(ALPHA, ALPHA) mixing, fused into the step "
                        "(classification configs, requires "
                        "--device-aug); 0 = off")
    p.add_argument("--loader-workers", type=int, default=1,
                   help="spread the host decode stage over N spawned "
                        "processes (data/loader.py; deterministic "
                        "round-robin merge over disjoint file shards) — "
                        "the multi-core answer to a decode-bound host; "
                        "ImageNet record runs only")
    p.add_argument("--max-worker-restarts", type=int, default=2,
                   help="bounded self-healing for a dead loader decode "
                        "worker: respawn it at its shard position "
                        "(merge order preserved, counted as "
                        "loader_worker_restarts) up to this many "
                        "CONSECUTIVE deaths per worker, then fail "
                        "fast; 0 = fail on the first death")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="device batches the async feed keeps in flight "
                        "ahead of the step (data/prefetch.py); 1 = "
                        "classic double buffering, larger values ride "
                        "out host-pipeline jitter at the cost of one "
                        "staged batch of host+HBM memory each")
    return p.parse_args()


def main():
    args = parse_args()

    import jax

    from deepvision_tpu.startup import init_runtime

    init_runtime(args.platform)

    from deepvision_tpu.core import create_mesh
    from deepvision_tpu.data.mnist import batches, load_mnist_idx, synthetic_mnist
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.configs import get_config
    from deepvision_tpu.train.trainer import Trainer

    cfg = get_config(args.model)
    if args.batch_size:
        cfg["batch_size"] = args.batch_size
    if args.num_classes:
        cfg["num_classes"] = args.num_classes
    if args.lr:
        cfg["optimizer_params"]["lr"] = args.lr
    if args.num_joints and "num_heatmaps" in cfg:
        cfg["num_heatmaps"] = args.num_joints
    if args.input_size:
        cfg["input_size"] = args.input_size
    from deepvision_tpu.core.precision import get_policy

    # get_policy validates config-sourced names (argparse choices only
    # cover the CLI flag) and normalizes aliases like "bfloat16".
    # Resolution order: CLI override > the config's EXPLICIT declaration
    # (every shipped entry carries one — train/configs.py is the source
    # of truth, so the CLI docs and the table can no longer disagree).
    policy = get_policy(args.precision or cfg["precision"])
    cfg["precision"] = policy.name  # Trainer builds the same policy
    dtype = policy.compute_dtype
    if args.use_raw is not None and not (
            args.data_dir and cfg["dataset"] == "imagenet"):
        raise SystemExit(
            "--raw/--no-raw only applies to --data-dir ImageNet configs "
            f"(this run: dataset={cfg['dataset']!r}, "
            f"data_dir={args.data_dir!r})"
        )
    if args.label_smooth and cfg["dataset"] != "gan_mnist":
        raise SystemExit(
            "--label-smooth only applies to the DCGAN config "
            f"(this run: {args.model!r})")
    if not 0.0 <= args.label_smooth < 1.0:
        raise SystemExit(
            f"--label-smooth must be in [0, 1), got {args.label_smooth}")
    if args.stall_timeout < 0:
        raise SystemExit(
            f"--stall-timeout must be >= 0, got {args.stall_timeout}")
    if args.prefetch_depth < 1:
        raise SystemExit(
            f"--prefetch-depth must be >= 1, got {args.prefetch_depth}")
    if args.lr_rewarm is not None and not args.recover:
        raise SystemExit("--lr-rewarm only applies with --recover "
                         "(it scales the LR on each rollback)")
    if args.loader_workers < 1:
        raise SystemExit(
            f"--loader-workers must be >= 1, got {args.loader_workers}")
    if args.max_worker_restarts < 0:
        raise SystemExit(f"--max-worker-restarts must be >= 0, got "
                         f"{args.max_worker_restarts}")
    if args.loader_workers > 1 and not (
            args.data_dir and cfg["dataset"] == "imagenet"):
        raise SystemExit(
            "--loader-workers parallelizes the record decode stage — "
            "--data-dir ImageNet configs only (this run: "
            f"dataset={cfg['dataset']!r}, data_dir={args.data_dir!r})")
    if args.device_aug and (
            not args.data_dir
            or cfg["dataset"] not in ("imagenet", "detection", "pose",
                                      "gan_unpaired")):
        raise SystemExit(
            "--device-aug splits a record-backed host pipeline — "
            "--data-dir imagenet/detection/pose/cyclegan configs only "
            f"(this run: dataset={cfg['dataset']!r}, "
            f"data_dir={args.data_dir!r})")
    if args.mixup and not (args.device_aug
                           and cfg["dataset"] == "imagenet"):
        raise SystemExit(
            "--mixup is a device-side classification augmentation; it "
            "requires --device-aug on a --data-dir ImageNet config "
            f"(this run: {args.model!r})")
    if args.mixup < 0:
        raise SystemExit(f"--mixup must be >= 0, got {args.mixup}")
    _maybe_enable_trace(args)
    # recovery/injector built BEFORE the data factories: the loader's
    # worker_kill chaos site and bounded respawn hook into the ImageNet
    # reader construction below
    recovery = None
    if args.recover:
        from deepvision_tpu.resilience import RecoveryPolicy

        recovery = RecoveryPolicy(max_rollbacks=args.max_rollbacks,
                                  lr_rewarm=args.lr_rewarm)
    injector = None
    if args.faults:
        import os as _os

        from deepvision_tpu.resilience import FaultInjector

        # the ':hostH'-targeted sdc sites key on the ORIGINAL cluster
        # host id (stable across elastic relaunches); supervisor
        # replays run quiesced so the replayed window is ground truth
        orig_host = _os.environ.get("DVTPU_CLUSTER_ORIG_HOST")
        injector = FaultInjector(
            args.faults, seed=args.fault_seed,
            host=int(orig_host) if orig_host is not None else None,
            sdc_quiesce=bool(_os.environ.get("DVTPU_SDC_QUIESCE")))
        print(f"fault injection armed: {args.faults!r}", flush=True)
    sentinel = None
    if args.sentinel:
        import os as _os

        from deepvision_tpu.resilience.sentinel import SentinelMonitor

        replay = _os.environ.get("DVTPU_SENTINEL_REPLAY")
        sentinel = SentinelMonitor(
            z_threshold=args.sentinel_z, warmup=args.sentinel_warmup,
            audit_every=args.audit_every,
            replay_until=int(replay) if replay else None)
        print("[sentinel] armed: in-graph invariants + EWMA z-score "
              f"(z={args.sentinel_z:g}, warmup={args.sentinel_warmup})"
              f", state audits every {args.audit_every} steps"
              + (f"; REPLAY mode through run step {replay}"
                 if replay else ""), flush=True)
    if cfg["dataset"].startswith("gan"):
        if args.recover or args.faults or args.sentinel:
            raise SystemExit(
                "--recover/--faults/--sentinel ride the Trainer "
                "rollback/drain loop; the GAN fit_gan path has no "
                f"hook yet (this run: {args.model!r})")
        if args.profile_steps or args.profile_dir:
            raise SystemExit(
                "--profile-steps/--profile-dir ride the Trainer step "
                "counter; the GAN fit_gan path has no profiler hook "
                f"yet (this run: {args.model!r}; --trace works)")
        run_gan(args, cfg, policy)
        return
    if cfg["dataset"] == "pose":
        model = get_model(args.model, dtype=dtype,
                          num_heatmaps=cfg["num_heatmaps"],
                          **cfg.get("model_kwargs", {}))
    elif cfg["dataset"] in ("vlm", "lm"):
        # token models: the registry entry fixes every size (the
        # vocabulary among them), the config the image and text lengths
        model = get_model(args.model, dtype=dtype,
                          **cfg.get("model_kwargs", {}))
    else:
        model = get_model(args.model, dtype=dtype,
                          num_classes=cfg["num_classes"],
                          **cfg.get("model_kwargs", {}))

    size, ch = cfg["input_size"], cfg["channels"]
    step_fns = {}
    if cfg["dataset"] == "pose":
        from deepvision_tpu.train.steps import pose_eval_step, pose_train_step

        step_fns = {"train_step": pose_train_step,
                    "eval_step": pose_eval_step}
        if args.data_dir:
            from deepvision_tpu.data.pose import make_pose_data

            steps = args.steps_per_epoch or 22245 // cfg["batch_size"]  # MPII
            train_data, val_data, steps = make_pose_data(
                args.data_dir, cfg["batch_size"], size,
                steps_per_epoch=steps, device_aug=args.device_aug,
            )
        else:
            from deepvision_tpu.data.pose import (
                synthetic_pose,
                synthetic_pose_batches,
            )

            n = args.synthetic_size
            size = min(size, 128)  # keep the synthetic smoke config small
            imgs, kx, ky, v = synthetic_pose(
                n, size=size, num_joints=cfg["num_heatmaps"]
            )
            split = max(cfg["batch_size"], int(n * 0.1))
            train_data = lambda e: synthetic_pose_batches(
                imgs[split:], kx[split:], ky[split:], v[split:],
                cfg["batch_size"], rng=np.random.default_rng(e),
            )
            val_data = lambda: synthetic_pose_batches(
                imgs[:split], kx[:split], ky[:split], v[:split],
                cfg["batch_size"], drop_remainder=False,
            )
            steps = (n - split) // cfg["batch_size"]
        cfg["input_size"] = size
    elif cfg["dataset"] in ("vlm", "lm"):
        from deepvision_tpu.data.padding import iter_array_batches
        from deepvision_tpu.data import synthetic
        from deepvision_tpu.train import steps as token_steps

        if args.data_dir:
            raise SystemExit(
                "the token models train on their seeded sets only "
                "(image-plus-tokens, or tokens alone): there is no record "
                "reader for them yet "
                f"(this run: --data-dir {args.data_dir!r})")
        kind = cfg["dataset"]
        # the config's step family (a dense text model's is "dense_lm");
        # the eval step is the dataset's
        step_fns = {"train_step": getattr(
                        token_steps, f"{cfg.get('steps', kind)}_train_step"),
                    "eval_step": getattr(token_steps, f"{kind}_eval_step")}
        n = args.synthetic_size
        if kind == "vlm":
            imgs, tokens, split = synthetic.synthetic_vlm(
                n, size, cfg["text_len"], model.vocab_size,
                cfg["batch_size"])
            arrays = {"image": imgs, "tokens": tokens}
        else:
            tokens, split = synthetic.synthetic_lm(
                n, cfg["text_len"], model.vocab_size, cfg["batch_size"])
            arrays = {"tokens": tokens}
        train_data = lambda e: iter_array_batches(
            {k: v[split:] for k, v in arrays.items()}, cfg["batch_size"],
            rng=np.random.default_rng(e))
        val_data = lambda: iter_array_batches(
            {k: v[:split] for k, v in arrays.items()}, cfg["batch_size"],
            drop_remainder=False)
        steps = (n - split) // cfg["batch_size"]
    elif cfg["dataset"] == "detection":
        if cfg.get("steps") == "centernet":
            from deepvision_tpu.train.steps import (
                centernet_eval_step as det_eval,
                centernet_train_step as det_train,
            )
        else:
            from deepvision_tpu.train.steps import (
                yolo_eval_step as det_eval,
                yolo_train_step as det_train,
            )

        step_fns = {"train_step": det_train, "eval_step": det_eval}
        if args.data_dir:
            from deepvision_tpu.data.detection import make_detection_data

            steps = args.steps_per_epoch or 2501 // cfg["batch_size"]  # VOC07
            train_data, val_data, steps = make_detection_data(
                args.data_dir, cfg["batch_size"], size,
                steps_per_epoch=steps, device_aug=args.device_aug,
            )
        else:
            from deepvision_tpu.data.detection import (
                synthetic_batches,
                synthetic_detection,
            )

            n = args.synthetic_size
            size = min(size, 128)  # keep the synthetic smoke config small
            imgs, boxes, labels = synthetic_detection(
                n, size=size, num_classes=cfg["num_classes"]
            )
            split = max(cfg["batch_size"], int(n * 0.1))
            train_data = lambda e: synthetic_batches(
                imgs[split:], boxes[split:], labels[split:],
                cfg["batch_size"], rng=np.random.default_rng(e),
                augment=True,
            )
            val_data = lambda: synthetic_batches(
                imgs[:split], boxes[:split], labels[:split],
                cfg["batch_size"], drop_remainder=False,
            )
            steps = (n - split) // cfg["batch_size"]
        cfg["input_size"] = size
    elif args.data_dir and cfg["dataset"] == "imagenet":
        from deepvision_tpu.data.imagenet import make_imagenet_data

        train_data, val_data, steps = make_imagenet_data(
            args.data_dir, cfg["batch_size"], size,
            augment=cfg.get("augment", "tf"),
            use_raw=args.use_raw,
            steps_per_epoch=args.steps_per_epoch,
            device_aug=args.device_aug,
            loader_workers=args.loader_workers,
            max_worker_restarts=args.max_worker_restarts,
            fault_injector=injector,
        )
    elif args.data_dir and cfg["dataset"] == "mnist":
        import os

        tr_i, tr_l = load_mnist_idx(
            os.path.join(args.data_dir, "train-images-idx3-ubyte"),
            os.path.join(args.data_dir, "train-labels-idx1-ubyte"),
        )
        te_i, te_l = load_mnist_idx(
            os.path.join(args.data_dir, "t10k-images-idx3-ubyte"),
            os.path.join(args.data_dir, "t10k-labels-idx1-ubyte"),
        )
        train_data = lambda e: batches(tr_i, tr_l, cfg["batch_size"],
                                       rng=np.random.default_rng(e))
        val_data = lambda: batches(te_i, te_l, cfg["batch_size"],
                                   drop_remainder=False)
        steps = len(tr_l) // cfg["batch_size"]
    else:
        # hermetic synthetic fallback
        n = args.synthetic_size
        if cfg["dataset"] == "mnist":
            imgs, labels = synthetic_mnist(n)
            split = max(cfg["batch_size"], int(n * 0.1))
        else:
            from deepvision_tpu.data.synthetic import (
                synthetic_classification,
            )

            imgs, labels, split = synthetic_classification(
                n, size, ch, cfg["num_classes"], cfg["batch_size"]
            )
        train_data = lambda e: batches(imgs[split:], labels[split:],
                                       cfg["batch_size"],
                                       rng=np.random.default_rng(e))
        val_data = lambda: batches(imgs[:split], labels[:split],
                                   cfg["batch_size"], drop_remainder=False)
        steps = (n - split) // cfg["batch_size"]

    if not step_fns and cfg.get("augment") == "pt":
        # PT-lineage configs ship uint8 crops; the on-device normalization
        # must be the torchvision mean/std, not the TF mean subtraction.
        from functools import partial

        from deepvision_tpu.train.steps import (
            classification_eval_step,
            classification_train_step,
        )

        step_fns = {
            "train_step": partial(classification_train_step,
                                  normalize_kind="torch"),
            "eval_step": partial(classification_eval_step,
                                 normalize_kind="torch"),
        }

    if args.device_aug:
        # device stage of the split pipeline: the host shipped
        # decode-stage uint8 (the make_*_data device_aug flags above);
        # the stochastic ops run INSIDE the compiled step, keyed
        # through the step's KeySeq subkey (bit-deterministic resume).
        # Detection/pose flips transform boxes/keypoints consistently;
        # eval steps stay unwrapped (validation has no augmentation).
        from deepvision_tpu.data.device_aug import (
            MPII_FLIP_PERM,
            DeviceAugment,
            augment_step,
        )
        from deepvision_tpu.data.imagenet import PT_JITTER

        if cfg["dataset"] == "detection":
            aug = DeviceAugment("detection", flip=True)
        elif cfg["dataset"] == "pose":
            aug = DeviceAugment(
                "pose", flip=True,
                # the left/right channel swap is defined by the MPII
                # joint order; reduced-joint synthetic configs have no
                # left/right semantics to swap
                flip_pairs=(MPII_FLIP_PERM
                            if cfg["num_heatmaps"] == 16 else None))
        else:  # imagenet classification
            aug = DeviceAugment(
                "classification", flip=True,
                jitter=(PT_JITTER if cfg.get("augment") == "pt"
                        else 0.0),
                mixup=args.mixup)
        if not step_fns:
            from deepvision_tpu.train.steps import (
                classification_train_step as _cls_train,
            )

            step_fns = {"train_step": _cls_train}
        step_fns["train_step"] = augment_step(step_fns["train_step"],
                                              aug)
        print(f"[device-aug] {aug} fused into the train step",
              flush=True)

    if args.steps_per_epoch:
        steps = args.steps_per_epoch
        if not args.data_dir or cfg["dataset"] == "mnist":
            # the tf.data paths bake the limit into their readers; the
            # in-memory iterators must be truncated here or the LR
            # schedule (built from `steps`) would desynchronize from the
            # actual epoch length
            from itertools import islice

            train_data = (lambda f: lambda e: islice(f(e), steps))(
                train_data)

    if jax.process_count() > 1 and (not args.data_dir
                                    or cfg["dataset"] == "mnist"):
        # In-memory synthetic datasets generate the SAME global batches
        # in every process (seeded rng); core.shard_batch treats its
        # input as the process-LOCAL share, so each process must feed
        # only its disjoint row block — else a 2-process run would
        # silently train on a 2x global batch of duplicated rows. The
        # tf.data --data-dir paths (imagenet/pose/detection) instead
        # file-shard per process inside their make_*_data factories.
        train_data, val_data = (
            _localize_batches(f, jax.process_count(), jax.process_index())
            for f in (train_data, val_data)
        )

    mesh = create_mesh()
    print(f"mesh: {dict(mesh.shape)}", flush=True)
    trainer = Trainer(
        model, cfg, mesh, train_data, val_data,
        workdir=args.workdir, steps_per_epoch=steps,
        check_numerics=args.check_numerics,
        shard_weight_update=bool(args.zero1),
        async_checkpoint=args.async_checkpoint,
        keep_best=args.keep_best, data_echo=args.data_echo,
        prefetch_depth=args.prefetch_depth,
        stall_timeout=args.stall_timeout or None,
        stall_abort=args.stall_abort,
        rss_limit_gb=args.rss_limit_gb or None,
        recovery=recovery, fault_injector=injector,
        sentinel=sentinel,
        ckpt_integrity=not args.no_ckpt_integrity,
        profile_steps=args.profile_steps, profile_dir=args.profile_dir,
        **step_fns,
    )
    # multi-host cluster supervision (train_dist.py --supervise): the
    # launcher exports the coordination dir; attach BEFORE resume() —
    # cluster resumes are lock-free/collective and heartbeats must
    # cover the restore
    from deepvision_tpu.resilience.cluster import ClusterMember

    member = ClusterMember.from_env()
    if member is not None:
        trainer.attach_cluster(member)
        print(f"[cluster] host {member.host}/{member.nhosts} "
              f"coordinating via {member.directory}", flush=True)
    if args.resume or args.checkpoint is not None:
        trainer.resume(args.checkpoint)
        print(f"resumed at epoch {trainer.start_epoch}"
              + (f" step {trainer.start_step}" if trainer.start_step
                 else ""))
    # SIGTERM (TPU-VM / k8s preemption grace signal) -> synchronous
    # mid-epoch checkpoint + exit 143; `--resume` picks it up and
    # continues bit-identically (SURVEY §5.3 — the reference has no
    # preemption story at all)
    trainer.install_preemption_handler()
    from deepvision_tpu.resilience.sentinel import (
        AuditDivergence,
        SentinelTrip,
    )

    try:
        trainer.fit(args.epochs)
    except (SentinelTrip, AuditDivergence) as e:
        # silent-data-corruption verdict: markers are already on the
        # cluster dir (trip / divergence); exit 76 tells a supervisor
        # this was an SDC stop, not a crash or a preemption
        print(f"[sentinel] FATAL: {e}", flush=True)
        raise SystemExit(76) from e
    finally:
        # export on EVERY exit (preemption and crashes included): a
        # truncated run's trace is exactly the one worth reading
        _maybe_export_trace(args)
    if trainer.replay_done:
        # replay-bisection window completed cleanly: the audit files
        # ARE the verdict; nothing to publish, nothing was saved
        print("[sentinel] replay verdict recorded; exiting 0",
              flush=True)
        return
    if trainer.preempted:
        raise SystemExit(143)
    _maybe_publish(args, f"{args.workdir}/{args.model}/ckpt")


def _maybe_enable_trace(args) -> None:
    if not args.trace:
        return
    from deepvision_tpu.obs.trace import get_tracer

    get_tracer().enable()
    print(f"[obs] span tracing on -> {args.trace}", flush=True)


def _maybe_export_trace(args) -> None:
    if not args.trace:
        return
    from deepvision_tpu.obs.trace import get_tracer

    n = get_tracer().export(args.trace)
    print(f"[obs] wrote {n} spans to {args.trace} "
          "(load in chrome://tracing or Perfetto; summarize with "
          "tools/trace_summary.py)", flush=True)


def _localize_batches(data_fn, nproc: int, pid: int):
    """Wrap a batch-iterator factory so every yielded batch is this
    process's row block (rows [pid·b/n, (pid+1)·b/n) of each globally
    identical batch)."""

    def wrapped(*a):
        for batch in data_fn(*a):
            n = next(iter(batch.values())).shape[0]
            if n % nproc:
                raise ValueError(
                    f"batch of {n} rows not divisible by {nproc} processes"
                )
            lb = n // nproc
            yield {k: v[pid * lb:(pid + 1) * lb] for k, v in batch.items()}

    return wrapped


def _maybe_publish(args, ckpt_dir: str):
    if not (args.output_bucket and args.output_dir):
        return
    from pathlib import Path

    from deepvision_tpu.train.publish import publish_to_gcs

    # publish only the newest retained epoch, not the whole manager tree
    root = Path(ckpt_dir)
    epochs = sorted(
        (p for p in root.iterdir() if p.name.isdigit()),
        key=lambda p: int(p.name),
    )
    target = epochs[-1] if epochs else root
    publish_to_gcs(target, args.output_bucket, args.output_dir)


def run_gan(args, cfg, policy):
    """GAN path: two-network state + fit_gan loop (train/gan.py)."""
    dtype = policy.compute_dtype

    from deepvision_tpu.core import create_mesh
    from deepvision_tpu.data.mnist import synthetic_mnist
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.gan import (
        create_cyclegan_state,
        create_dcgan_state,
        cyclegan_train_step,
        dcgan_train_step,
        fit_gan,
    )
    from deepvision_tpu.train.schedules import linear_decay

    mesh = create_mesh()
    bs = cfg["batch_size"]
    epochs = args.epochs or cfg["total_epochs"]
    workdir = f"{args.workdir}/{cfg['name']}"

    if cfg["name"] == "dcgan":
        from deepvision_tpu.data.mnist import load_mnist_idx
        from deepvision_tpu.data.padding import iter_array_batches

        if args.data_dir:
            import os

            imgs, _ = load_mnist_idx(
                os.path.join(args.data_dir, "train-images-idx3-ubyte"),
                os.path.join(args.data_dir, "train-labels-idx1-ubyte"),
                pad_to_32=False,
            )
        else:
            imgs, _ = synthetic_mnist(args.synthetic_size)
            imgs = imgs[:, 2:30, 2:30, :]  # 28² (DCGAN geometry)
        imgs = (imgs * 2.0 - 1.0).astype(np.float32)  # [-1, 1] (ref :26)
        train_data = lambda e: iter_array_batches(
            {"image": imgs}, bs, rng=np.random.default_rng(e)
        )
        state = create_dcgan_state(
            get_model("dcgan_generator", dtype=dtype),
            get_model("dcgan_discriminator", dtype=dtype),
            noise_dim=cfg["noise_dim"],
            lr=cfg["optimizer_params"]["lr"],
            policy=policy,
        )
        step_fn = dcgan_train_step
        if args.label_smooth:
            from functools import partial

            step_fn = partial(dcgan_train_step,
                              label_smooth=args.label_smooth)
    else:  # cyclegan
        size = cfg["input_size"]
        if args.data_dir:
            from deepvision_tpu.data.gan import make_cyclegan_data

            steps = args.steps_per_epoch or 1000 // bs
            train_data = make_cyclegan_data(
                args.data_dir, bs, size, steps_per_epoch=steps,
                device_aug=args.device_aug,
            )
        else:
            from deepvision_tpu.data.gan import synthetic_unpaired
            from deepvision_tpu.data.padding import iter_array_batches

            size = min(size, 64)
            a, b = synthetic_unpaired(args.synthetic_size, size=size)
            steps = len(a) // bs
            train_data = lambda e: iter_array_batches(
                {"a": a, "b": b}, bs, rng=np.random.default_rng(e)
            )
        lr = linear_decay(
            cfg["optimizer_params"]["lr"],
            cfg["total_epochs"] * steps,
            cfg["decay_epochs"] * steps,
        )
        state = create_cyclegan_state(
            get_model("cyclegan_generator", dtype=dtype),
            get_model("cyclegan_discriminator", dtype=dtype),
            image_size=size,
            lr_schedule=lr,
            beta1=cfg["optimizer_params"]["beta1"],
            policy=policy,
        )
        step_fn = cyclegan_train_step
        if args.device_aug:
            # split pipeline, GAN flavor: the host ships the uint8
            # size+30 canvas (data/gan.py device_aug); crop/flip and
            # the [-1,1] scale fuse into the compiled two-phase step
            # (the GAN steps don't call maybe_normalize themselves, so
            # the augment carries normalize="tanh")
            from deepvision_tpu.data.device_aug import (
                DeviceAugment,
                augment_step,
            )

            aug = DeviceAugment("gan", crop=size, flip=True,
                                normalize="tanh")
            step_fn = augment_step(step_fn, aug)
            print(f"[device-aug] {aug} fused into the train step",
                  flush=True)

    print(f"mesh: {dict(mesh.shape)}", flush=True)
    # SIGTERM -> stop at the next epoch boundary with an off-cadence save
    # (same contract as Trainer.install_preemption_handler)
    from deepvision_tpu.train.trainer import (
        StallWatchdog,
        make_preempt_flag,
    )

    preempted = make_preempt_flag()
    # --rss-limit-gb on the GAN path: the epoch-granular preempt poll
    # doubles as the RSS check (fit_gan saves at epoch boundaries, so
    # "stop after this epoch + exit 143 + supervised --resume" is the
    # right granularity here)
    if args.rss_limit_gb:
        from deepvision_tpu.train.trainer import make_rss_limit_flag

        rss_exceeded = make_rss_limit_flag(args.rss_limit_gb)
        sigterm = preempted
        preempted = lambda: sigterm() or rss_exceeded()  # noqa: E731
    watchdog = (StallWatchdog(args.stall_timeout, abort=args.stall_abort)
                if args.stall_timeout else None)
    try:
        fit_gan(
            state, step_fn, train_data, mesh,
            epochs=epochs, workdir=workdir,
            save_every=cfg.get("save_every", 2),
            resume=args.resume or args.checkpoint is not None,
            resume_epoch=args.checkpoint,
            check_numerics=args.check_numerics,
            shard_weight_update=bool(args.zero1),
            async_checkpoint=args.async_checkpoint,
            preempt=preempted,
            watchdog=watchdog,
            prefetch_depth=args.prefetch_depth,
        )
    finally:
        _maybe_export_trace(args)  # same every-exit contract as main()
    if preempted():
        raise SystemExit(143)
    _maybe_publish(args, f"{workdir}/ckpt")


if __name__ == "__main__":
    main()
