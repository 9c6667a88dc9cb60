#!/usr/bin/env python
"""Offline evaluation CLI: classification top-1/5, detection mAP, pose PCK.

Completes the evaluation surface the reference never shipped (mAP is
explicitly WIP there, ref: YOLO/tensorflow/README.md:28; PCKh is never
reported); the classification subcommand is the exact masked full-set
validation pass runnable against any checkpoint.

    evaluate.py classification -m resnet50 --workdir runs/resnet50 --data-dir /data/imagenet
    evaluate.py detection -m yolov3 --workdir runs/yolov3 --data-dir /data/voc
    evaluate.py pose -m hourglass104 --workdir runs/hourglass104 --data-dir /data/mpii

Without --data-dir both commands run on the synthetic sets (hermetic
smoke — the same data the synthetic trainers use).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def _load(model_name, workdir, sample, **kw):
    import predict

    return predict.load_state(model_name, workdir, sample, **kw)


def _apply(state, images):
    from predict import _apply as apply_fn  # one shared eval-apply impl

    return apply_fn(state, images)


def cmd_classification(args):
    """Exact masked top-1/top-5 over the full validation set (the
    reference's validate pass, ref: ResNet/pytorch/train.py:488-520,
    without its batch-tail drop)."""
    from deepvision_tpu.core import create_mesh, shard_batch
    from deepvision_tpu.core.step import compile_eval_step
    from deepvision_tpu.train.configs import get_config
    from deepvision_tpu.train.steps import classification_eval_step

    cfg = get_config(args.model)
    if args.num_classes:
        cfg["num_classes"] = args.num_classes
    if args.input_size:
        cfg["input_size"] = args.input_size
    size, ch = cfg["input_size"], cfg["channels"]
    bs = args.batch_size

    if args.data_dir and cfg["dataset"] == "imagenet":
        from deepvision_tpu.data.imagenet import make_imagenet_data

        # evaluation must use the config's normalization lineage: a
        # pt-lineage net expects torchvision mean/std inputs, not the TF
        # mean subtraction (same wiring as train.py)
        _, val_data, _ = make_imagenet_data(
            args.data_dir, bs, size, augment=cfg.get("augment", "tf")
        )
        batches = val_data()
    elif args.data_dir and cfg["dataset"] == "mnist":
        import os

        from deepvision_tpu.data.mnist import batches as mk, load_mnist_idx

        te_i, te_l = load_mnist_idx(
            os.path.join(args.data_dir, "t10k-images-idx3-ubyte"),
            os.path.join(args.data_dir, "t10k-labels-idx1-ubyte"),
        )
        batches = mk(te_i, te_l, bs, drop_remainder=False)
    else:
        from deepvision_tpu.data.mnist import batches as mk, synthetic_mnist

        if cfg["dataset"] == "mnist":
            imgs, labels = synthetic_mnist(256)
        else:
            # SAME generator + split as train.py's synthetic fallback:
            # score exactly the held-out slice the training run never
            # saw (pass the run's --synthetic-size and --batch-size).
            # Without --train-batch-size the split is computed with
            # batch_size=1 — an UNDER-approximation of train.py's
            # max(batch, n/10) split, so the scored slice is always a
            # subset of the true held-out set (never leaks training
            # images; at worst scores a few images fewer).
            from deepvision_tpu.data.synthetic import (
                synthetic_classification,
            )

            imgs, labels, split = synthetic_classification(
                args.synthetic_size, size, ch, cfg["num_classes"],
                args.train_batch_size or 1,
            )
            imgs, labels = imgs[:split], labels[:split]
        batches = mk(imgs, labels, bs, drop_remainder=False)

    from deepvision_tpu.train.steps import aggregate_eval_parts

    mesh = create_mesh()
    state = None
    eval_fn = classification_eval_step
    if cfg.get("augment") == "pt":  # uint8 batches need torch stats
        from functools import partial

        eval_fn = partial(classification_eval_step, normalize_kind="torch")
    step = compile_eval_step(eval_fn, mesh)

    def parts():
        nonlocal state
        for batch in batches:
            if state is None:
                state = _load(args.model, args.workdir, batch["image"][:1],
                              epoch=args.epoch,
                              num_classes=cfg["num_classes"])
            yield step(state, shard_batch(mesh, batch))

    metrics, n = aggregate_eval_parts(parts())
    print(json.dumps({
        "metric": "classification_eval", "images": int(n),
        **{k: round(v, 4) for k, v in metrics.items()},
    }))


def cmd_detection(args):
    from deepvision_tpu.data.metadata import class_names
    from deepvision_tpu.eval import evaluate_map
    from deepvision_tpu.ops.iou import xywh_to_corners
    from deepvision_tpu.ops.yolo_postprocess import yolo_postprocess

    names = class_names(args.names)
    if args.num_classes:  # synthetic gates train with few classes
        names = names[: args.num_classes] if (
            args.num_classes <= len(names)
        ) else [f"class{i}" for i in range(args.num_classes)]
    num_classes = len(names)
    size = args.size

    if args.data_dir:
        from deepvision_tpu.data.detection import make_detection_dataset
        from deepvision_tpu.data.padding import iter_tf_batches

        ds = make_detection_dataset(
            f"{args.data_dir}/{args.split}-*", args.batch_size, size,
            is_training=False,
        )
        batches = iter_tf_batches(ds, ("image", "boxes", "label"))
    else:
        from deepvision_tpu.data.detection import (
            synthetic_batches,
            synthetic_detection,
        )

        size = min(size, 128)
        imgs, boxes, labels = synthetic_detection(
            64, size=size, num_classes=num_classes
        )
        batches = synthetic_batches(imgs, boxes, labels, args.batch_size)

    is_centernet = "centernet" in args.model
    state = None
    dets, gts = [], []
    # NMS exactness tripwire (ops/nms.py) — greedy-NMS (YOLO) path only;
    # centernet's peak-NMS has no candidate cap, so the fields stay null
    # rather than reporting a check that never ran
    nms_candidates_max = None if is_centernet else 0
    for batch in batches:
        if state is None:
            state = _load(args.model, args.workdir, batch["image"][:1],
                          epoch=args.epoch, num_classes=num_classes)
        preds = _apply(state, batch["image"])
        if is_centernet:
            # peak-NMS decode of the LAST stack (ops/centernet_decode —
            # the inference path the reference never reached)
            from deepvision_tpu.ops.centernet_decode import decode_centernet

            heat, wh, off = preds[-1]
            d = decode_centernet(heat, wh, off)
            b_boxes = xywh_to_corners(d["boxes"])
            b_scores, b_cls = d["scores"], d["classes"]
            b_valid = d["scores"] >= args.score
        else:
            b_boxes, b_scores, b_cls, b_valid, b_ncand = yolo_postprocess(
                preds, num_classes, score_thresh=args.score
            )
            nms_candidates_max = max(
                nms_candidates_max, int(np.asarray(b_ncand).max())
            )
        b_boxes = np.asarray(b_boxes)
        b_scores, b_cls = np.asarray(b_scores), np.asarray(b_cls)
        b_valid = np.asarray(b_valid).astype(bool)
        for i in range(len(b_boxes)):
            keep = b_valid[i]
            dets.append({
                "boxes": b_boxes[i][keep],
                "scores": b_scores[i][keep],
                "classes": b_cls[i][keep],
            })
            gt_keep = batch["label"][i] >= 0
            gts.append({
                "boxes": np.asarray(
                    xywh_to_corners(batch["boxes"][i][gt_keep])
                ),
                "classes": batch["label"][i][gt_keep],
            })
    out = evaluate_map(dets, gts, num_classes,
                       iou_thresh=args.iou, method=args.ap_method)
    per_class = {
        names[c]: round(float(out["ap"][c]), 4)
        for c in range(num_classes) if np.isfinite(out["ap"][c])
    }
    from deepvision_tpu.ops.nms import NMS_CANDIDATE_CAP as nms_cap

    if nms_candidates_max is not None and nms_candidates_max > nms_cap:
        print(f"# WARNING: {nms_candidates_max} candidates cleared the "
              f"score threshold (> candidate_cap={nms_cap}); greedy-NMS "
              "exactness degraded — raise candidate_cap or score_thresh.",
              file=sys.stderr)
    print(json.dumps({
        "metric": "mAP", "iou": args.iou, "value": round(out["map"], 4),
        "images": len(dets), "per_class": per_class,
        "nms_candidates_max": nms_candidates_max,
        "nms_exact": (None if nms_candidates_max is None
                      else nms_candidates_max <= nms_cap),
    }))


def cmd_pose(args):
    from deepvision_tpu.eval import pck
    from deepvision_tpu.eval.pose import heatmap_argmax_keypoints

    size = args.size
    if args.data_dir:
        from deepvision_tpu.data.padding import iter_tf_batches
        from deepvision_tpu.data.pose import make_pose_dataset

        ds = make_pose_dataset(
            f"{args.data_dir}/{args.split}-*", args.batch_size, size,
            is_training=False,
        )
        batches = iter_tf_batches(ds, ("image", "kx", "ky", "v"))
    else:
        from deepvision_tpu.data.pose import (
            synthetic_pose,
            synthetic_pose_batches,
        )

        size = min(size, 128)
        imgs, kx, ky, v = synthetic_pose(
            32, size=size, num_joints=args.num_joints or 16
        )
        batches = synthetic_pose_batches(imgs, kx, ky, v, args.batch_size)

    state = None
    preds, trues, viss = [], [], []
    for batch in batches:
        if state is None:
            state = _load(args.model, args.workdir, batch["image"][:1],
                          epoch=args.epoch,
                          num_heatmaps=batch["kx"].shape[1])
        heat = np.asarray(_apply(state, batch["image"])[-1])  # last stack
        grid = heat.shape[1]
        preds.append(heatmap_argmax_keypoints(heat) / grid)
        trues.append(np.stack([batch["kx"], batch["ky"]], axis=-1))
        viss.append(batch["v"])
    pred = np.concatenate(preds)
    true = np.concatenate(trues)
    vis = np.concatenate(viss)
    # normalized coords; PCK reference length = the standard head
    # fraction of the (crop-normalized) body: ``--norm`` of the frame
    out = pck(pred, true, vis,
              norm_length=np.full(len(pred), args.norm),
              threshold=args.threshold)
    print(json.dumps({
        "metric": f"PCK@{args.threshold}", "norm": args.norm,
        "value": round(out["pck"], 4),
        "per_joint": [round(float(x), 4) if np.isfinite(x) else None
                      for x in out["per_joint"]],
    }))


def cmd_gan(args):
    """Trained-quality metrics for the GANs on the hermetic synthetic
    sets — a MEASURED gate where the reference only eyeballs samples
    (ref: DCGAN/tensorflow/inference.py:7-33).

    cyclegan: the synthetic domains (data/gan.synthetic_unpaired) are
    related by exact color inversion, so the unpaired-trained generator
    can be scored PAIRED on held-out data: pixel-MSE of G_AB(a) against
    the true mapping -a (and G_BA(b) vs -b), normalized by the
    ZERO-predictor baseline E[a²] (a fresh tanh generator emits ≈0 and
    must score ≈0; the true inversion scores 1).
    score = 1 - mse/mse_baseline.

    dcgan: a classifier is trained on the synthetic reals to ~1.0
    accuracy, then scores generated samples with the Inception-Score
    construction exp(E KL(p(y|x) || p(y))) — confident AND diverse
    samples score high; the held-out-real IS is printed as the ceiling.
    score = IS_generated / IS_real."""
    import jax

    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.checkpoint import CheckpointManager

    out = {"model": args.model}
    if args.model == "cyclegan":
        from deepvision_tpu.data.gan import synthetic_unpaired
        from deepvision_tpu.train.gan import (
            create_cyclegan_state,
            cyclegan_translate,
        )

        state = create_cyclegan_state(
            get_model("cyclegan_generator"),
            get_model("cyclegan_discriminator"),
            image_size=args.size,
        )
        mgr = CheckpointManager(f"{args.workdir}/ckpt")
        state, meta = mgr.restore_inference(state, args.epoch)
        mgr.close()
        # held-out draw: training uses seed=0 (train.run_gan default)
        a, b = synthetic_unpaired(args.n, size=args.size, seed=113)
        fake_b = np.asarray(cyclegan_translate(state, a, "a2b"))
        fake_a = np.asarray(cyclegan_translate(state, b, "b2a"))
        mse_a2b = float(np.mean((fake_b - (-a)) ** 2))
        mse_b2a = float(np.mean((fake_a - (-b)) ** 2))
        base = float(np.mean(a ** 2) + np.mean(b ** 2)) / 2.0
        score = 1.0 - 0.5 * (mse_a2b + mse_b2a) / base
        out.update(
            epoch=meta["epoch"], n=int(len(a)),
            mse_a2b=round(mse_a2b, 5), mse_b2a=round(mse_b2a, 5),
            mse_baseline=round(base, 5), score=round(score, 4),
        )
    elif args.model == "dcgan":
        import optax

        from deepvision_tpu.core import create_mesh, shard_batch
        from deepvision_tpu.core.step import compile_train_step
        from deepvision_tpu.data.mnist import synthetic_mnist
        from deepvision_tpu.train.gan import (
            create_dcgan_state,
            dcgan_sample,
        )
        from deepvision_tpu.train.state import create_train_state
        from deepvision_tpu.train.steps import classification_train_step

        state = create_dcgan_state(
            get_model("dcgan_generator"), get_model("dcgan_discriminator")
        )
        mgr = CheckpointManager(f"{args.workdir}/ckpt")
        state, meta = mgr.restore_inference(state, args.epoch)
        mgr.close()

        # judge classifier: LeNet on the full 32² [-1,1] synthetic reals
        # (LeNet's geometry needs 32²); generated 28² samples are
        # re-embedded at the training crop's offset ([2:30] —
        # train.run_gan dcgan branch) on a background-valued canvas
        imgs, labels = synthetic_mnist(2048, seed=0)
        imgs = (imgs * 2.0 - 1.0).astype(np.float32)
        mesh = create_mesh(1, 1)
        clf = get_model("lenet5", num_classes=10)
        cstate = create_train_state(clf, optax.adam(1e-3), imgs[:1])
        cstep = compile_train_step(classification_train_step, mesh)
        key = jax.random.key(0)
        bs = 64
        for epoch in range(4):
            for i in range(0, 1536, bs):
                db = shard_batch(mesh, {"image": imgs[i:i + bs],
                                        "label": labels[i:i + bs]})
                key, sub = jax.random.split(key)
                cstate, _ = cstep(cstate, db, sub)

        def probs(x):
            logits = clf.apply(
                {"params": cstate.params,
                 "batch_stats": cstate.batch_stats or {}}, x)
            return np.asarray(jax.nn.softmax(logits, axis=-1))

        def inception_score(p):
            marg = p.mean(0, keepdims=True)
            kl = (p * (np.log(p + 1e-10) - np.log(marg + 1e-10))).sum(1)
            return float(np.exp(kl.mean()))

        held = probs(imgs[1536:])  # held-out reals (never seen by clf)
        acc = float((held.argmax(1) == labels[1536:]).mean())
        samples = np.asarray(
            dcgan_sample(state, jax.random.key(7), args.n))
        # -0.8 = the synthetic background mean (0.1) in [-1,1] scale
        canvas = np.full((len(samples), 32, 32, 1), -0.8, np.float32)
        canvas[:, 2:30, 2:30, :] = samples.astype(np.float32)
        gen = probs(canvas)
        is_gen = inception_score(gen)
        is_real = inception_score(held)
        out.update(
            epoch=meta["epoch"], n=int(args.n),
            judge_holdout_acc=round(acc, 4),
            is_generated=round(is_gen, 3), is_real=round(is_real, 3),
            class_coverage=int(len(set(gen.argmax(1)))),
            score=round(is_gen / is_real, 4),
        )
    else:
        raise SystemExit(f"evaluate gan: unknown model {args.model!r}")
    print(json.dumps(out))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("classification")
    sp.add_argument("-m", "--model", default="resnet50")
    sp.add_argument("--workdir", default=None)
    sp.add_argument("--data-dir", default=None)
    sp.add_argument("--batch-size", type=int, default=64)
    sp.add_argument("--num-classes", type=int, default=None,
                    help="override class count (rehearsal/smoke sets)")
    sp.add_argument("--input-size", type=int, default=None,
                    help="override eval crop (must match training)")
    sp.add_argument("--epoch", type=int, default=None,
                    help="saved epoch to score (default latest; with "
                         "--keep-best the best is often not the newest)")
    sp.add_argument("--synthetic-size", type=int, default=2048,
                    help="regenerate the train run's synthetic set "
                         "(pass the SAME value as train.py "
                         "--synthetic-size; defaults match) and score "
                         "its held-out slice")
    sp.add_argument("--train-batch-size", type=int, default=None,
                    help="the training run's batch size (sizes the "
                         "held-out split; default 1 under-approximates "
                         "the split so training images never leak in)")
    sp.set_defaults(fn=cmd_classification)

    sp = sub.add_parser("detection")
    sp.add_argument("-m", "--model", default="yolov3")
    sp.add_argument("--workdir", default=None)
    sp.add_argument("--data-dir", default=None)
    sp.add_argument("--split", default="val")
    sp.add_argument("--names", default="voc", choices=["voc", "mscoco"])
    sp.add_argument("--num-classes", type=int, default=None,
                    help="override class count (synthetic gates)")
    sp.add_argument("--size", type=int, default=416)
    sp.add_argument("--batch-size", type=int, default=16)
    sp.add_argument("--score", type=float, default=0.05)
    sp.add_argument("--iou", type=float, default=0.5)
    sp.add_argument("--ap-method", default="area",
                    choices=["area", "11point"])
    sp.add_argument("--epoch", type=int, default=None,
                    help="saved epoch to score (default latest; with "
                         "--keep-best the best is often not the newest)")
    sp.set_defaults(fn=cmd_detection)

    sp = sub.add_parser("pose")
    sp.add_argument("-m", "--model", default="hourglass104")
    sp.add_argument("--num-joints", type=int, default=None,
                    help="synthetic joint count (match training)")
    sp.add_argument("--workdir", default=None)
    sp.add_argument("--data-dir", default=None)
    sp.add_argument("--split", default="val")
    sp.add_argument("--size", type=int, default=256)
    sp.add_argument("--batch-size", type=int, default=16)
    sp.add_argument("--threshold", type=float, default=0.5)
    sp.add_argument("--norm", type=float, default=0.1,
                    help="PCK reference length as a fraction of the "
                         "normalized crop (0.1 ≈ head fraction)")
    sp.add_argument("--epoch", type=int, default=None,
                    help="saved epoch to score (default latest; with "
                         "--keep-best the best is often not the newest)")
    sp.set_defaults(fn=cmd_pose)

    sp = sub.add_parser("gan")
    sp.add_argument("-m", "--model", default="cyclegan",
                    choices=["cyclegan", "dcgan"])
    sp.add_argument("--workdir", default=None)
    sp.add_argument("--size", type=int, default=64)
    sp.add_argument("--n", type=int, default=256,
                    help="held-out images (cyclegan) / samples (dcgan)")
    sp.add_argument("--epoch", type=int, default=None,
                    help="saved epoch to score (default latest; with "
                         "--keep-best the best is often not the newest)")
    sp.set_defaults(fn=cmd_gan)

    args = p.parse_args(argv)
    from deepvision_tpu.startup import init_runtime

    init_runtime()
    args.fn(args)


if __name__ == "__main__":
    main()
