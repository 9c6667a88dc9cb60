#!/usr/bin/env python
"""Inference / demo CLI — the reference's notebook surface as commands.

Replaces the per-model demo notebooks (classification predictions
``ResNet/pytorch/notebooks/ResNet50.ipynb``; box demo
``YOLO/tensorflow/demo_mscoco.ipynb``; pose demo
``Hourglass/tensorflow/demo_hourglass_pose.ipynb``; GAN sampling
``DCGAN/tensorflow/inference.py``; translation + export
``CycleGAN/tensorflow/inference.py``, ``convert.py``) with one CLI:

    predict.py classify -m resnet50 --workdir runs/resnet50 IMG [IMG...]
    predict.py detect   -m yolov3   --workdir runs/yolov3 IMG -o out.png
    predict.py pose     -m hourglass104 --workdir ... IMG -o out.png
    predict.py dcgan    --workdir runs/dcgan -o samples.png
    predict.py cyclegan --workdir runs/cyclegan IMG -o out.png
    predict.py export   -m resnet50 --workdir ... -o resnet50.stablehlo

Checkpoints come from the Trainer/fit_gan Orbax workdirs; with no
checkpoint present the model runs freshly initialized (still useful for
pipeline smoke tests) and says so.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np


# ----------------------------------------------------------- image io


def load_image(path: str, size: int, *, scale: str) -> np.ndarray:
    """JPEG/PNG → (1, size, size, 3) f32;
    scale: 'imagenet' | 'torch' | 'unit' | 'tanh'."""
    import tensorflow as tf

    tf.config.set_visible_devices([], "GPU")
    data = tf.io.read_file(path)
    img = tf.io.decode_image(data, channels=3, expand_animations=False)
    img = tf.image.resize(tf.cast(img, tf.float32), [size, size])
    img = img.numpy()
    if scale == "imagenet":
        from deepvision_tpu.ops.normalize import IMAGENET_CHANNEL_MEANS

        img = img - np.asarray(IMAGENET_CHANNEL_MEANS, np.float32)
    elif scale == "torch":  # torchvision mean/std (PT-lineage configs)
        from deepvision_tpu.ops.normalize import (
            TORCH_CHANNEL_MEANS,
            TORCH_CHANNEL_STDS,
        )

        img = (img / 255.0 - np.asarray(TORCH_CHANNEL_MEANS, np.float32)) \
            / np.asarray(TORCH_CHANNEL_STDS, np.float32)
    elif scale == "unit":  # [0,1] (the MNIST-family loaders)
        img = img / 255.0
    else:
        img = img / 127.5 - 1.0
    return img[None]


def save_image(path: str, img: np.ndarray) -> None:
    """(H, W, C) array in [-1,1] or [0,255] → PNG."""
    import tensorflow as tf

    if img.dtype != np.uint8:
        if img.min() < 0 or img.max() <= 1.5:  # tanh range
            img = (img + 1.0) * 127.5
        img = np.clip(img, 0, 255).astype(np.uint8)
    if img.shape[-1] == 1:
        img = np.repeat(img, 3, axis=-1)
    tf.io.write_file(path, tf.io.encode_png(tf.constant(img)))
    print(f"wrote {path}")


def draw_box(img: np.ndarray, x1, y1, x2, y2, color, thickness=2):
    """In-place rectangle on a (H, W, 3) uint8 array."""
    h, w = img.shape[:2]
    x1, x2 = sorted((int(np.clip(x1, 0, w - 1)), int(np.clip(x2, 0, w - 1))))
    y1, y2 = sorted((int(np.clip(y1, 0, h - 1)), int(np.clip(y2, 0, h - 1))))
    t = thickness
    img[y1:y1 + t, x1:x2 + 1] = color
    img[max(y2 - t, 0):y2 + 1, x1:x2 + 1] = color
    img[y1:y2 + 1, x1:x1 + t] = color
    img[y1:y2 + 1, max(x2 - t, 0):x2 + 1] = color


def draw_dot(img: np.ndarray, x, y, color, radius=3):
    h, w = img.shape[:2]
    x, y = int(x), int(y)
    img[max(y - radius, 0):y + radius + 1,
        max(x - radius, 0):x + radius + 1] = color


_PALETTE = [(255, 64, 64), (64, 255, 64), (64, 64, 255), (255, 255, 64),
            (255, 64, 255), (64, 255, 255), (255, 160, 64), (160, 64, 255)]


# ------------------------------------------------------ model loading
# Restore + per-task decode live in deepvision_tpu/serve/models.py so
# this one-shot CLI and the batched serving engine (serve.py) share ONE
# code path; the names below are kept as thin delegates.


def load_state(model_name: str, workdir: str | None, sample, epoch=None,
               **model_kw):
    """Delegates to ``serve.models.restore_state`` (the shared
    CLI/server restore path). ``epoch``: a specific saved epoch to
    restore (default latest)."""
    from deepvision_tpu.serve.models import restore_state

    return restore_state(model_name, workdir, sample, epoch, **model_kw)


def _model_geometry(model_name: str) -> tuple[int, int]:
    from deepvision_tpu.serve.models import model_geometry

    return model_geometry(model_name)


def _apply(state, images):
    """Raw eval-mode forward on a restored state — still the building
    block for evaluate.py's metric loops and the converter tests (the
    task-decoded paths go through serve.models instead)."""
    variables = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    return state.apply_fn(variables, images, train=False)


# --------------------------------------------------------- subcommands


def cmd_classify(args):
    from deepvision_tpu.data.metadata import imagenet_label_name
    from deepvision_tpu.serve.models import (
        input_scale,
        load_served,
        model_geometry,
    )

    size, channels = model_geometry(args.model)
    scale = input_scale(args.model)
    imgs = [load_image(p, size, scale=scale) for p in args.images]
    if channels == 1:  # grayscale nets (lenet5)
        imgs = [img.mean(axis=-1, keepdims=True) for img in imgs]
    served = load_served(args.model, args.workdir, task="classify",
                         num_classes=args.num_classes, top_k=args.top)
    for path, img in zip(args.images, imgs):
        res = served.postprocess(served.run(img), 0)
        print(f"{path}:")
        for cls, prob in zip(res["classes"], res["probs"]):
            name = (imagenet_label_name(cls)
                    if args.num_classes == 1000 else str(cls))
            print(f"  {prob:6.2%}  {name}")


def cmd_detect(args):
    from deepvision_tpu.data.metadata import class_names
    from deepvision_tpu.serve.models import load_served

    names = class_names(args.names)
    img = load_image(args.images[0], args.size, scale="tanh")
    served = load_served(args.model, args.workdir, task="detect",
                         input_size=args.size, num_classes=len(names),
                         score_thresh=args.score)
    det = served.postprocess(served.run(img), 0)
    canvas = np.clip((img[0] + 1) * 127.5, 0, 255).astype(np.uint8)
    kept = 0
    for box, score, cls in zip(det["boxes"], det["scores"],
                               det["classes"]):
        x1, y1, x2, y2 = (np.asarray(box) * args.size).tolist()
        color = _PALETTE[int(cls) % len(_PALETTE)]
        draw_box(canvas, x1, y1, x2, y2, color)
        print(f"  {names[int(cls)]}: {score:.2f} at "
              f"({x1:.0f},{y1:.0f})-({x2:.0f},{y2:.0f})")
        kept += 1
    print(f"{kept} detections ≥ {args.score}")
    save_image(args.output, canvas)


def cmd_pose(args):
    from deepvision_tpu.serve.models import load_served

    img = load_image(args.images[0], args.size, scale="tanh")
    served = load_served(args.model, args.workdir, task="pose",
                         input_size=args.size, num_heatmaps=16)
    res = served.postprocess(served.run(img), 0)
    canvas = np.clip((img[0] + 1) * 127.5, 0, 255).astype(np.uint8)
    for j, (x, y, conf) in enumerate(res["joints"]):
        if conf <= args.score:
            continue
        draw_dot(canvas, x * args.size, y * args.size,
                 _PALETTE[j % len(_PALETTE)])
        print(f"  joint {j}: ({x:.3f}, {y:.3f}) conf {conf:.2f}")
    save_image(args.output, canvas)


def cmd_dcgan(args):
    import jax

    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.checkpoint import CheckpointManager
    from deepvision_tpu.train.gan import create_dcgan_state, dcgan_sample

    state = create_dcgan_state(
        get_model("dcgan_generator"), get_model("dcgan_discriminator")
    )
    ckpt = Path(f"{args.workdir}/ckpt")
    if ckpt.exists():
        mgr = CheckpointManager(ckpt)
        if mgr.latest_epoch() is not None:
            state, meta = mgr.restore_inference(state)
            print(f"restored epoch {meta['epoch']}")
        mgr.close()
    n = args.n
    samples = np.asarray(dcgan_sample(state, jax.random.key(args.seed), n))
    side = int(np.ceil(np.sqrt(n)))
    grid = np.full((side * 28, side * 28, 1), -1.0, np.float32)
    for i in range(n):
        r, c = divmod(i, side)
        grid[r * 28:(r + 1) * 28, c * 28:(c + 1) * 28] = samples[i]
    save_image(args.output, grid)


def cmd_cyclegan(args):
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.checkpoint import CheckpointManager
    from deepvision_tpu.train.gan import (
        create_cyclegan_state,
        cyclegan_translate,
    )

    img = load_image(args.images[0], args.size, scale="tanh")
    state = create_cyclegan_state(
        get_model("cyclegan_generator"),
        get_model("cyclegan_discriminator"),
        image_size=args.size,
    )
    ckpt = Path(f"{args.workdir}/ckpt")
    if ckpt.exists():
        mgr = CheckpointManager(ckpt)
        if mgr.latest_epoch() is not None:
            state, meta = mgr.restore_inference(state)
            print(f"restored epoch {meta['epoch']}")
        mgr.close()
    out = np.asarray(cyclegan_translate(state, img, args.direction))[0]
    save_image(args.output, out)


def cmd_curves(args):
    """Re-plot the metric curves stored INSIDE the checkpoint — the
    reference's notebook workflow (loggers dict persisted with the model,
    ref: ResNet/pytorch/train.py:417-428, re-plotted in
    notebooks/ResNet50.ipynb)."""
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    from deepvision_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(f"{args.workdir}/ckpt")
    epoch = mgr.latest_epoch()
    if epoch is None:
        sys.exit(f"no checkpoints under {args.workdir}/ckpt")
    # read only the JSON meta (loggers live there, not in the state)
    meta = mgr.restore_meta(epoch)
    mgr.close()
    loggers = meta["loggers"]
    if loggers is None or not loggers.data:
        sys.exit("checkpoint has no logged metrics")
    metrics = sorted(loggers.data)
    cols = 2
    rows = (len(metrics) + cols - 1) // cols
    fig, axes = plt.subplots(rows, cols, figsize=(10, 3 * rows),
                             squeeze=False)
    for ax, name in zip(axes.flat, metrics):
        series = loggers.data[name]
        ax.plot(series["epochs"], series["value"])
        ax.set_title(name)
        ax.set_xlabel("epoch")
        ax.grid(alpha=0.3)
    for ax in axes.flat[len(metrics):]:
        ax.axis("off")
    fig.tight_layout()
    fig.savefig(args.output, dpi=120)
    print(f"wrote {args.output} ({len(metrics)} curves, "
          f"epoch {epoch})")


def cmd_export(args):
    from deepvision_tpu.export import export_forward, save_exported

    size, channels = _model_geometry(args.model)
    if getattr(args, "size", None):
        size = args.size
    sample = np.zeros((1, size, size, channels), np.float32)
    state = load_state(args.model, args.workdir, sample,
                       num_classes=args.num_classes)
    variables = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    data = export_forward(state.apply_fn, variables, sample)
    out = args.output or f"{args.model}.stablehlo"
    save_exported(out, data)
    print(f"exported {len(data)/1e6:.1f} MB StableHLO artifact to {out}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp, model=None, images=True, output=None):
        if model:
            sp.add_argument("-m", "--model", default=model)
        sp.add_argument("--workdir", default=None)
        if images:
            sp.add_argument("images", nargs="+")
        if output:
            sp.add_argument("-o", "--output", default=output)

    sp = sub.add_parser("classify")
    common(sp, model="resnet50")
    sp.add_argument("--top", type=int, default=5)
    sp.add_argument("--num-classes", type=int, default=1000)
    sp.set_defaults(fn=cmd_classify)

    sp = sub.add_parser("detect")
    common(sp, model="yolov3", output="detections.png")
    sp.add_argument("--names", default="voc", choices=["voc", "mscoco"])
    sp.add_argument("--size", type=int, default=416)
    sp.add_argument("--score", type=float, default=0.5)
    sp.set_defaults(fn=cmd_detect)

    sp = sub.add_parser("pose")
    common(sp, model="hourglass104", output="pose.png")
    sp.add_argument("--size", type=int, default=256)
    sp.add_argument("--score", type=float, default=0.1)
    sp.set_defaults(fn=cmd_pose)

    sp = sub.add_parser("dcgan")
    common(sp, images=False, output="samples.png")
    sp.add_argument("-n", type=int, default=16)
    sp.add_argument("--seed", type=int, default=0)
    sp.set_defaults(fn=cmd_dcgan)

    sp = sub.add_parser("cyclegan")
    common(sp, output="translated.png")
    sp.add_argument("--direction", default="a2b", choices=["a2b", "b2a"])
    sp.add_argument("--size", type=int, default=256)
    sp.set_defaults(fn=cmd_cyclegan)

    sp = sub.add_parser("curves")
    sp.add_argument("--workdir", required=True)
    sp.add_argument("-o", "--output", default="curves.png")
    sp.set_defaults(fn=cmd_curves)

    sp = sub.add_parser("export")
    common(sp, model="resnet50", images=False)
    sp.add_argument("-o", "--output", default=None)
    sp.add_argument("--num-classes", type=int, default=1000)
    sp.add_argument("--size", type=int, default=None,
                    help="override the config input size (must match "
                         "training, e.g. rehearsal --input-size runs)")
    sp.set_defaults(fn=cmd_export)

    args = p.parse_args(argv)
    if args.fn is not cmd_curves:  # curves only re-plots stored metrics
        from deepvision_tpu.startup import init_runtime

        init_runtime()
    args.fn(args)


if __name__ == "__main__":
    main()
