"""Plain reference of the ``yolov3`` configuration.

Redmon & Farhadi, "YOLOv3: An Incremental Improvement",
arXiv:1804.02767, as ``pjreddie/darknet cfg/yolov3.cfg`` lays it out:
Darknet-53 (3x3 stem of 32, five stride-2 stages of 1/2/8/8/4 residual
blocks, each a 1x1 squeeze to half the width and a 3x3 expand back),
three detection heads at strides 32/16/8 (five alternating 1x1/3x3
convolutions, a 3x3, a linear 1x1 to 3 x (5 + classes) channels), the
coarser head's branch reduced by a 1x1, upsampled 2x (nearest) and
concatenated in front of the next backbone map. Every convolution but
the three outputs is followed by BatchNorm (inference: the stored
statistics, epsilon 1e-5) and leaky ReLU 0.1.

Decoding and suppression as the yolo layer and the usual post-process
do: centre = (sigmoid(t_xy) + cell) / grid, size = exp(t_wh) x anchor,
score = sigmoid(objectness), class = argmax of the class sigmoids;
candidates under the score threshold dropped, greedy class-agnostic
suppression above the IoU threshold in score order, at most
``max_detections``. One stated departure, taken from the configuration
file: ``anchor_norm_px`` (the repo divides the pixel anchors by 416 at
every input size; darknet divides by the network's width).

Leaf names are the ones a layer table of this network uses; the driver
places them into the program's tree and fails if the two trees differ.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import plain

BN_EPS = 1e-5
LEAK = 0.1
STAGE_BLOCKS = (1, 2, 8, 8, 4)
ANCHORS_PX = ((10, 13), (16, 30), (33, 23), (30, 61), (62, 45), (59, 119),
              (116, 90), (156, 198), (373, 326))
# The weights stand for a trained detector, not for an initialisation.
# A trained network's stored BatchNorm statistics are those of its own
# activations; with he-normal kernels and statistics drawn near (0, 1)
# they are not, and 75 leaky-ReLU layers then carry every image to
# nearly the same output (PERF.md, Findings, PR 23): the answers would
# not say which request they belong to. So ``make_weights`` sets the
# stored statistics to what a few seeded images produce, layer by layer
# (``calibrate``). A residual branch's BatchNorm scale is small so that
# 23 additions do not blow the activations up. The three output layers
# are scaled by output channel so that boxes stay near their anchors
# and the objectness bias is set from the same seeded images so that
# ``seeded_candidates_per_image`` of an image's 22,743 candidates clear
# the score threshold, as on a photograph: far under the 512 the
# program's suppression looks at
# (``deepvision_tpu/ops/nms.NMS_CANDIDATE_CAP``, beyond which it is no
# longer the exact greedy one). With a fixed bias the count swung from 8
# to 657 an image between seeds (40 seeds on the CPU, PERF.md, PR 23):
# every seed has to offer the same work and stay under the cap.
BN_SCALE = (0.8, 1.2)
BRANCH_BN_SCALE = (0.2, 0.4)
BN_BIAS_STD = 0.1
CALIBRATION_IMAGES = 4
# standard deviation of the raw outputs: centre offsets, log sizes,
# objectness, classes (times lecun-normal of the output kernel)
OUT_STD = {"xy": 1.0, "wh": 0.25, "objectness": 1.0, "classes": 1.0}


def _conv_shapes(cfg) -> dict:
    """``{path: (k, cin, cout)}`` of every ConvBN, and the outputs."""
    out_ch = 3 * (5 + cfg["num_classes"])
    convs, outs = {}, {}
    convs[("backbone", "stem")] = (3, cfg["channels"], 32)
    width = 32
    maps = []
    for s, blocks in enumerate(STAGE_BLOCKS):
        convs[("backbone", f"down{s}")] = (3, width, width * 2)
        width *= 2
        for b in range(blocks):
            convs[("backbone", f"stage{s}_block{b}", "squeeze")] = (
                1, width, width // 2)
            convs[("backbone", f"stage{s}_block{b}", "expand")] = (
                3, width // 2, width)
        maps.append(width)
    cin = maps[-1]
    for head, f, skip in (("head_large", 512, None),
                          ("head_medium", 256, maps[-2]),
                          ("head_small", 128, maps[-3])):
        if skip is not None:
            lateral = head.replace("head", "lateral")
            convs[(lateral,)] = (1, cin, f)
            cin = f + skip
        for i in range(3):
            convs[(head, f"conv1x1_{i}")] = (1, cin if i == 0 else 2 * f, f)
            convs[(head, f"conv3x3_{i}")] = (3, f, 2 * f)
        outs[(head, "out")] = (1, 2 * f, out_ch)
        cin = f
    return convs, outs


def make_weights(cfg, key) -> dict:
    """``{"params", "batch_stats"}`` from ``key`` (``plain.seed_key(seed)``,
    passed as an argument so that one compiled program serves every
    seed) in one traced function (jit it), float32, the type the
    configuration serves in."""
    convs, outs = _conv_shapes(cfg)
    n_classes = cfg["num_classes"]
    keys = iter(jax.random.split(key, 3 * len(convs) + len(outs) + 1))
    params, stats = {}, {}
    for path, (k, cin, cout) in convs.items():
        lo, hi = BRANCH_BN_SCALE if path[-1] == "expand" else BN_SCALE
        params[path + ("conv", "kernel")] = plain.he_normal_fan_in(
            next(keys), (k, k, cin, cout))
        params[path + ("bn", "scale")] = jax.random.uniform(
            next(keys), (cout,), jnp.float32, lo, hi)
        params[path + ("bn", "bias")] = BN_BIAS_STD * jax.random.normal(
            next(keys), (cout,), jnp.float32)
        stats[path + ("bn", "mean")] = jnp.zeros((cout,), jnp.float32)
        stats[path + ("bn", "var")] = jnp.ones((cout,), jnp.float32)
    std = jnp.concatenate([
        jnp.full((2,), OUT_STD["xy"]), jnp.full((2,), OUT_STD["wh"]),
        jnp.full((1,), OUT_STD["objectness"]),
        jnp.full((n_classes,), OUT_STD["classes"])])
    for path, (k, cin, cout) in outs.items():
        kernel = jax.random.normal(next(keys), (k, k, cin, 3, 5 + n_classes),
                                   jnp.float32) * std / math.sqrt(cin)
        params[path + ("kernel",)] = kernel.reshape(k, k, cin, cout)
        params[path + ("bias",)] = jnp.zeros((cout,), jnp.float32)
    v = {"params": plain.nest(params), "batch_stats": plain.nest(stats)}
    size = cfg["input_size"]
    images = jax.random.uniform(
        next(keys), (CALIBRATION_IMAGES, size, size, cfg["channels"]),
        jnp.float32, -1.0, 1.0)
    stats, objectness = calibrate(cfg, v, images)
    # one objectness bias for the three scales: the logit of the score
    # threshold falls on the k-th largest of the seeded images' logits
    k = min(cfg["seeded_candidates_per_image"] * CALIBRATION_IMAGES,
            objectness.size // 2)
    kth = jax.lax.top_k(objectness, k)[0][-1]
    thr = cfg["score_threshold"]
    shift = math.log(thr / (1.0 - thr)) - kth
    for path in outs:
        bias = params[path + ("bias",)].reshape(3, 5 + n_classes)
        params[path + ("bias",)] = bias.at[:, 4].add(shift).reshape(-1)
    return {"params": plain.nest(params), "batch_stats": stats}


def calibrate(cfg, variables, images):
    """The stored statistics of a network that has seen data like
    ``images``: every BatchNorm's mean and variance of its own input,
    each layer fed by the layers before it normalised the same way.
    -> (statistics, the objectness logits of every candidate of those
    images, flat)."""
    seen: dict = {}
    grids = forward(cfg, variables, images, plain.DEFAULT, seen=seen)
    return plain.nest(seen), jnp.concatenate(
        [y[..., 4].reshape(-1) for y in grids])


def make_images(cfg, seed: int, count: int) -> np.ndarray:
    """``count`` distinct float32 images in [-1, 1), the range the
    served model takes its pixels in, made on the host from ``seed``."""
    rng = np.random.default_rng([int(seed), 608])
    size = cfg["input_size"]
    x = rng.random((count, size, size, cfg["channels"]), dtype=np.float32)
    return x * 2.0 - 1.0


def _convbn(v, path, x, stride, nm, tally, seen=None):
    p = functools.reduce(lambda n, k: n[k], path, v["params"])
    s = functools.reduce(lambda n, k: n[k], path, v["batch_stats"])
    k = p["conv"]["kernel"].shape[0]
    if stride == 2:
        # 'SAME' at stride 2 on an even map: one row and column of
        # zeros at the bottom and right (darknet pads both sides; the
        # configuration ships the XLA convention, stated here)
        x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
        y = plain.conv(x, p["conv"]["kernel"], 2, 0, nm, tally)
    else:
        y = plain.conv(x, p["conv"]["kernel"], 1, k // 2, nm, tally)
    mean, var = s["bn"]["mean"], s["bn"]["var"]
    if seen is not None:        # calibration: this input's own moments
        mean = jnp.mean(y, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
        seen[path + ("bn", "mean")] = mean
        seen[path + ("bn", "var")] = var
    y = plain.batchnorm_eval(y, p["bn"]["scale"], p["bn"]["bias"],
                             mean, var, BN_EPS, nm)
    return jnp.where(y > 0, y, LEAK * y)


def _head(v, name, x, nm, tally, seen=None):
    for i in range(3):
        x = _convbn(v, (name, f"conv1x1_{i}"), x, 1, nm, tally, seen)
        if i < 2:
            x = _convbn(v, (name, f"conv3x3_{i}"), x, 1, nm,
                        tally, seen)
    branch = x
    x = _convbn(v, (name, "conv3x3_2"), x, 1, nm, tally, seen)
    p = v["params"][name]["out"]
    # the output layer is float32 whatever the rest is stored in
    y = plain.conv(x.astype(jnp.float32), p["kernel"], 1, 0,
                   plain.Numerics(nm.name, "float32", nm.operands,
                                  nm.highest), tally)
    return branch, y + p["bias"]


def _upsample2x(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def forward(cfg, variables, images, nm: plain.Numerics = plain.HIGHEST,
            tally=None, seen=None):
    """Raw grids (B, S, S, 3, 5 + classes) at strides 8, 16, 32. With
    ``seen`` (a dict) every BatchNorm normalises by its input's own
    moments and leaves them there: :func:`calibrate`."""
    v = variables
    cb = functools.partial(_convbn, v, nm=nm, tally=tally, seen=seen)
    x = cb(("backbone", "stem"), images.astype(nm.store), 1)
    maps = []
    for s, blocks in enumerate(STAGE_BLOCKS):
        x = cb(("backbone", f"down{s}"), x, 2)
        for b in range(blocks):
            y = cb(("backbone", f"stage{s}_block{b}", "squeeze"), x, 1)
            y = cb(("backbone", f"stage{s}_block{b}", "expand"), y, 1)
            x = x + y
        maps.append(x)
    branch, y_large = _head(v, "head_large", maps[-1], nm, tally, seen)
    x = cb(("lateral_medium",), branch, 1)
    x = jnp.concatenate([_upsample2x(x), maps[-2]], axis=-1)
    branch, y_medium = _head(v, "head_medium", x, nm, tally, seen)
    x = cb(("lateral_small",), branch, 1)
    x = jnp.concatenate([_upsample2x(x), maps[-3]], axis=-1)
    _, y_small = _head(v, "head_small", x, nm, tally, seen)
    split = lambda y: y.reshape(*y.shape[:3], 3, 5 + cfg["num_classes"])
    return split(y_small), split(y_medium), split(y_large)


def decode(cfg, grids):
    """-> corner boxes (B, N, 4), scores (B, N), classes (B, N), over
    the three scales in the order small, medium, large."""
    norm = cfg["anchor_norm_px"]
    anchors = np.asarray(ANCHORS_PX, np.float32) / norm
    boxes, scores, classes = [], [], []
    for y, a in zip(grids, (anchors[0:3], anchors[3:6], anchors[6:9])):
        size = y.shape[1]
        cx, cy = jnp.meshgrid(jnp.arange(size), jnp.arange(size))
        cell = jnp.stack([cx, cy], axis=-1)[:, :, None, :].astype(
            jnp.float32)
        xy = (jax.nn.sigmoid(y[..., 0:2]) + cell) / size
        wh = jnp.exp(y[..., 2:4]) * a
        b = y.shape[0]
        boxes.append(jnp.concatenate([xy - wh / 2, xy + wh / 2],
                                     axis=-1).reshape(b, -1, 4))
        scores.append(jax.nn.sigmoid(y[..., 4]).reshape(b, -1))
        classes.append(jnp.argmax(y[..., 5:], axis=-1).reshape(b, -1))
    return (jnp.concatenate(boxes, 1), jnp.concatenate(scores, 1),
            jnp.concatenate(classes, 1).astype(jnp.int32))


def candidates(cfg, variables, images, nm=plain.HIGHEST):
    """The decoded candidates of ``images`` as numpy arrays."""
    fn = _candidates_fn(json.dumps(
        {k: cfg[k] for k in ("num_classes", "anchor_norm_px")},
        sort_keys=True), nm)
    return tuple(np.asarray(a) for a in fn(variables, images))


@functools.lru_cache(maxsize=None)
def _candidates_fn(cfg_json: str, nm):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda v, x: decode(cfg, forward(cfg, v, x, nm)))


def forward_flops_per_image(cfg) -> int:
    """2 x multiply-adds of every convolution of one image's forward."""
    tally: list = []
    convs, outs = _conv_shapes(cfg)
    f32 = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
    params, stats = {}, {}
    for path, (k, cin, cout) in convs.items():
        params[path + ("conv", "kernel")] = f32((k, k, cin, cout))
        for leaf in ("scale", "bias"):
            params[path + ("bn", leaf)] = f32((cout,))
        for leaf in ("mean", "var"):
            stats[path + ("bn", leaf)] = f32((cout,))
    for path, (k, cin, cout) in outs.items():
        params[path + ("kernel",)] = f32((k, k, cin, cout))
        params[path + ("bias",)] = f32((cout,))
    v = {"params": plain.nest(params), "batch_stats": plain.nest(stats)}
    size = cfg["input_size"]
    jax.eval_shape(lambda v, x: forward(cfg, v, x, tally=tally), v,
                   f32((1, size, size, cfg["channels"])))
    return int(sum(tally))


def iou_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """IoU of corner boxes a (n, 4) against b (m, 4) -> (n, m)."""
    lt = np.maximum(a[:, None, :2], b[None, :, :2])
    rb = np.minimum(a[:, None, 2:], b[None, :, 2:])
    inter = np.prod(np.clip(rb - lt, 0, None), axis=-1)
    area = lambda x: np.prod(np.clip(x[:, 2:] - x[:, :2], 0, None), axis=-1)
    return inter / np.maximum(area(a)[:, None] + area(b)[None] - inter,
                              1e-12)


def suppress(cfg, boxes: np.ndarray, scores: np.ndarray) -> np.ndarray:
    """Greedy class-agnostic NMS of one image -> kept indices, best
    first. Exact: no cap on the candidates."""
    order = np.flatnonzero(scores >= cfg["score_threshold"])
    order = order[np.argsort(-scores[order], kind="stable")]
    kept: list = []
    while order.size and len(kept) < cfg["max_detections"]:
        i = order[0]
        kept.append(int(i))
        iou = iou_matrix(boxes[i:i + 1], boxes[order[1:]])[0]
        order = order[1:][iou <= cfg["iou_threshold"]]
    return np.asarray(kept, np.int64)
