"""Plain reference of the ``resnet50`` configuration.

He et al., "Deep Residual Learning for Image Recognition",
arXiv:1512.03385, table 1, 50-layer column: 7x7/2 stem, 3x3/2 max pool,
bottleneck stacks (3, 4, 6, 3) of width 64/128/256/512 with expansion 4,
global average pool, 1000-way dense layer; BatchNorm after every
convolution, ReLU after the addition. Departures, both as the
configuration ships: the stride of a stage's first block sits on its
first 1x1 convolution (the paper's original layout, not torchvision's
v1.5), and the first block of every stage has a projection shortcut.
The stem here is the plain 7x7/2 convolution with padding 3; the
program's space-to-depth stem computes the same function from the same
``[7, 7, 3, 64]`` kernel.

Training follows the configuration's optimiser as the published recipe
states it: softmax cross-entropy averaged over the batch, weight decay
added to the gradient, then momentum SGD. Leaf names are the ones a
parameter table of this network uses (``stage2_block1/conv3/...``); the
driver places them into the program's tree and fails if the two trees
differ in shape.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.reference import plain

BN_EPS = 1e-5
# The weights stand for a network some way into training, not for the
# first step of a run: a residual branch's last BatchNorm scale is
# small (Goyal et al., arXiv:1706.02677, start it at 0), the classifier
# is small, so that the loss starts near ln(classes) and one rounding
# early in the network is not amplified fifty layers deep. Every leaf
# is drawn, none is constant, so none has a gradient of exactly 0.
BN_SCALE = (0.8, 1.2)
LAST_BN_SCALE = (0.1, 0.3)
BIAS_STD = 0.05
FC_STD = 0.01


def _blocks(cfg):
    """(name, width, stride, project) of every bottleneck, in order."""
    out = []
    for i, n in enumerate(cfg["stage_sizes"]):
        width = cfg["num_filters"] * 2 ** i
        for j in range(n):
            out.append((f"stage{i + 1}_block{j + 1}", width,
                        2 if (i > 0 and j == 0) else 1, j == 0))
    return out


def param_shapes(cfg) -> dict:
    """Nested ``{name: shape}`` of every parameter leaf."""
    exp = cfg["bottleneck_expansion"]

    def convbn(kh, cin, cout):
        return {"conv": {"kernel": (kh, kh, cin, cout)},
                "bn": {"scale": (cout,), "bias": (cout,)}}

    shapes = {"stem": convbn(7, cfg["channels"], cfg["num_filters"])}
    cin = cfg["num_filters"]
    for name, width, _stride, project in _blocks(cfg):
        blk = {"conv1": convbn(1, cin, width),
               "conv2": convbn(3, width, width),
               "conv3": convbn(1, width, width * exp)}
        if project:
            blk["proj"] = convbn(1, cin, width * exp)
        shapes[name] = blk
        cin = width * exp
    shapes["fc"] = {"kernel": (cin, cfg["num_classes"]),
                    "bias": (cfg["num_classes"],)}
    return shapes


def make_weights(cfg, key) -> dict:
    """All parameters from ``key`` (``plain.seed_key(seed)``, passed as
    an argument so that one compiled program serves every seed) in one
    traced function (jit it):
    he-normal (fan-out) kernels and the constants above. float32, the
    type the configuration keeps its parameters in."""
    flat = plain.tree_paths(param_shapes(cfg))
    keys = jax.random.split(key, len(flat))
    leaves: dict = {}
    for key, (path, shape) in zip(keys, flat.items()):
        if path == ("fc", "kernel"):
            leaf = FC_STD * jax.random.normal(key, shape, jnp.float32)
        elif path[-1] == "kernel":
            leaf = plain.he_normal_fan_out(key, shape)
        elif path[-1] == "scale":
            lo, hi = LAST_BN_SCALE if path[-3] == "conv3" else BN_SCALE
            leaf = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        else:
            leaf = BIAS_STD * jax.random.normal(key, shape, jnp.float32)
        leaves[path] = leaf
    return plain.nest(leaves)


def make_batch(cfg, key, rows: int) -> dict:
    """One seeded batch: unit-normal float32 images whose rows all
    differ, labels uniform over the classes."""
    k_img, k_lab = jax.random.split(jax.random.fold_in(key, 7919))
    size = cfg["input_size"]
    return {
        "image": jax.random.normal(
            k_img, (rows, size, size, cfg["channels"]), jnp.float32),
        "label": jax.random.randint(
            k_lab, (rows,), 0, cfg["num_classes"], jnp.int32),
    }


def _convbn(p, x, stride, pad, nm, tally, relu=True):
    y = plain.conv(x, p["conv"]["kernel"], stride, pad, nm, tally)
    y = plain.batchnorm_train(y, p["bn"]["scale"], p["bn"]["bias"],
                              BN_EPS, nm)
    return jnp.maximum(y, 0) if relu else y


def _bottleneck(p, x, stride, nm, tally):
    y = _convbn(p["conv1"], x, stride, 0, nm, tally)
    y = _convbn(p["conv2"], y, 1, 1, nm, tally)
    y = _convbn(p["conv3"], y, 1, 0, nm, tally, relu=False)
    if "proj" in p:
        x = _convbn(p["proj"], x, stride, 0, nm, tally, relu=False)
    return jnp.maximum(y + x, 0)


def forward(cfg, params, images, nm: plain.Numerics = plain.HIGHEST,
            tally=None, remat: bool = False):
    """Logits of a training-mode forward (BatchNorm on batch statistics).
    ``remat`` recomputes inside each block on the way back, so that the
    float32 pass at the cell's batch fits beside nothing else on one
    chip; it changes no value."""
    x = images.astype(nm.store)
    x = _convbn(params["stem"], x, 2, 3, nm, tally)
    x = plain.max_pool(x, 3, 2, 1)
    for name, _width, stride, _project in _blocks(cfg):
        block = functools.partial(_bottleneck, stride=stride, nm=nm,
                                  tally=tally)
        if remat:
            block = jax.checkpoint(block)
        x = block(params[name], x)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    return plain.dense(x, params["fc"]["kernel"], params["fc"]["bias"],
                       nm, tally)


def forward_flops_per_image(cfg) -> int:
    """2 x multiply-adds of every convolution and the dense layer of one
    image's forward pass at the configuration's shapes."""
    tally: list = []
    shapes = param_shapes(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    size = cfg["input_size"]
    x = jax.ShapeDtypeStruct((1, size, size, cfg["channels"]), jnp.float32)
    jax.eval_shape(lambda p, i: forward(cfg, p, i, tally=tally), params, x)
    return int(sum(tally))


def train_flops_per_image(cfg) -> int:
    """Forward and backward: three times the forward count (each
    multiply of the forward has two in the backward)."""
    return 3 * forward_flops_per_image(cfg)


def loss(cfg, params, batch, nm=plain.HIGHEST, remat=False):
    logits = forward(cfg, params, batch["image"], nm, remat=remat)
    return plain.softmax_cross_entropy(logits, batch["label"])


def make_step(cfg, nm: plain.Numerics = plain.HIGHEST, remat: bool = True,
              rows: tuple | None = None):
    """See :func:`_make_step`; one jitted function for each distinct
    set of arguments, so that a second call does not compile again."""
    keys = ("stage_sizes", "num_filters", "bottleneck_expansion",
            "input_size", "channels", "num_classes", "optimizer")
    return _make_step(json.dumps({k: cfg[k] for k in keys}, sort_keys=True),
                      nm, remat, rows)


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, nm, remat, rows):
    """One jitted step of the configuration's SGD: (params, momentum,
    batch) -> (params, momentum, loss, the gradient as the momentum
    update gets it, weight decay added). ``rows`` = (start, stop) keeps
    only those rows of the batch: the planted fault "half of the batch
    left out"."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    lr, mom, wd = opt["lr"], opt["momentum"], opt["weight_decay"]

    @jax.jit
    def step(p, trace, b):
        if rows is not None:
            b = jax.tree.map(lambda a: a[rows[0]:rows[1]], b)
        value, g = jax.value_and_grad(
            lambda q: loss(cfg, q, b, nm, remat))(p)
        g = jax.tree.map(lambda gi, pi: gi + wd * pi, g, p)
        trace = jax.tree.map(lambda t, gi: mom * t + gi, trace, g)
        p = jax.tree.map(lambda pi, t: pi - lr * t, p, trace)
        return p, trace, value, g

    return step


def train_steps(cfg, params, batch, n_steps: int,
                nm: plain.Numerics = plain.HIGHEST, remat: bool = True,
                rows: tuple | None = None):
    """``n_steps`` of that step on one batch, from ``params``.
    -> (losses [n], first gradient, parameters after the last step)."""
    step = make_step(cfg, nm, remat, rows)
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    p = params
    for i in range(n_steps):
        p, trace, value, g = step(p, trace, batch)
        losses.append(value)
        if i == 0:
            first = g
    return jnp.stack(losses), first, p
