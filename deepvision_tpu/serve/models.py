"""Served models: one restore + post-process path for CLI and server.

A :class:`ServedModel` wraps everything the engine needs to serve a
registry model or a restored artifact: the pure forward (a jit-able
``(variables, batch) -> outputs`` closure with the task's post-
processing folded INSIDE the traced computation — classify top-k via
``jax.lax.top_k``, YOLO decode+NMS via ``ops.yolo_postprocess``,
CenterNet peak decoding via ``ops.centernet_decode``, pose heatmap
argmax via ``ops.heatmap.decode_heatmaps`` — so the whole request path
is one fixed-shape XLA program per bucket), the restored variables, the
per-example input geometry, and a host-side ``postprocess`` that turns
batch row ``i`` into a JSON-able result.

``predict.py`` delegates its classify/detect/pose subcommands through
:func:`load_served` / :func:`restore_state`, so the one-shot CLI and the
batched engine share a single checkpoint-restore and decode code path
(previously duplicated in ``predict.py``).

Restored StableHLO artifacts (``export.load_exported``) serve too:
:func:`from_stablehlo` wraps the deserialized executable as a
ServedModel pinned to the batch size it was exported at (its bucket
ladder is exactly that one shape — ``jax.export`` artifacts are
shape-specialized).
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Callable

import numpy as np

from deepvision_tpu.obs.trace import startup_phase

__all__ = [
    "ServedModel", "load_served", "from_stablehlo", "restore_state",
    "model_geometry", "task_for",
]

# model name -> serving task; everything else in the registry is an
# image classifier. ("gan" serves the DCGAN *generator*: input is the
# latent z, output the sampled image.)
_TASKS = {
    "yolov3": "detect",
    "centernet": "detect",
    "hourglass104": "pose",
    "dcgan": "gan",
    "dcgan_generator": "gan",
}


def task_for(model_name: str) -> str:
    return _TASKS.get(model_name.removesuffix("_ref"), "classify")


def model_geometry(model_name: str) -> tuple[int, int]:
    """(input_size, channels) from the model's training config so
    restored checkpoints see the shapes they were trained with."""
    from deepvision_tpu.train.configs import TRAINING_CONFIG

    cfg = TRAINING_CONFIG.get(model_name.removesuffix("_ref"), {})
    return cfg.get("input_size", 224), cfg.get("channels", 3)


def input_scale(model_name: str) -> str:
    """Pixel-scaling convention for this model's inputs (mirrors the
    training pipeline): 'unit' for grayscale nets, 'torch' for
    PT-lineage configs, 'imagenet' otherwise, 'tanh' for the
    detection/pose/GAN families."""
    if task_for(model_name) != "classify":
        return "tanh"
    from deepvision_tpu.train.configs import TRAINING_CONFIG

    cfg = TRAINING_CONFIG.get(model_name.removesuffix("_ref"), {})
    if cfg.get("channels", 3) == 1:
        return "unit"  # grayscale nets (lenet5)
    return "torch" if cfg.get("augment", "tf") == "pt" else "imagenet"


# ------------------------------------------------------------- restore


def restore_state(model_name: str, workdir: str | None, sample,
                  epoch=None, **model_kw):
    """Build an inference TrainState and restore the latest (or a
    specific) checkpoint epoch from ``workdir`` — the single restore
    path shared by ``predict.py`` and the serving engine.

    ``epoch``: a specific saved epoch to restore (default latest) —
    with ``--keep-best`` retention the best checkpoint is often not the
    newest, so offline eval must be able to target it."""
    import jax.numpy as jnp
    import optax

    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.state import create_train_state

    model = get_model(model_name, dtype=jnp.float32, **model_kw)
    # Throwaway tx: restore_inference never touches opt_state, so the
    # template needn't match the training optimizer (which varies per
    # config: momentum SGD, adam, plateau-wrapped schedules).
    state = create_train_state(model, optax.sgd(0.1), sample)
    if workdir and Path(f"{workdir}/ckpt").exists():
        from deepvision_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(f"{workdir}/ckpt")
        if mgr.latest_epoch() is not None:
            state, meta = mgr.restore_inference(state, epoch)
            print(f"restored epoch {meta['epoch']} from {workdir}/ckpt")
            mgr.close()
            return state
        mgr.close()
    if epoch is not None:
        # an EXPLICIT epoch request must not silently score random
        # weights (near-zero metrics recorded as that epoch's result)
        raise FileNotFoundError(
            f"requested epoch {epoch} but no checkpoint dir under "
            f"{workdir!r}")
    print("no checkpoint found — running freshly initialized weights")
    return state


def _state_variables(state) -> dict:
    variables = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    return variables


# ---------------------------------------------------------- ServedModel


@dataclasses.dataclass
class ServedModel:
    """One model the engine can serve. ``forward`` is pure/jit-able
    (``(variables, batch) -> outputs``); ``postprocess`` runs on the
    host on fetched outputs and extracts row ``i`` as a JSON-able dict.
    ``buckets`` overrides the engine's ladder (StableHLO artifacts are
    pinned to the batch they were exported at); ``precompiled`` is a
    ready runner that bypasses compilation entirely."""

    name: str
    task: str
    forward: Callable
    variables: Any
    input_shape: tuple[int, ...]
    postprocess: Callable
    input_dtype: Any = np.float32
    buckets: tuple[int, ...] | None = None
    scale: str = "unit"
    precompiled: Callable | None = None
    _direct: Callable | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # tenancy: the live WeightsEdition (``serve.tenancy``) once the
    # engine adopts this model as a tenant. Runners compiled while an
    # edition is attached read weights through it at call time, which
    # is what makes LRU eviction and zero-drop hot-swap possible.
    edition: Any = dataclasses.field(
        default=None, repr=False, compare=False)
    _fingerprint: str | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def dtype_str(self) -> str:
        return str(np.dtype(self.input_dtype))

    def weights_fingerprint(self) -> str:
        """Content hash of the weights (cached): the compile-cache /
        artifact-store key component that keeps an executable compiled
        against one weights generation from ever pairing with another.
        Content-derived, so a respawned replica restoring the same
        checkpoint agrees with the store on disk."""
        if self.edition is not None:
            return self.edition.fingerprint
        if self._fingerprint is None:
            from deepvision_tpu.serve.tenancy import fingerprint_variables

            self._fingerprint = fingerprint_variables(self.variables)
        return self._fingerprint

    # -- engine path -----------------------------------------------------
    def as_stage(self):
        """The compiled unit behind this model: a ``pipeline.ModelStage``
        carrying the pure forward + variables + explicit input/output
        avals — what a serving DAG composes. ``compile_for`` delegates
        here so the single-model and pipeline paths share one AOT
        compile recipe. The stage snapshots the CURRENT weights edition:
        runners compiled from it read that edition at call time."""
        from deepvision_tpu.serve.pipeline import ModelStage

        ed = self.edition
        return ModelStage(
            name=self.name, forward=self.forward,
            variables=self.variables, input_shape=self.input_shape,
            input_dtype=self.input_dtype, precompiled=self.precompiled,
            pinned_buckets=self.buckets,
            variables_ref=(lambda: ed.variables) if ed is not None
            else None,
            # config-time hash of host weights (cached after first
            # call), not a fetch on the DAG execution path
            fingerprint=self.weights_fingerprint(),  # jaxlint: disable=JX127
        )

    def in_avals(self, bucket: int):
        return self.as_stage().in_avals(bucket)

    def out_avals(self, bucket: int):
        """Abstract output pytree at ``bucket`` (``jax.eval_shape``, no
        compile) — the seam a pipeline validator type-checks DAG edges
        against, mirroring ``export.py``'s artifact metadata."""
        return self.as_stage().out_avals(bucket)

    def compile_for(self, bucket: int, mesh) -> Callable:
        """AOT-compile the forward at ``(bucket, *input_shape)`` over
        ``mesh`` — batch sharded on the data axis, variables replicated,
        the input buffer donated — and return a runner
        ``x_device -> device outputs``. StableHLO-backed models return
        their deserialized executable (already compiled, one shape)."""
        return self.as_stage().compile(bucket, mesh, donate=True)

    def export_bytes(self, bucket: int) -> bytes:
        """Serialize the whole request program at ``bucket`` —
        forward + in-graph post-processing with the CURRENT weights
        baked in as constants — as StableHLO bytes. What the serve
        artifact store persists (keyed by this model's
        ``weights_fingerprint``), so a fresh replica deserializes
        instead of re-tracing."""
        from deepvision_tpu.export import export_callable

        variables = self.variables
        forward = self.forward

        def fn(x):
            return forward(variables, x)

        return export_callable(fn, self.in_avals(bucket))

    # -- direct (engine-less) path: the one-shot CLI ---------------------
    def run(self, batch) -> Any:
        """Direct host-side call for the one-shot CLI path (no queue, no
        buckets): jit once per instance, fetch outputs to host."""
        import jax

        if self.precompiled is not None:
            return jax.device_get(self.precompiled(np.asarray(batch)))
        if self._direct is None:
            self._direct = jax.jit(self.forward)
        return jax.device_get(
            self._direct(self.variables, np.asarray(batch)))

    def run_one(self, x) -> dict:
        """Single example (no batch dim) -> this task's result dict."""
        return self.postprocess(self.run(np.asarray(x)[None]), 0)


# ------------------------------------------------------- task forwards

# Every served program names its two halves (``jax.named_scope``), so
# that each device operation's ``op_name`` metadata, and with it every
# `XLA Ops` event of a profile, says which half it belongs to. Metadata
# only: the compiled code is the same.
FORWARD_SCOPE = "served/forward"
POSTPROCESS_SCOPE = "served/postprocess"


def _classify_forward(apply_fn, top_k: int):
    import jax
    import jax.numpy as jnp

    def forward(variables, x):
        with jax.named_scope(FORWARD_SCOPE):
            logits = apply_fn(variables, x, train=False)
        with jax.named_scope(POSTPROCESS_SCOPE):
            if isinstance(logits, (tuple, list)):
                logits = logits[0]  # aux-head models (inception) -> main
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            top_probs, top_classes = jax.lax.top_k(probs, top_k)
        return {"probs": top_probs, "classes": top_classes}

    return forward


def _classify_post(host: dict, i: int) -> dict:
    return {"classes": np.asarray(host["classes"][i]).tolist(),
            "probs": np.asarray(host["probs"][i]).tolist()}


def _yolo_forward(apply_fn, num_classes: int, score_thresh: float,
                  iou_thresh: float):
    import jax

    from deepvision_tpu.ops.yolo_postprocess import yolo_postprocess

    def forward(variables, x):
        with jax.named_scope(FORWARD_SCOPE):
            preds = apply_fn(variables, x, train=False)
        with jax.named_scope(POSTPROCESS_SCOPE):
            boxes, scores, classes, valid, _ = yolo_postprocess(
                preds, num_classes,
                score_thresh=score_thresh, iou_thresh=iou_thresh,
            )
        return {"boxes": boxes, "scores": scores, "classes": classes,
                "valid": valid}

    return forward


def _detect_post(host: dict, i: int) -> dict:
    keep = np.asarray(host["valid"][i]).astype(bool)
    return {
        # normalized corner boxes (x1, y1, x2, y2)
        "boxes": np.asarray(host["boxes"][i])[keep].tolist(),
        "scores": np.asarray(host["scores"][i])[keep].tolist(),
        "classes": np.asarray(host["classes"][i])[keep].tolist(),
    }


def _centernet_forward(apply_fn, score_thresh: float, top_k: int = 100):
    import jax

    from deepvision_tpu.ops.centernet_decode import decode_centernet
    from deepvision_tpu.ops.iou import xywh_to_corners

    def forward(variables, x):
        with jax.named_scope(FORWARD_SCOPE):
            heat, wh, off = apply_fn(variables, x, train=False)[-1]
        with jax.named_scope(POSTPROCESS_SCOPE):
            det = decode_centernet(heat, wh, off, top_k=top_k)
            # normalize to the same corner-box contract as the YOLO head
            det["boxes"] = xywh_to_corners(det["boxes"])
            det["valid"] = det["scores"] > score_thresh
        return det

    return forward


def _pose_forward(apply_fn):
    import jax

    from deepvision_tpu.ops.heatmap import decode_heatmaps

    def forward(variables, x):
        with jax.named_scope(FORWARD_SCOPE):
            heatmaps = apply_fn(variables, x, train=False)[-1]  # last stack
        with jax.named_scope(POSTPROCESS_SCOPE):
            kx, ky, conf = decode_heatmaps(heatmaps)
        return {"x": kx, "y": ky, "conf": conf}

    return forward


def _pose_post(host: dict, i: int) -> dict:
    return {"joints": np.stack(
        [np.asarray(host["x"][i]), np.asarray(host["y"][i]),
         np.asarray(host["conf"][i])], axis=-1).tolist()}


def _gan_post(host: dict, i: int) -> dict:
    return {"image": np.asarray(host["image"][i]).tolist()}


# --------------------------------------------------------------- loaders


@startup_phase("load_model")
def load_served(
    name: str,
    workdir: str | None = None,
    *,
    task: str | None = None,
    epoch: int | None = None,
    input_size: int | None = None,
    num_classes: int | None = None,
    top_k: int = 5,
    score_thresh: float = 0.5,
    iou_thresh: float = 0.5,
    num_heatmaps: int = 16,
    **model_kw,
) -> ServedModel:
    """Restore registry model ``name`` from ``workdir`` (or fresh
    weights) and wrap it as a :class:`ServedModel` for its task."""
    task = task or task_for(name)
    size, channels = model_geometry(name)
    if input_size is not None:
        size = input_size

    if task == "gan":
        return _load_gan_served(name, workdir, epoch=epoch)

    from deepvision_tpu.train.configs import TRAINING_CONFIG

    if num_classes is None:
        num_classes = TRAINING_CONFIG.get(
            name.removesuffix("_ref"), {}).get("num_classes", 1000)

    if task == "classify":
        sample = np.zeros((1, size, size, channels), np.float32)
        state = restore_state(name, workdir, sample, epoch,
                              num_classes=num_classes, **model_kw)
        forward = _classify_forward(state.apply_fn, top_k)
        post = _classify_post
    elif task == "detect":
        sample = np.zeros((1, size, size, channels), np.float32)
        state = restore_state(name, workdir, sample, epoch,
                              num_classes=num_classes, **model_kw)
        if name.removesuffix("_ref") == "centernet":
            forward = _centernet_forward(state.apply_fn, score_thresh)
        else:
            forward = _yolo_forward(state.apply_fn, num_classes,
                                    score_thresh, iou_thresh)
        post = _detect_post
    elif task == "pose":
        sample = np.zeros((1, size, size, channels), np.float32)
        state = restore_state(name, workdir, sample, epoch,
                              num_heatmaps=num_heatmaps, **model_kw)
        forward = _pose_forward(state.apply_fn)
        post = _pose_post
    else:
        raise ValueError(f"unknown serving task {task!r}")

    return ServedModel(
        name=name, task=task, forward=forward,
        variables=_state_variables(state),
        input_shape=(size, size, channels), postprocess=post,
        scale=input_scale(name),
    )


def _load_gan_served(name: str, workdir: str | None, *,
                     epoch: int | None = None) -> ServedModel:
    """DCGAN generator as a served model: input z, output image."""
    from deepvision_tpu.models import get_model
    from deepvision_tpu.train.gan import create_dcgan_state

    state = create_dcgan_state(
        get_model("dcgan_generator"), get_model("dcgan_discriminator")
    )
    restored = False
    if workdir and Path(f"{workdir}/ckpt").exists():
        from deepvision_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(f"{workdir}/ckpt")
        if mgr.latest_epoch() is not None:
            state, meta = mgr.restore_inference(state, epoch)
            print(f"restored epoch {meta['epoch']} from {workdir}/ckpt")
            restored = True
        mgr.close()
    if epoch is not None and not restored:
        # same invariant as restore_state: an EXPLICIT epoch request
        # must not silently serve random weights
        raise FileNotFoundError(
            f"requested epoch {epoch} but no checkpoint under "
            f"{workdir!r}")
    g_apply = state.g_apply
    variables = {"params": state.params, "batch_stats": state.batch_stats}

    def forward(v, z):
        image = g_apply(
            {"params": v["params"]["generator"],
             "batch_stats": v["batch_stats"]["generator"]},
            z, train=False,
        )
        return {"image": image}

    return ServedModel(
        name=name, task="gan", forward=forward, variables=variables,
        input_shape=(state.noise_dim,), postprocess=_gan_post,
        scale="tanh",
    )


def from_stablehlo(path: str | Path, *, name: str | None = None,
                   task: str = "classify", top_k: int = 5) -> ServedModel:
    """Wrap an ``export.py`` StableHLO artifact as a ServedModel.

    The artifact is shape-specialized at export time, so its bucket
    ladder is exactly the exported batch size; the engine serves it with
    zero compiles (the deserialized executable IS the runner)."""
    from deepvision_tpu.export import load_exported

    fn = load_exported(path)
    (aval,) = fn.in_avals  # export_forward exports a single-arg forward
    batch, *input_shape = aval.shape
    name = name or Path(path).stem

    if task == "classify":
        def post(host, i):
            out = host
            if isinstance(out, (tuple, list)):
                out = out[0]
            logits = np.asarray(out[i])
            top = np.argsort(logits)[::-1][:top_k]
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            return {"classes": top.tolist(),
                    "probs": probs[top].tolist()}
    else:
        raise ValueError(
            f"StableHLO serving currently supports classify heads only, "
            f"got task {task!r}")

    def precompiled(x):
        return fn(x)

    return ServedModel(
        name=name, task=task, forward=lambda _v, x: fn(x), variables=None,
        input_shape=tuple(input_shape), postprocess=post,
        input_dtype=np.dtype(aval.dtype), buckets=(int(batch),),
        precompiled=precompiled,
    )
