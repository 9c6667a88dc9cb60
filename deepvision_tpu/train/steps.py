"""Canonical pure step functions for classification models.

The reference repeats this logic in every train.py (forward → CE → backward →
step → metrics; ref: ResNet/pytorch/train.py:438-485 and validate :488-520).
Here it is written once, as pure functions suitable for
``core.step.compile_train_step``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from deepvision_tpu.core.precision import precision_metrics
from deepvision_tpu.ops.normalize import maybe_normalize
from deepvision_tpu.losses.classification import (
    softmax_cross_entropy,
    softmax_cross_entropy_per_sample,
    topk_accuracy,
    topk_correct,
)
from deepvision_tpu.train.state import TrainState


def classification_train_step(
    state: TrainState, batch: dict, key: jax.Array,
    normalize_kind: str = "imagenet",
) -> tuple[TrainState, dict]:
    """One SGD step on {'image','label'}; returns (new_state, metrics).

    ``normalize_kind`` must match the host pipeline's uint8 wire contract:
    "imagenet" (TF-lineage mean subtraction) or "torch" (PT-lineage
    mean/std — configs with ``augment: "pt"``); bind it with
    ``functools.partial`` before compiling.

    Mixup (``data/device_aug.py``, device-side): when the in-step
    augmentation mixed the images it adds ``label_b`` (the partner
    permutation's labels) and ``lam`` to the batch, and the loss becomes
    the standard convex pair ``lam*CE(y) + (1-lam)*CE(y_b)`` (Zhang et
    al. 2018); top-k accuracy stays against the primary labels. The
    keys are present-or-absent per CONFIG (never per batch), so there is
    no retrace churn."""
    images = maybe_normalize(batch["image"], normalize_kind)
    labels = batch["label"]
    labels_b, lam = batch.get("label_b"), batch.get("lam")

    def mixed_ce(logits):
        loss = softmax_cross_entropy(logits, labels)
        if labels_b is None:
            return loss
        return lam * loss + (1.0 - lam) * softmax_cross_entropy(
            logits, labels_b)

    def loss_fn(params):
        out, mutated = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            images,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": key},
        )
        # Inception-style aux heads return (main, aux...) tuples; weight the
        # aux losses 0.3 as the paper/reference do
        # (ref: Inception/pytorch/train.py aux handling, models/inception_v1.py:92-113).
        if isinstance(out, (tuple, list)):
            main, *aux = out
            loss = mixed_ce(main)
            for a in aux:
                loss = loss + 0.3 * mixed_ce(a)
            logits = main
        else:
            logits = out
            loss = mixed_ce(logits)
        # backward runs on the (possibly loss-scaled) value; the RAW
        # loss rides the aux so metrics never report the scaled number
        return state.scale_loss(loss), (
            loss, logits, mutated.get("batch_stats", state.batch_stats))

    (_, (loss, logits, new_bs)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(state.params)
    new_state = state.apply_gradients(grads, batch_stats=new_bs)
    metrics = {"loss": loss, **topk_accuracy(logits, labels),
               **precision_metrics(new_state)}
    return new_state, metrics


def yolo_train_step(state: TrainState, batch: dict, key: jax.Array):
    """One detection step on {'image','boxes','label'}.

    Ground-truth grid encoding runs INSIDE the compiled step
    (ops.yolo_encode — the reference does it per-sample on the host with
    TensorArray loops, ref: YOLO/tensorflow/preprocess.py:137-269); grids
    never cross the host↔device boundary. ``boxes`` are (B, M, 4) xywh
    normalized, padded with zeros; ``label`` is (B, M) int32, -1 padding.
    """
    from deepvision_tpu.losses.yolo import yolo_loss
    from deepvision_tpu.ops.yolo_encode import encode_labels

    images = maybe_normalize(batch["image"], "tanh")
    boxes, labels = batch["boxes"], batch["label"]
    size = images.shape[1]
    grid_sizes = (size // 8, size // 16, size // 32)

    def loss_fn(params):
        preds, mutated = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            images,
            train=True,
            mutable=["batch_stats"],
        )
        num_classes = preds[0].shape[-1] - 5
        y_true = encode_labels(
            boxes, labels, num_classes, grid_sizes=grid_sizes
        )
        parts = yolo_loss(y_true, preds, num_classes,
                          true_boxes_xywh=boxes)
        loss = jnp.mean(parts["loss"])
        return state.scale_loss(loss), (
            parts, mutated.get("batch_stats", state.batch_stats))

    (_, (parts, new_bs)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(state.params)
    new_state = state.apply_gradients(grads, batch_stats=new_bs)
    metrics = {k: jnp.mean(v) for k, v in parts.items()}
    metrics.update(precision_metrics(new_state))
    return new_state, metrics


def yolo_eval_step(state: TrainState, batch: dict) -> dict:
    """Mask-weighted val-loss sums (exact full-set aggregation)."""
    from deepvision_tpu.losses.yolo import yolo_loss
    from deepvision_tpu.ops.yolo_encode import encode_labels

    images = maybe_normalize(batch["image"], "tanh")
    boxes, labels = batch["boxes"], batch["label"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(images.shape[0], jnp.float32)
    size = images.shape[1]
    grid_sizes = (size // 8, size // 16, size // 32)
    variables: dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    preds = state.apply_fn(variables, images, train=False)
    num_classes = preds[0].shape[-1] - 5
    y_true = encode_labels(boxes, labels, num_classes, grid_sizes=grid_sizes)
    parts = yolo_loss(y_true, preds, num_classes, true_boxes_xywh=boxes)
    return {
        "loss_sum": jnp.sum(parts["loss"] * mask),
        "count": jnp.sum(mask),
    }


def classification_eval_step(
    state: TrainState, batch: dict, normalize_kind: str = "imagenet"
) -> dict:
    """Count-weighted sums over one batch, for exact epoch aggregation.

    ``batch["mask"]`` (optional, (B,) float 1/0) marks padding rows: the
    final partial validation batch is padded to full size and masked so the
    whole 50k-image set is evaluated with one compiled shape — the
    reference evaluates the full set too (ref: ResNet/pytorch/train.py:488-520).
    """
    images = maybe_normalize(batch["image"], normalize_kind)
    labels = batch["label"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(labels.shape[0], jnp.float32)
    variables: dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    logits = state.apply_fn(variables, images, train=False)
    if isinstance(logits, (tuple, list)):
        logits = logits[0]
    losses = softmax_cross_entropy_per_sample(logits, labels)
    correct = topk_correct(logits, labels)
    return {
        "loss_sum": jnp.sum(losses * mask),
        "count": jnp.sum(mask),
        **{k: jnp.sum(v * mask) for k, v in correct.items()},
    }


def pose_train_step(state: TrainState, batch: dict, key: jax.Array):
    """One pose step on {'image','kx','ky','v'}.

    Gaussian heatmap targets are rasterized INSIDE the compiled step
    (ops.heatmap — the reference does it per-joint on the host with
    TensorArray loops, ref: Hourglass/tensorflow/preprocess.py:91-173);
    loss is the stack-summed foreground-weighted MSE
    (ref: Hourglass/tensorflow/train.py:65-76).
    """
    from deepvision_tpu.losses.pose import weighted_heatmap_mse
    from deepvision_tpu.ops.heatmap import gaussian_heatmaps

    images = maybe_normalize(batch["image"], "tanh")
    grid = images.shape[1] // 4  # stem downsamples 256² -> 64²
    targets = gaussian_heatmaps(
        batch["kx"], batch["ky"], batch["v"], height=grid, width=grid
    )

    def loss_fn(params):
        outputs, mutated = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            images,
            train=True,
            mutable=["batch_stats"],
        )
        loss = weighted_heatmap_mse(targets, outputs)
        return state.scale_loss(loss), (
            loss, mutated.get("batch_stats", state.batch_stats))

    (_, (loss, new_bs)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params
    )
    new_state = state.apply_gradients(grads, batch_stats=new_bs)
    return new_state, {"loss": loss,
                       **precision_metrics(new_state)}


def pose_eval_step(state: TrainState, batch: dict) -> dict:
    """Mask-weighted val-loss sums (exact full-set aggregation)."""
    from deepvision_tpu.losses.pose import weighted_heatmap_mse
    from deepvision_tpu.ops.heatmap import gaussian_heatmaps

    images = maybe_normalize(batch["image"], "tanh")
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(images.shape[0], jnp.float32)
    grid = images.shape[1] // 4
    targets = gaussian_heatmaps(
        batch["kx"], batch["ky"], batch["v"], height=grid, width=grid
    )
    variables: dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    outputs = state.apply_fn(variables, images, train=False)
    losses = weighted_heatmap_mse(targets, outputs, per_sample=True)
    return {
        "loss_sum": jnp.sum(losses * mask),
        "count": jnp.sum(mask),
    }


def centernet_train_step(state: TrainState, batch: dict, key: jax.Array):
    """One CenterNet step on the detection batch format
    {'image','boxes','label'} (shared with YOLO); targets encoded in-step
    (ops.centernet_encode), loss = focal + L1s over both stacks
    (losses.centernet — the capability the reference left unfinished,
    ref: ObjectsAsPoints/tensorflow/train.py:35,248).
    """
    from deepvision_tpu.losses.centernet import centernet_loss
    from deepvision_tpu.ops.centernet_encode import encode_centernet

    images = maybe_normalize(batch["image"], "tanh")
    boxes, labels = batch["boxes"], batch["label"]
    grid = images.shape[1] // 4  # output stride 4

    def loss_fn(params):
        outputs, mutated = state.apply_fn(
            {"params": params, "batch_stats": state.batch_stats},
            images,
            train=True,
            mutable=["batch_stats"],
        )
        num_classes = outputs[0][0].shape[-1]
        targets = encode_centernet(boxes, labels, num_classes, grid)
        parts = centernet_loss(targets, outputs)
        return state.scale_loss(parts["loss"]), (
            parts, mutated.get("batch_stats", state.batch_stats))

    (_, (parts, new_bs)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(state.params)
    new_state = state.apply_gradients(grads, batch_stats=new_bs)
    return new_state, {**parts, **precision_metrics(new_state)}


def centernet_eval_step(state: TrainState, batch: dict) -> dict:
    """Mask-weighted val-loss sums (exact full-set aggregation)."""
    from deepvision_tpu.losses.centernet import centernet_loss
    from deepvision_tpu.ops.centernet_encode import encode_centernet

    images = maybe_normalize(batch["image"], "tanh")
    boxes, labels = batch["boxes"], batch["label"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(images.shape[0], jnp.float32)
    grid = images.shape[1] // 4
    variables: dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    outputs = state.apply_fn(variables, images, train=False)
    num_classes = outputs[0][0].shape[-1]
    targets = encode_centernet(boxes, labels, num_classes, grid)
    parts = centernet_loss(targets, outputs, per_sample=True)
    return {
        "loss_sum": jnp.sum(parts["loss"] * mask),
        "count": jnp.sum(mask),
    }


def _vlm_losses(out: dict, index_loss_weight: float):
    """(loss, language loss, alignment loss) from the token model's
    per-sample results: mean next-token cross-entropy over every text
    position plus the indexer's alignment loss (summed over layers and
    positions inside the model) averaged over the sequences."""
    lm = jnp.mean(out["nll"])
    index = jnp.mean(out["index_kl"])
    return lm + index_loss_weight * index, lm, index


def vlm_train_step(state: TrainState, batch: dict, key: jax.Array,
                   index_loss_weight: float = 1.0):
    """One step of the vision-language token model on {'image',
    'tokens'} (``models/transformer.KeyeVL2``): next-token loss on the
    text over the vocabulary the model holds, plus the indexer's
    alignment loss, which alone reaches the indexer's parameters (its
    input and its target are detached inside the model).

    ``metrics`` carries the routing and selection counts of the step:
    ``moe_local_assignments`` (token-expert choices that fell on the
    experts held here, all layers), ``moe_expert_tokens_max`` / ``_mean``
    (tokens of the busiest held expert of any layer / of the average
    one), ``moe_dropped`` (0: the expert layer drops nothing) and
    ``dsa_selected_pairs`` (query-key pairs attention ran over)."""
    del key                                     # no dropout in this family
    inputs = {"image": batch["image"], "tokens": batch["tokens"]}

    def loss_fn(params):
        out = state.apply_fn({"params": params}, inputs, train=True)
        loss, lm, index = _vlm_losses(out, index_loss_weight)
        return state.scale_loss(loss), (loss, lm, index, out)

    (_, (loss, lm, index, out)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    new_state = state.apply_gradients(grads)
    per_expert = jnp.sum(out["expert_tokens"], 0)        # [layers, held]
    metrics = {
        "loss": loss, "lm_loss": lm, "index_loss": index,
        "moe_local_assignments": jnp.sum(per_expert),
        "moe_expert_tokens_max": jnp.max(per_expert),
        "moe_expert_tokens_mean": jnp.mean(per_expert.astype(jnp.float32)),
        "moe_dropped": jnp.max(out["moe_dropped"]),
        "dsa_selected_pairs": jnp.sum(out["selected_pairs"]),
        **precision_metrics(new_state),
    }
    return new_state, metrics


def vlm_eval_step(state: TrainState, batch: dict,
                  index_loss_weight: float = 1.0) -> dict:
    """Count-weighted sums over one batch of {'image', 'tokens'}."""
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["tokens"].shape[0], jnp.float32)
    out = state.apply_fn(
        {"params": state.params},
        {"image": batch["image"], "tokens": batch["tokens"]}, train=False)
    lm = jnp.mean(out["nll"], -1)
    return {"loss_sum": jnp.sum((lm + index_loss_weight * out["index_kl"])
                                * mask),
            "lm_loss_sum": jnp.sum(lm * mask),
            "count": jnp.sum(mask)}


def lm_train_step(state: TrainState, batch: dict, key: jax.Array,
                  bias_rate: float = 1e-3, mtp_weight: float = 0.3):
    """One step of a text token model on {'tokens'}
    (``models/latent_moe.LatentMoeLM``, ``models/hyper_latent``): mean
    next-token cross-entropy over the vocabulary the model holds, plus
    ``mtp_weight`` times the mean cross-entropy of the multi-token
    prediction where the model has such a module (``mtp_nll``; 0.3 is
    DeepSeek-V3's weight of its first training phase); no auxiliary
    loss. The state
    holds a leaf the optimiser never moves and a rule does: each expert
    layer's selection bias has a gradient of 0 (it enters only the
    discrete choice; Adam leaves such a leaf where it is), and after the
    optimiser's update ``balance_router_bias`` adds ``bias_rate *
    sign(mean load - load)`` from this step's routing counts (this
    batch's tokens, every expert counted).

    ``metrics`` carries the routing counts of the step
    (``moe_local_assignments``, ``moe_expert_tokens_max`` / ``_mean``,
    ``moe_dropped``, as :func:`vlm_train_step`'s), ``moe_bias_abs_mean``
    (the mean of ``|b|`` over all bias entries after the rule: how far
    it has carried the bias) and ``attn_causal_pairs`` (query-key pairs
    attention ran over); a model with the module adds ``lm_loss`` and
    ``mtp_loss`` (the two means) and ``mhc_sinkhorn_err`` (the largest
    ``|row or column sum - 1|`` of any hyper-connection's mixing
    matrix)."""
    from deepvision_tpu.models.latent_moe import (
        balance_router_bias,
        router_bias_abs_mean,
    )

    del key                                     # no dropout in this family
    inputs = {"tokens": batch["tokens"]}

    def loss_fn(params):
        out = state.apply_fn({"params": params}, inputs, train=True)
        loss = jnp.mean(out["nll"])
        if "mtp_nll" in out:
            loss = loss + mtp_weight * jnp.mean(out["mtp_nll"])
        return state.scale_loss(loss), (loss, out)

    (_, (loss, out)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    new_state = state.apply_gradients(grads)
    with jax.named_scope("lm/moe/bias"):
        new_state = new_state.replace(params=balance_router_bias(
            new_state.params, jnp.sum(out["expert_counts"], 0), bias_rate))
        bias_abs_mean = router_bias_abs_mean(new_state.params)
    per_expert = jnp.sum(out["expert_tokens"], 0)        # [layers, held]
    metrics = {
        "loss": loss,
        "moe_local_assignments": jnp.sum(per_expert),
        "moe_expert_tokens_max": jnp.max(per_expert),
        "moe_expert_tokens_mean": jnp.mean(per_expert.astype(jnp.float32)),
        "moe_dropped": jnp.max(out["moe_dropped"]),
        "moe_bias_abs_mean": bias_abs_mean,
        "attn_causal_pairs": jnp.sum(out["causal_pairs"]),
        **precision_metrics(new_state),
    }
    if "mtp_nll" in out:
        metrics.update(lm_loss=jnp.mean(out["nll"]),
                       mtp_loss=jnp.mean(out["mtp_nll"]),
                       mhc_sinkhorn_err=jnp.max(out["mhc_sinkhorn_err"]))
    return new_state, metrics


def dense_lm_train_step(state: TrainState, batch: dict, key: jax.Array):
    """One step of a text token model without experts
    (``models/olmo_hybrid``) on {'tokens'}: mean next-token
    cross-entropy over the vocabulary, no auxiliary loss; ``metrics``
    carries the loss (and the ``mp_*`` numbers where loss scaling is on)."""
    del key                                     # no dropout in this family
    inputs = {"tokens": batch["tokens"]}

    def loss_fn(params):
        out = state.apply_fn({"params": params}, inputs, train=True)
        loss = jnp.mean(out["nll"])
        return state.scale_loss(loss), loss

    (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params)
    new_state = state.apply_gradients(grads)
    return new_state, {"loss": loss, **precision_metrics(new_state)}


def lm_eval_step(state: TrainState, batch: dict) -> dict:
    """Count-weighted sums over one batch of {'tokens'}."""
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["tokens"].shape[0], jnp.float32)
    out = state.apply_fn({"params": state.params},
                         {"tokens": batch["tokens"]}, train=False)
    sums = {"loss_sum": jnp.sum(jnp.mean(out["nll"], -1) * mask),
            "count": jnp.sum(mask)}
    if "mtp_nll" in out:
        sums["mtp_loss_sum"] = jnp.sum(jnp.mean(out["mtp_nll"], -1) * mask)
    return sums


def aggregate_eval_parts(parts) -> tuple[dict, float]:
    """Sum an iterable of eval-step outputs (count-weighted sums + a
    'count' key) into ``(val_* means, total count)`` — the one masked
    exact-aggregation impl shared by Trainer.validate and evaluate.py.
    '<k>_sum' and bare keys both become ``val_<k>`` means."""
    totals = None
    for part in parts:
        part = {k: float(v) for k, v in part.items()}
        if totals is None:
            totals = part
        else:
            totals = {k: totals[k] + part[k] for k in totals}
    if not totals:
        return {}, 0.0
    n = totals.pop("count")
    return {
        f"val_{k[:-4] if k.endswith('_sum') else k}": v / n
        for k, v in totals.items()
    }, n
