"""What the token model's indexer has to do a sample, from the
configuration's shapes: the operations and the bytes a roofline divides.

Counted once, whatever the program does. The reference's count of the
indexer (``forward_flops_parts(cfg)["indexer"]``, 2 x multiply-adds) has
two terms a layer: the score products over the causal pairs (``2 x
causal x heads x width``) and the three projections that make ``qi``,
``ki`` and ``w`` (``2 x positions x hidden x (heads x width + width +
heads)``). Forward and backward are three times the scores and twice
the projections: their input is detached (only the alignment loss
trains the indexer), so the way back gives the weights a gradient and
the input none. The bytes of one pass over the scores in and out of HBM
a layer are ``qi``, ``ki`` in the compute dtype and the float32 ``w`` in
and the float32 scores over the causal pairs out, three times as well.
Pairs above the diagonal that a blocked kernel computes, scores computed
once more for the selection and per-head products formed again on the
way back are the program's, not the model's: they are in the time only.

At the cell's shapes (``keye_vl2_30b_a3b``: 8,192 positions, 16 indexer
heads of 64, hidden 2,048, 5 layers) a sample is 1.40e12 operations,
7.1 ms at 197 TFLOP/s, and 2.29e9 bytes, 2.8 ms at 819 GB/s: the
operations bind.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def operations_and_bytes(cfg: dict, reference) -> tuple:
    """-> (operations, bytes) of one sample's indexer, forward and
    backward, over all of the configuration's decoder layers.
    ``reference`` is the configuration's plain reference module."""
    sa, layers = cfg["sa_config"], cfg["num_hidden_layers"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    t = reference.sizes(cfg)["seq"]
    causal = t * (t + 1) // 2
    scores = layers * 2 * causal * heads * width
    projections = reference.forward_flops_parts(cfg)["indexer"] - scores
    narrow = _BYTES[cfg["compute_dtype"]]
    layer = (narrow * t * (heads * width + width) + 4 * t * heads
             + 4 * causal)
    return 3 * scores + 2 * projections, 3 * layers * layer


def least_seconds(cfg: dict, reference, peaks: dict) -> float:
    """The roofline: the longer of operations over the bf16 peak and
    bytes over the HBM peak (``peaks``: a row of ``peaks.json``)."""
    operations, moved = operations_and_bytes(cfg, reference)
    return max(operations / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
