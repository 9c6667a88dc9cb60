"""What the token model's sparse attention has to do a sample, from the
configuration's shapes: the operations and the bytes a roofline divides.

Counted once, whatever the program does: the reference's count of the
attention over the selected pairs (``forward_flops_parts(cfg)
["attention"]``: logits and the product with the values, 2 x
multiply-adds) for forward and backward, three times the forward; and
the bytes of one pass in and out of HBM a layer (``q``, ``k``, ``v`` and
the output in the compute dtype, the indexer's float32 scores over the
causal pairs in, the float32 alignment target over them out), three
times as well. Masked-out pairs a blocked kernel computes, logits
recomputed on the way back and a target computed twice are the
program's, not the model's: they are in the time only.

At the cell's shapes (``keye_vl2_30b_a3b``: 8,192 positions, 32 heads
and 4 key/value heads of 128, top-2048, 5 layers) a sample is 3.61e12
operations, 18.3 ms at 197 TFLOP/s, and 6.29e9 bytes, 7.7 ms at 819
GB/s: the operations bind.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def operations_and_bytes(cfg: dict, reference) -> tuple:
    """-> (operations, bytes) of one sample's attention, forward and
    backward, over all of the configuration's decoder layers.
    ``reference`` is the configuration's plain reference module."""
    operations = 3 * reference.forward_flops_parts(cfg)["attention"]
    t = reference.sizes(cfg)["seq"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    wide = _BYTES[cfg["compute_dtype"]] * t * cfg["head_dim"]
    causal = t * (t + 1) // 2
    layer = 2 * heads * wide + 2 * groups * wide + 2 * 4 * causal
    return operations, 3 * cfg["num_hidden_layers"] * layer


def least_seconds(cfg: dict, reference, peaks: dict) -> float:
    """The roofline: the longer of operations over the bf16 peak and
    bytes over the HBM peak (``peaks``: a row of ``peaks.json``)."""
    operations, moved = operations_and_bytes(cfg, reference)
    return max(operations / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
