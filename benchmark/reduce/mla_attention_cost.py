"""What the text token model's causal latent attention has to do a
sample, from the configuration's shapes: the operations and the bytes a
roofline divides.

Counted once, whatever the program does: the reference's count of the
attention over the causal pairs (``forward_flops_parts(cfg)
["attention"]``: scores at the query/key width, the product with the
values at the value width, 2 x multiply-adds) for forward and backward,
three times the forward; and the bytes of ``q``, ``k``, ``v``, the
output and their gradients, each once in and out of HBM in the compute
dtype, a layer. The upper half of a causal block that a blocked form
computes and masks, logits recomputed on the way back and the
``[heads, queries, keys]`` tensors an XLA form writes out are the
program's, not the model's: they are in the time only.

At the cell's shapes (``kanana2_30b_a3b``: 8,192 positions, 32 heads of
192 for queries and keys and of 128 for values, 6 layers) a sample is
1.237e13 operations, 62.8 ms at 197 TFLOP/s, and 4.03e9 bytes, 4.9 ms at
819 GB/s: the operations bind.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def operations_and_bytes(cfg: dict, reference) -> tuple:
    """-> (operations, bytes) of one sample's attention, forward and
    backward, over all of the configuration's layers. ``reference`` is
    the configuration's plain reference module."""
    operations = 3 * reference.forward_flops_parts(cfg)["attention"]
    sz = reference.sizes(cfg)
    wide = 2 * sz["qk_dim"] + 2 * cfg["v_head_dim"]       # q, k, v, o
    layer = (_BYTES[cfg["compute_dtype"]] * sz["seq"]
             * cfg["num_attention_heads"] * wide)
    return operations, 2 * cfg["num_hidden_layers"] * layer


def least_seconds(cfg: dict, reference, peaks: dict) -> float:
    """The roofline: the longer of operations over the bf16 peak and
    bytes over the HBM peak (``peaks``: a row of ``peaks.json``)."""
    operations, moved = operations_and_bytes(cfg, reference)
    return max(operations / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
