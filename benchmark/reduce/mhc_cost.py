"""What the hyper-connected token model's manifold-constrained
hyper-connections have to do a sample, from the configuration's shapes:
the operations and the bytes a roofline divides.

Counted once, whatever the program does: around each sublayer (two a
block: the dense blocks, the expert blocks and the MTP block) the ``n``
streams of every position are read once and written once on the way
forward, and their cotangent is read once and written once on the way
back, in the compute dtype; the operations are the reference's count
of the maps' product and the three mixes
(``forward_flops_parts(cfg)["hyper_connections"]``), three times for
forward and backward. Float32 copies of the streams, the norm's second
read, recomputation and the small ``[n, n]`` arithmetic of the Sinkhorn
iterations are the program's, not the model's: they are in the time
only.

At the cell's shapes (``xing4_29b_a4b``: 2,048 positions, 4 streams of
3,584, 6 blocks) a sample is 2.82e9 bytes, 3.44 ms at 819 GB/s, and
6.34e10 operations, 0.32 ms at 197 TFLOP/s: the bytes bind.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def operations_and_bytes(cfg: dict, reference) -> tuple:
    """-> (operations, bytes) of one sample's hyper-connections, forward
    and backward, over all of the configuration's blocks. ``reference``
    is the configuration's plain reference module."""
    operations = 3 * reference.forward_flops_parts(cfg)["hyper_connections"]
    sz = reference.sizes(cfg)
    blocks = sz["dense_layers"] + sz["expert_layers"] + sz["mtp_layers"]
    streams = (_BYTES[cfg["compute_dtype"]] * sz["seq"] * sz["streams"]
               * cfg["hidden_size"])
    # two sublayers a block; read and written, forward and backward
    return operations, blocks * 2 * 4 * streams


def least_seconds(cfg: dict, reference, peaks: dict) -> float:
    """The roofline: the longer of operations over the bf16 peak and
    bytes over the HBM peak (``peaks``: a row of ``peaks.json``)."""
    operations, moved = operations_and_bytes(cfg, reference)
    return max(operations / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
