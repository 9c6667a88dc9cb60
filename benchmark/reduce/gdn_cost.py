"""What the hybrid model's gated delta rule has to do in a serving
window, from the configuration's shapes: the operations and the bytes a
roofline divides.

Counted once, whatever the program does, over every linear-attention
layer and head (``H`` heads, key and value widths ``dk`` and ``dv``):

- a prefill token: the chunk form's products in sub-chunks of ``C =
  delta_chunk`` tokens, at or below the diagonal only: ``beta k k^T``
  strictly below it (``C (C - 1) dk``), the unit triangular solve of
  ``[beta v | beta k exp(G)]`` (``C (C - 1) (dk + dv)``), ``q k^T`` and
  its product with the new values at or below it (``C (C + 1) (dk +
  dv)``), and the three products with the state ``w S``, ``q S`` and
  ``k^T v'`` (``6 C dk dv``), over ``C``: 2 x multiply-adds, against the
  bf16 peak;
- a decode row (a request a decode step serves): the float32 state read
  once and written once, ``2 x 4 dk dv`` bytes, against the HBM peak.

The conv, the gates, the norms and the gated output are in the time
only. At the cell's shapes (``olmo_hybrid_7b``: 6 linear layers of 30
heads of 96 and 192, sub-chunks of 64) a prefill token is 2.763e7
operations (0.14 us at 197 TFLOP/s) and a decode row 2.654e7 bytes (32.4
us at 819 GB/s).
"""

from __future__ import annotations


def _dims(cfg: dict) -> tuple:
    layers = cfg["layer_types"].count("linear_attention")
    return (layers * cfg["linear_num_key_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def prefill_flops_per_token(cfg: dict) -> float:
    heads, dk, dv = _dims(cfg)
    c = cfg["delta_chunk"]
    per_chunk = (c * (c - 1) * (2 * dk + dv) + c * (c + 1) * (dk + dv)
                 + 6 * c * dk * dv)
    return heads * per_chunk / c


def decode_bytes_per_row(cfg: dict) -> float:
    heads, dk, dv = _dims(cfg)
    return heads * 2 * 4 * dk * dv


def least_seconds(cfg: dict, prefill_tokens: int, decode_rows: int,
                  peaks: dict) -> float:
    """The roofline of a window's work: its prefill tokens' operations
    over the bf16 peak plus its decode rows' bytes over the HBM peak
    (``peaks``: a row of ``peaks.json``)."""
    return (prefill_tokens * prefill_flops_per_token(cfg)
            / peaks["bf16_flops_per_s"]
            + decode_rows * decode_bytes_per_row(cfg)
            / peaks["hbm_bytes_per_s"])
