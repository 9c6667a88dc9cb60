"""The generation cache: two kinds of state side by side under one slot
index.

A slot holds one request from its first prefill chunk to its last
token. Under its index the cache keeps, on the device, the state of
every layer of a hybrid model (``models/olmo_hybrid.cache_shapes``):

- for the linear-attention layers, state of a fixed size whatever the
  request's length: the conv's last ``kernel - 1`` inputs (``conv``, in
  the compute dtype) and the delta rule's ``[heads, dk, dv]`` matrices
  (``delta``, float32);
- for the full-attention layers, keys and values (``k``, ``v``) of a
  buffer of ``max_len`` positions that the request fills as it grows;
- the request's length (``length``).

Slots are contiguous and of one size: no paging, no prefix sharing. The
arrays are one pytree that the two programs take (donated) and give
back; this object holds the current one and the free list. A slot given
back is not cleared here: the next prefill into it starts at position 0,
and the program zeroes the slot's recurrent state and length there, so
nothing of the last request is read; its keys and values are written
before they are read and nothing past the length is read.
"""

from __future__ import annotations

import threading
from collections import deque

import jax.numpy as jnp

from deepvision_tpu.models.olmo_hybrid import HybridConfig, cache_shapes

__all__ = ["SlotCache"]


class SlotCache:
    """``slots`` slots of ``max_len`` positions for a model of ``cfg``."""

    def __init__(self, cfg: HybridConfig, slots: int, max_len: int):
        if max_len % cfg.key_block:
            raise ValueError(f"max_len {max_len} is not a multiple of the "
                             f"attention's key block {cfg.key_block}")
        self.cfg, self.slots, self.max_len = cfg, slots, max_len
        self.shapes = cache_shapes(cfg, slots, max_len)
        self.arrays = {k: jnp.zeros(s.shape, s.dtype)
                       for k, s in self.shapes.items()}
        self._free = deque(range(slots))
        self._lock = threading.Lock()

    def take(self) -> int | None:
        """A free slot's index, or None where every slot is held."""
        with self._lock:
            return self._free.popleft() if self._free else None

    def give(self, slot: int) -> None:
        with self._lock:
            if slot in self._free or not 0 <= slot < self.slots:
                raise ValueError(f"slot {slot} is not held")
            self._free.append(slot)

    @property
    def held(self) -> int:
        with self._lock:
            return self.slots - len(self._free)

    def release(self) -> None:
        """Drop the device arrays (the generator is closing)."""
        self.arrays = {}
