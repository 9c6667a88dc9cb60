"""Token generation: a slot scheduler over two compiled programs.

A request is a prompt of token ids and a number of tokens to generate,
greedily, with no stop token. :class:`GenerationEngine` keeps a bounded
queue (``max_queue``; beyond it ``submit`` raises :class:`ShedError`
with a retry hint, as the image engine does) and a :class:`SlotCache`.
Its loop takes one step at a time, and a step does not answer one
request:

- **admit** (span ``gen/admit``): queued requests move into free slots,
  in order; one whose deadline has passed fails with ``TimeoutError``;
- **prefill** (``gen/prefill``): one chunk of at most ``prefill_chunk``
  prompt tokens of the oldest request still prefilling, padded to that
  length; its last chunk gives the request's first token;
- **decode** (``gen/decode``): one token for every slot whose prompt is
  done, in one call over all slots.

When both have work they alternate. Both programs are compiled once,
ahead of any request, at fixed shapes (``[prefill_chunk]`` ids and a
slot index; ``[slots]`` ids and a mask), and take the cache donated:
nothing compiles after warm-up, whatever the prompt lengths. A future
resolves to ``{"tokens": [...]}`` (and ``"logits"``, ``[max_new_tokens,
vocab]`` float32, the first row the prompt's last position's, for a
request submitted with ``keep_logits``).

Counters in the process's registry: ``gen_prefill_tokens`` (prompt
tokens run), ``gen_decode_tokens`` (tokens handed to requests, the
first one of each from its prefill among them), ``gen_decode_steps``,
``gen_decode_rows`` (requests a decode step served, summed over steps)
and the histogram ``gen_ttft`` (seconds from ``submit`` to the first
token).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from deepvision_tpu.models import olmo_hybrid
from deepvision_tpu.obs.metrics import GEN_COUNTERS, GEN_TTFT, default_registry
from deepvision_tpu.obs.trace import span
from deepvision_tpu.serve.admission import ShedError
from deepvision_tpu.serve.slot_cache import SlotCache

__all__ = ["GenerationEngine", "GenTelemetry", "ShedError", "is_generative",
           "load_generator"]


class GenTelemetry:
    """The generator's counters and its time-to-first-token histogram,
    registered in ``registry`` (the process's by default)."""

    def __init__(self, registry=None):
        reg = registry if registry is not None else default_registry()
        self.counters = {n: reg.counter(n) for n in GEN_COUNTERS}
        self.ttft = reg.histogram(GEN_TTFT)

    def add(self, name: str, n: int) -> None:
        self.counters[name].inc(n)

    def values(self) -> dict:
        return {n: c.value for n, c in self.counters.items()}


class _Request:
    __slots__ = ("tokens", "max_new", "future", "t_submit", "deadline",
                 "keep_logits", "slot", "done", "out", "logits")

    def __init__(self, tokens, max_new, deadline, keep_logits):
        self.tokens, self.max_new = tokens, max_new
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.deadline, self.keep_logits = deadline, keep_logits
        self.slot = None
        self.done = 0            # prompt tokens prefilled
        self.out: list = []
        self.logits: list = []


class GenerationEngine:
    """Greedy generation with a hybrid model (``models/olmo_hybrid``) over
    ``params`` (the weights as served, :func:`olmo_hybrid.serving_params`).

    ``slots`` requests are held at once, each in a slot of ``max_len``
    positions; a prompt runs in chunks of ``prefill_chunk`` tokens (a
    multiple of the model's ``chunk``)."""

    def __init__(self, cfg: olmo_hybrid.HybridConfig, params, *,
                 slots: int, max_len: int, prefill_chunk: int,
                 max_queue: int = 64, registry=None):
        if prefill_chunk % cfg.chunk or prefill_chunk > max_len:
            raise ValueError(f"prefill_chunk {prefill_chunk} must be a "
                             f"multiple of {cfg.chunk} and at most max_len")
        self.cfg, self.params = cfg, params
        self.prefill_chunk, self.max_queue = prefill_chunk, max_queue
        self.cache = SlotCache(cfg, slots, max_len)
        self.telemetry = GenTelemetry(registry)
        self._prefill, self._decode = self._compile()
        self._queue: deque = deque()
        self._prefilling: deque = deque()       # admitted, prompt not done
        self._decoding: dict = {}               # slot -> request
        self._turn_decode = False
        self._service_s = 1.0                   # EWMA of a request's time
        self._cond = threading.Condition()
        self._closing = False
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="generator")
        self._thread.start()

    # -- programs ------------------------------------------------------
    def _compile(self):
        cfg, sds = self.cfg, jax.ShapeDtypeStruct
        i32 = sds((), jnp.int32)
        slots = self.cache.slots
        prefill = jax.jit(functools.partial(olmo_hybrid.prefill, cfg=cfg),
                          donate_argnums=(1,)).lower(
            self.params, self.cache.shapes, i32,
            sds((self.prefill_chunk,), jnp.int32), i32, i32).compile()
        decode = jax.jit(functools.partial(olmo_hybrid.decode, cfg=cfg),
                         donate_argnums=(1,)).lower(
            self.params, self.cache.shapes, sds((slots,), jnp.int32),
            sds((slots,), jnp.bool_)).compile()
        return prefill, decode

    # -- client side ---------------------------------------------------
    def submit(self, tokens, max_new_tokens: int, *,
               timeout_s: float | None = None,
               keep_logits: bool = False) -> Future:
        tokens = np.asarray(tokens).reshape(-1)
        n, chunk = len(tokens), self.prefill_chunk
        if n < 1 or max_new_tokens < 1:
            raise ValueError("a request needs a prompt and at least one "
                             "token to generate")
        if -(-n // chunk) * chunk > self.cache.max_len \
                or n + max_new_tokens - 1 > self.cache.max_len:
            raise ValueError(f"{n} prompt tokens and {max_new_tokens} new "
                             f"ones do not fit a slot of "
                             f"{self.cache.max_len} positions")
        if not np.issubdtype(tokens.dtype, np.integer) or tokens.min() < 0 \
                or tokens.max() >= self.cfg.vocab_size:
            raise ValueError("a token id lies outside the vocabulary")
        tokens = tokens.astype(np.int32)
        deadline = (time.perf_counter() + timeout_s
                    if timeout_s is not None else None)
        req = _Request(tokens, int(max_new_tokens), deadline, keep_logits)
        with self._cond:
            if self._closing or self._error is not None:
                raise RuntimeError("the generator is closed"
                                   if self._error is None else
                                   f"the generator failed: {self._error!r}")
            if len(self._queue) >= self.max_queue:
                raise ShedError(
                    f"generation queue full ({self.max_queue})",
                    self._service_s * len(self._queue) / self.cache.slots)
            self._queue.append(req)
            self._cond.notify()
        return req.future

    def close(self) -> None:
        with self._cond:
            self._closing = True
            self._cond.notify()
        self._thread.join()
        self.cache.release()

    def stats(self) -> dict:
        with self._cond:
            return {"queued": len(self._queue),
                    "prefilling": len(self._prefilling),
                    "decoding": len(self._decoding),
                    "slots": self.cache.slots, **self.telemetry.values()}

    # -- the loop ------------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._cond:
                self._cond.wait_for(lambda: self._closing or self._queue
                                    or self._prefilling or self._decoding)
                if self._closing:
                    break
            try:
                self._step()
            except Exception as e:      # fail everything held, then stop
                self._fail_all(e)
                return
        self._fail_all(RuntimeError("the generator closed"))

    def _step(self) -> None:
        now = time.perf_counter()
        self._admit(now)
        self._expire(now)
        prefill = bool(self._prefilling)
        if prefill and (not self._decoding or not self._turn_decode):
            self._prefill_step(self._prefilling[0])
            self._turn_decode = True
        elif self._decoding:
            self._decode_step()
            self._turn_decode = False

    def _admit(self, now: float) -> None:
        with span("admit", cat="gen"), self._cond:
            while self._queue and self.cache.held < self.cache.slots:
                req = self._queue.popleft()
                if req.deadline is not None and now > req.deadline:
                    req.future.set_exception(TimeoutError(
                        "deadline expired in the queue"))
                    continue
                req.slot = self.cache.take()
                self._prefilling.append(req)

    def _expire(self, now: float) -> None:
        with self._cond:
            held = [*self._prefilling, *self._decoding.values()]
        for req in held:
            if req.deadline is not None and now > req.deadline:
                self._finish(req, TimeoutError("deadline expired after "
                                               f"{len(req.out)} tokens"))

    def _prefill_step(self, req: _Request) -> None:
        chunk = self.prefill_chunk
        start = req.done
        n = min(chunk, len(req.tokens) - start)
        ids = np.zeros(chunk, np.int32)
        ids[:n] = req.tokens[start:start + n]
        with span("prefill", cat="gen", args={"tokens": n}):
            self.cache.arrays, token, logits = self._prefill(
                self.params, self.cache.arrays, np.int32(req.slot), ids,
                np.int32(start), np.int32(n))
            req.done += n
            self.telemetry.add("gen_prefill_tokens", n)
            if req.done < len(req.tokens):
                return
            with self._cond:
                self._prefilling.popleft()
            req.out.append(int(token))
        self.telemetry.ttft.observe(time.perf_counter() - req.t_submit)
        self.telemetry.add("gen_decode_tokens", 1)
        if req.keep_logits:
            req.logits.append(np.asarray(logits))
        if len(req.out) == req.max_new:
            self._finish(req)
        else:
            with self._cond:
                self._decoding[req.slot] = req

    def _decode_step(self) -> None:
        slots = self.cache.slots
        ids, active = np.zeros(slots, np.int32), np.zeros(slots, bool)
        for slot, req in self._decoding.items():
            ids[slot], active[slot] = req.out[-1], True
        rows = list(self._decoding.values())
        with span("decode", cat="gen", args={"rows": len(rows)}):
            self.cache.arrays, tokens, logits = self._decode(
                self.params, self.cache.arrays, ids, active)
            tokens = np.asarray(tokens)
            kept = (np.asarray(logits) if any(r.keep_logits for r in rows)
                    else None)
        self.telemetry.add("gen_decode_steps", 1)
        self.telemetry.add("gen_decode_rows", len(rows))
        self.telemetry.add("gen_decode_tokens", len(rows))
        for req in rows:
            req.out.append(int(tokens[req.slot]))
            if req.keep_logits:
                req.logits.append(kept[req.slot])
            if len(req.out) == req.max_new:
                self._finish(req)

    def _finish(self, req: _Request, error: BaseException | None = None
                ) -> None:
        """Take ``req`` out of its slot and resolve its future."""
        with self._cond:
            if req in self._prefilling:
                self._prefilling.remove(req)
            self._decoding.pop(req.slot, None)
            if error is None:
                self._service_s += 0.2 * (time.perf_counter() - req.t_submit
                                          - self._service_s)
        self.cache.give(req.slot)
        if error is not None:
            req.future.set_exception(error)
            return
        result = {"tokens": req.out}
        if req.keep_logits:
            result["logits"] = np.stack(req.logits)
        req.future.set_result(result)

    def _fail_all(self, error: BaseException) -> None:
        with self._cond:
            self._error = self._error or error
            queued = list(self._queue)
            self._queue.clear()
            held = [*self._prefilling, *self._decoding.values()]
        for req in held:
            self._finish(req, error)
        for req in queued:
            req.future.set_exception(error)


def is_generative(name: str) -> bool:
    """Whether registry model ``name`` is served by this generator."""
    from deepvision_tpu.models import get_model

    return isinstance(get_model(name), olmo_hybrid.OlmoHybridLM)


def load_generator(name: str, workdir: str | None = None,
                   **engine_kw) -> GenerationEngine:
    """A generator over registry model ``name``: its checkpoint's
    parameters where ``workdir`` holds one (``train.py`` writes it), else
    the model's own initialisation, as served (bf16)."""
    from deepvision_tpu.models import get_model
    from deepvision_tpu.serve.models import restore_state

    model = get_model(name)
    state = restore_state(name, workdir, model.sample_input())
    return GenerationEngine(model.config,
                            olmo_hybrid.serving_params(state.params),
                            **engine_kw)
