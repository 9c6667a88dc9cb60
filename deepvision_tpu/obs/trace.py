"""Lightweight span tracing with Chrome-trace-format export.

``jax.profiler`` answers "what did XLA do" at op granularity; this
module answers the coarser operator question the epoch/request loops
need — *what did step 1432 spend its time on* — with host-side spans
cheap enough to leave compiled into every loop:

    from deepvision_tpu.obs.trace import span, get_tracer

    get_tracer().enable()
    with span("h2d"):
        batch = next(feed)
    with span("step") as sp:
        out = compiled(state, batch)
        sp.device_sync(out)   # block_until_ready BEFORE the end stamp
    get_tracer().export("trace.json")   # chrome://tracing / Perfetto

Design points:

- **disabled-by-default, near-zero cost**: ``span()`` returns a shared
  no-op context manager unless the tracer is enabled or a
  ``jax.profiler`` trace is running, so the feed and step loops carry
  their spans unconditionally;
- **one clock with the device trace**: while a ``jax.profiler`` trace
  runs (whoever started it), a span is also a
  ``jax.profiler.TraceAnnotation`` named ``<cat>/<name>``
  (``serve/pack``, ``feed/host_next``, ``train/step``) with its scalar
  ``args`` as the event's stats, so it lands among the host events of
  the ``.xplane.pb`` beside the device's operations. A span that
  encloses other spans on its thread says so where it is opened
  (``encloses=True``: ``train/epoch``, ``train/eval``) and stays off
  the profiler: an event covering its children would take every idle
  gap that they should name;
- **monotonic clock** (``time.perf_counter``) — wall-clock steps from
  NTP can never produce negative spans;
- **thread-aware**: every span records its thread id/name and its
  nesting depth (a thread-local stack), so the producer thread's
  ``host_next``/``shard`` spans land on their own track;
- **explicit ``device_sync``**: JAX dispatch is asynchronous — a span
  closed right after a compiled call measures *enqueue*, not compute
  (the same lie jaxlint JX112 flags for ad-hoc ``time.perf_counter()``
  deltas). ``device_sync=`` (ctor kwarg) or ``sp.device_sync(out)``
  inserts ``jax.block_until_ready`` before the end timestamp;
- **ring buffer**: the most recent ``capacity`` spans are kept (bounded
  memory on long runs); export writes Chrome trace format JSON that
  loads directly in ``chrome://tracing`` and Perfetto. Overflow is
  never silent: evicted spans are counted (``dropped_spans``, the
  ``trace_dropped_spans`` obs counter) and the export carries the
  count in its metadata, so a truncated trace can't masquerade as a
  complete one;
- **sinks**: ``add_sink(fn)`` registers a per-span callback (the
  distributed spool writer and the flight recorder,
  ``obs/distributed.py``). Spans record whenever the tracer is enabled
  OR a sink is attached, so an always-on flight recorder doesn't
  require the in-memory ring/export machinery to be on;
- **retroactive spans**: :meth:`Tracer.record_span` records a span
  from explicit ``perf_counter`` stamps (host clock only: a profiler
  annotation cannot be back-dated) — for code that already times
  a region with its own clock reads (the serve engine's per-request
  queue-wait, measured as ``t_dispatch - t_submit``) and wants the
  interval on the trace without restructuring into a ``with`` block;
- **wall-clock calibration**: ``epoch_wall`` records the wall time of
  the monotonic trace zero, so a cross-process merger
  (``tools/trace_merge.py``) can align rings/spools from many
  processes onto one timeline;
- **process labels**: ``set_labels(role=..., host=..., generation=...)``
  stamps exports and spool headers so a merged fleet/cluster trace
  names its pid rows (``replica r1``, ``host 0 gen 2``);
- **start-up spans**: :func:`startup_span` (``startup/runtime``,
  ``startup/load_model``, ``startup/engine``, ``startup/state``,
  ``startup/compile``) always measures and keeps its interval
  (:meth:`Tracer.startup_spans`, a handful a process, capped), enabled
  or not, and is a profiler annotation like any span while a profile
  runs; ``deepvision_tpu.startup`` reads them into the ``[startup]``
  ready line and the benchmark's ``setup_runtime_s``.

:func:`summarize_chrome` turns an exported trace back into per-span
totals + a wall-time-attribution figure; ``tools/trace_summary.py`` is
its CLI.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
from collections import deque
from pathlib import Path

__all__ = ["Span", "Tracer", "format_labels", "get_tracer", "span",
           "startup_phase", "startup_span", "summarize_chrome"]

STARTUP_CAPACITY = 256  # start-up spans kept per process


class _NoopSpan:
    """Shared do-nothing span handed out while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def device_sync(self, value):
        return value


_NOOP = _NoopSpan()

_ANNOTATION = None  # jax.profiler.TraceAnnotation, bound on first use


def _profile_running() -> bool:
    """Is a ``jax.profiler`` trace running in this process? A process
    that never imported jax (the fleet router's parent) cannot be under
    one, and is not made to import it here."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION.is_enabled()


class Span:
    """One live ``with`` region; created by :meth:`Tracer.span` or
    :meth:`Tracer.timed`. After the region ``t0`` and ``dur`` hold its
    ``perf_counter`` start and length: the one pair of reads the ring,
    the caller's ``observe`` and (to the call's own cost) the profiler
    annotation share."""

    __slots__ = ("_tracer", "name", "cat", "args", "_sync", "_observe",
                 "_annotation", "t0", "dur")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: dict | None, device_sync, observe=None,
                 profiled: bool = False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._sync = device_sync
        self._observe = observe
        self._annotation = None
        if profiled:
            # scalars only: an annotation's stats are key=value text
            stats = {k: v for k, v in (args or {}).items()
                     if isinstance(v, (str, int, float))}
            self._annotation = _ANNOTATION(f"{cat}/{name}", **stats)

    def device_sync(self, value):
        """Mark ``value`` (array/pytree) to be ``block_until_ready``-ed
        before the span's end timestamp, so the span measures compute
        rather than async dispatch. Returns ``value`` for chaining."""
        self._sync = value
        return value

    def __enter__(self):
        self._tracer._push()
        if self._annotation is not None:
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._sync is not None:
            try:
                import jax

                jax.block_until_ready(self._sync)
            except Exception:
                pass  # a failed sync must not mask the body's exception
        self.dur = time.perf_counter() - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        depth = self._tracer._pop()
        if self._observe is not None:
            self._observe(self.dur)
        self._tracer._record(self.name, self.cat, self.t0, self.dur,
                             depth, self.args)
        return False


class _StartupSpan(Span):
    """A span of the program's own start-up: measured whether or not
    the tracer is on, and its interval kept by the tracer."""

    __slots__ = ()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._tracer._keep_startup(self.name, self.t0, self.dur)
        return False


class Tracer:
    """Ring buffer of completed spans + Chrome-trace export."""

    def __init__(self, capacity: int = 65536):
        self._lock = threading.Lock()
        self._events: deque[tuple] = deque(maxlen=capacity)
        self._capacity = capacity
        self._enabled = False
        self._epoch = time.perf_counter()  # trace time zero
        self.epoch_wall = time.time()      # wall clock of that zero
        self._local = threading.local()
        self._sinks: list = []
        self._dropped = 0          # ring evictions since clear()
        self._drop_counter = None  # lazily bound obs counter
        self._labels: dict = {}
        # (name, perf_counter start, seconds) of each start-up span;
        # clear() leaves them: start-up happens once
        self._startup: list[tuple[str, float, float]] = []
        self.startup_dropped = 0

    # -- lifecycle -------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def active(self) -> bool:
        """Spans record when the ring is enabled OR a sink is attached
        (a spool/flight-recorder sink keeps spans flowing without the
        in-memory export machinery)."""
        return self._enabled or bool(self._sinks)

    @property
    def wall_offset(self) -> float:
        """Add to a ``time.time()`` stamp to put it on the spans'
        ``perf_counter`` clock (the trace zero's two readings)."""
        return self._epoch - self.epoch_wall

    @property
    def dropped_spans(self) -> int:
        """Spans evicted from the ring since the last :meth:`clear` —
        the count the export metadata reports so truncation is never
        silent."""
        with self._lock:
            return self._dropped

    def enable(self, clear: bool = True) -> "Tracer":
        if clear:
            self.clear()
        self._enabled = True
        return self

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0
            self._epoch = time.perf_counter()
            self.epoch_wall = time.time()

    def add_sink(self, fn) -> None:
        """Register ``fn(record: dict)`` called (under the tracer lock,
        in recording order) for every completed span. Keep sinks cheap:
        they run on the recording thread."""
        with self._lock:
            if fn not in self._sinks:
                self._sinks.append(fn)

    def remove_sink(self, fn) -> None:
        with self._lock:
            if fn in self._sinks:
                self._sinks.remove(fn)

    def set_labels(self, **labels) -> None:
        """Stamp process identity (``role`` / ``host`` / ``generation``)
        onto exports and spool headers; a cross-process merge uses them
        to name this process's pid row."""
        self._labels.update({k: v for k, v in labels.items()
                             if v is not None})

    @property
    def labels(self) -> dict:
        return dict(self._labels)

    # -- recording -------------------------------------------------------
    def span(self, name: str, cat: str = "app", args: dict | None = None,
             device_sync=None, *, encloses: bool = False):
        """Context manager timing its body; the shared no-op while the
        tracer is inactive and no profile runs. ``encloses``: this span
        wraps other spans on its thread, so it stays off the profiler
        (see the module docstring)."""
        profiled = not encloses and _profile_running()
        if not (profiled or self.active):
            return _NOOP
        return Span(self, name, cat, args, device_sync, profiled=profiled)

    def timed(self, name: str, cat: str = "app", args: dict | None = None,
              observe=None) -> Span:
        """A span that always measures, for a caller that keeps the
        seconds whatever else records them: it reads ``t0``/``dur`` back
        after the region, or hands ``observe`` (called with the seconds
        as the span closes) a sink of its own. The serve engine's phases
        use it: one measurement for the registry's histogram, the ring
        and the profile."""
        return Span(self, name, cat, args, None, observe=observe,
                    profiled=_profile_running())

    def startup(self, name: str) -> Span:
        """``startup/<name>``: a span of the program's own start-up. It
        always measures and is kept (:meth:`startup_spans`) whether or
        not the tracer is enabled; it is on the ring when the tracer is,
        and a profiler annotation while a profile runs."""
        return _StartupSpan(self, name, "startup", None, None,
                            profiled=_profile_running())

    def _keep_startup(self, name: str, t0: float, dur: float) -> None:
        with self._lock:
            if len(self._startup) < STARTUP_CAPACITY:
                self._startup.append((f"startup/{name}", t0, dur))
            else:
                self.startup_dropped += 1

    def startup_spans(self) -> list[tuple[str, float, float]]:
        """Every start-up span closed so far, in closing order:
        ``(name, perf_counter start, seconds)``."""
        with self._lock:
            return list(self._startup)

    def _push(self) -> None:
        self._local.depth = getattr(self._local, "depth", 0) + 1

    def _pop(self) -> int:
        depth = getattr(self._local, "depth", 1) - 1
        self._local.depth = depth
        return depth  # 0 for outermost spans

    def record_span(self, name: str, t0: float, t1: float,
                    cat: str = "app", args: dict | None = None) -> None:
        """Retroactively record a completed span from explicit
        ``time.perf_counter()`` stamps (same clock as live spans).
        Used where the timing already exists as stamps — the serve
        engine's per-request queue-wait/device/postprocess intervals —
        so the trace carries them without a ``with`` rewrite."""
        if not self.active:
            return
        self._emit(name, cat, t0, max(0.0, t1 - t0), 0, args)

    def _record(self, name: str, cat: str, t0: float, dur: float,
                depth: int, args: dict | None) -> None:
        if not self.active:
            return  # deactivated while the span was open: drop it
        self._emit(name, cat, t0, dur, depth, args)

    def _emit(self, name: str, cat: str, t0: float, dur: float,
              depth: int, args: dict | None) -> None:
        thread = threading.current_thread()
        event = (name, cat, t0 - self._epoch, dur,
                 thread.ident, thread.name, depth, args)
        with self._lock:
            if self._enabled:
                if len(self._events) >= self._capacity:
                    # the deque evicts silently; the count keeps the
                    # truncation honest ("no silent caps")
                    self._dropped += 1
                    self._inc_drop_counter()
                self._events.append(event)
            if self._sinks:
                rec = self._sink_record(event)
                for sink in self._sinks:
                    try:
                        sink(rec)
                    except Exception:
                        pass  # a broken sink must never fail the loop

    @staticmethod
    def _sink_record(event: tuple) -> dict:
        name, cat, ts, dur, tid, tname, depth, args = event
        rec = {"name": name, "cat": cat, "ts": ts, "dur": dur,
               "tid": tid, "tname": tname, "depth": depth}
        if args:
            rec["args"] = args
        return rec

    def _inc_drop_counter(self) -> None:
        if self._drop_counter is None:
            from deepvision_tpu.obs.metrics import default_registry

            self._drop_counter = default_registry().counter(
                "trace_dropped_spans")
        self._drop_counter.inc()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- export ----------------------------------------------------------
    def chrome_events(self) -> list[dict]:
        """Chrome trace event dicts ("X" complete events, ts/dur in
        microseconds) + thread-name metadata events."""
        with self._lock:
            events = list(self._events)
        pid = os.getpid()
        out: list[dict] = []
        threads: dict[int, str] = {}
        for name, cat, ts, dur, tid, tname, depth, args in events:
            threads.setdefault(tid, tname)
            out.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": round(ts * 1e6, 3), "dur": round(dur * 1e6, 3),
                "pid": pid, "tid": tid,
                "args": {**(args or {}), "depth": depth},
            })
        for tid, tname in threads.items():
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": tname}})
        if self._labels:
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0, "args": {"name": format_labels(
                            self._labels)}})
        return out

    def export(self, path: str | Path) -> int:
        """Write ``{"traceEvents": [...]}`` (loads in chrome://tracing
        and Perfetto); returns the number of span events written. The
        ``metadata`` block carries ``trace_dropped_spans`` — how many
        spans the ring evicted since the last clear — so a truncated
        trace is labelled as such instead of silently passing for the
        whole story."""
        events = self.chrome_events()
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        meta = {"trace_dropped_spans": self.dropped_spans,
                "complete": self.dropped_spans == 0,
                "pid": os.getpid(), "epoch_wall": self.epoch_wall}
        if self._labels:
            meta["labels"] = dict(self._labels)
        path.write_text(json.dumps(
            {"traceEvents": events, "displayTimeUnit": "ms",
             "metadata": meta}))
        return sum(1 for e in events if e.get("ph") == "X")


def format_labels(labels: dict) -> str:
    """Human row name for a labelled process: ``role`` first, then the
    cluster identity — ``"replica r1"``, ``"host 0 gen 2"``."""
    parts = []
    role = labels.get("role")
    if role:
        parts.append(str(role))
    host = labels.get("host")
    if host is not None and (not role or str(role) != f"host{host}"):
        parts.append(f"host {host}")
    gen = labels.get("generation")
    if gen is not None:
        g = str(gen)
        parts.append(g if g.startswith(("gen", "replay"))
                     else f"gen {g}")
    for k in sorted(labels):
        if k not in ("role", "host", "generation"):
            parts.append(f"{k}={labels[k]}")
    return " ".join(parts) or "process"


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer the loops' ``span(...)`` calls feed."""
    return _TRACER


def span(name: str, cat: str = "app", args: dict | None = None,
         device_sync=None, *, encloses: bool = False):
    """``with span("step"): ...`` against the default tracer."""
    return _TRACER.span(name, cat=cat, args=args, device_sync=device_sync,
                        encloses=encloses)


def startup_span(name: str) -> Span:
    """``with startup_span("runtime"): ...`` against the default tracer
    (:meth:`Tracer.startup`)."""
    return _TRACER.startup(name)


def startup_phase(name: str):
    """Decorator: each call of the function is one ``startup/<name>``
    span."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            with _TRACER.startup(name):
                return fn(*args, **kwargs)
        return timed
    return wrap


# ------------------------------------------------------- trace analysis


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of possibly-overlapping [start, end) intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(intervals, windows) -> list[tuple[float, float]]:
    """Intersect merged ``intervals`` with merged ``windows``."""
    out = []
    for s, e in intervals:
        for ws, we in windows:
            lo, hi = max(s, ws), min(e, we)
            if lo < hi:
                out.append((lo, hi))
    return _merge(out)


def summarize_chrome(trace: dict | list, wall_span: str = "epoch") -> dict:
    """Per-span time attribution from a Chrome-trace event list.

    ``wall_span`` names the enclosing span whose total duration is the
    wall clock being attributed (default ``"epoch"`` — the trainer's
    outermost per-epoch span). Attribution is the UNION of the other
    spans' intervals on the wall spans' threads, clipped to the wall
    windows — nesting and overlap never double-count. When no
    ``wall_span`` events exist, the full [first start, last end) extent
    of the trace is the wall.

    Returns ``{"spans": {name: {count,total_ms,mean_ms,max_ms,
    pct_of_wall}}, "wall_ms", "attributed_ms", "coverage", "wall_span"}``.
    """
    events = trace.get("traceEvents", []) if isinstance(trace, dict) \
        else trace
    xs = [e for e in events if e.get("ph") == "X"]
    per: dict[str, dict] = {}
    for e in xs:
        d = per.setdefault(e["name"], {"count": 0, "total_us": 0.0,
                                       "max_us": 0.0})
        d["count"] += 1
        d["total_us"] += e["dur"]
        d["max_us"] = max(d["max_us"], e["dur"])

    walls = [e for e in xs if e["name"] == wall_span]
    if walls:
        wall_tids = {(e.get("pid"), e.get("tid")) for e in walls}
        windows = _merge([(e["ts"], e["ts"] + e["dur"]) for e in walls])
    elif xs:
        wall_tids = {(e.get("pid"), e.get("tid")) for e in xs}
        windows = _merge([(min(e["ts"] for e in xs),
                           max(e["ts"] + e["dur"] for e in xs))])
    else:
        wall_tids, windows = set(), []
    wall_us = sum(e - s for s, e in windows)
    leaves = _merge([(e["ts"], e["ts"] + e["dur"]) for e in xs
                     if e["name"] != wall_span
                     and (e.get("pid"), e.get("tid")) in wall_tids])
    attributed_us = sum(e - s for s, e in _clip(leaves, windows))

    spans = {}
    for name, d in sorted(per.items(), key=lambda kv: -kv[1]["total_us"]):
        spans[name] = {
            "count": d["count"],
            "total_ms": round(d["total_us"] / 1e3, 3),
            "mean_ms": round(d["total_us"] / d["count"] / 1e3, 3),
            "max_ms": round(d["max_us"] / 1e3, 3),
            "pct_of_wall": (round(d["total_us"] / wall_us * 100.0, 1)
                            if wall_us else 0.0),
        }
    return {
        "spans": spans,
        "wall_span": wall_span,
        "wall_ms": round(wall_us / 1e3, 3),
        "attributed_ms": round(attributed_us / 1e3, 3),
        "coverage": round(attributed_us / wall_us, 4) if wall_us else 0.0,
    }
