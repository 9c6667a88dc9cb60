"""Process start-up shared by every entry point: which device this
process runs on, where its compiled programs are cached, and which chip
a child process is confined to.

Importable without JAX. The fleet router (``serve.py --fleet``), the
cluster supervisor (``train_dist.py --supervise``), ``bench.py serve
--sweep`` and ``chip_smoke.py`` are parents of processes that need the
chip, and a chip belongs to one process at a time — so they take their
facts from :func:`probe_devices` (a child that exits before any worker
starts) and never initialise a backend themselves. Only
:func:`init_runtime` imports JAX, inside the call.
"""

from __future__ import annotations

import atexit
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEVICE_TAG = "[device] "
COMPILE_TAG = "[compile] "
READY_TAG = "[startup] "


def compile_cache_dir(environ=None) -> str | None:
    """The persistent compile-cache directory this program must set in
    code: ``None`` when ``JAX_COMPILATION_CACHE_DIR`` already places it
    (JAX reads the variable itself), otherwise one fixed path inside the
    checkout. The path is part of the cache key, so it is resolved from
    this file's location — never from a temp dir, a pid or a clock —
    and every process of a command lands in the same directory."""
    environ = os.environ if environ is None else environ
    if environ.get(CACHE_ENV):
        return None
    return str(REPO_ROOT / ".jax_cache")


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PHASES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
           COMPILE_EVENT: "compile"}
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_COUNTS = ("traces", "lowerings", "compiles", "cache_hits", "cache_misses")
_SECONDS = ("trace_s", "lower_s", "compile_s", "fetch_s")
_IMPORTED = time.perf_counter()


class _Frame:
    """One trace, lowering or backend compile open on a thread."""

    __slots__ = ("phase", "name", "inner_s", "fetch_s", "hits", "misses")

    def __init__(self, phase: str, name: str):
        self.phase, self.name = phase, name
        self.inner_s = self.fetch_s = 0.0   # nested programs; cache fetch
        self.hits = self.misses = 0


def _fold(programs: dict, phase: str, name: str, own_s: float,
          fetch_s: float, hits: int, misses: int) -> None:
    p = programs.get(name)
    if p is None:
        p = programs[name] = dict.fromkeys(_COUNTS, 0) | dict.fromkeys(
            _SECONDS, 0.0)
    if phase == "trace":
        p["traces"] += 1
        p["trace_s"] += own_s
    elif phase == "lower":
        p["lowerings"] += 1
        p["lower_s"] += own_s
    else:
        p["compiles"] += 1
        p["compile_s"] += own_s - fetch_s
        p["fetch_s"] += fetch_s
        p["cache_hits"] += hits
        p["cache_misses"] += misses


class CompileRecord:
    """What this process traced, lowered and compiled (or fetched from
    the persistent cache), per program, from ``jax.monitoring``.

    A program is JAX's ``fun_name`` of its lowering and compile
    (``jit(classification_train_step)``, ``jit(multiply)``); a trace's
    bare name is put in the same form. Each event is opened by the
    scalar JAX records at its start (which names it) and closed by its
    time span, on a stack per thread. A trace inside another trace or a
    lowering is the enclosing program's (jnp functions are jitted and
    traced inline); a lowering or compile nested in another event is a
    program of its own and its seconds leave the enclosing one's, so
    the seconds of all programs add up to the time spent in any of them.
    Cache hits, misses and fetch seconds fire inside a backend compile
    on its thread and are charged to it; a compile's seconds exclude
    its fetch.

    Totals are kept by name; the intervals (start and end on the
    tracer's ``perf_counter`` clock, JAX's ``time.time()`` stamps
    converted by one offset) are kept up to ``capacity``, the earliest
    first, and the rest counted in ``dropped``, so that
    :meth:`summary` can cut at an instant."""

    def __init__(self, capacity: int = 1 << 15):
        from deepvision_tpu.obs.trace import get_tracer

        self._offset = get_tracer().wall_offset
        self._capacity = capacity
        self._lock = threading.Lock()
        self._local = threading.local()
        self.programs: dict[str, dict] = {}
        self.intervals: list[tuple] = []
        self.dropped = 0
        self.ready_at: float | None = None
        self._recompiled: set[str] = set()

    # -- jax.monitoring listeners ----------------------------------------
    def install(self) -> "CompileRecord":
        from jax import monitoring

        monitoring.register_scalar_listener(self._on_start)
        monitoring.register_event_time_span_listener(self._on_end)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_seconds)
        return self

    def uninstall(self) -> None:
        from jax import monitoring

        monitoring.unregister_scalar_listener(self._on_start)
        monitoring.unregister_event_time_span_listener(self._on_end)
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_seconds)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _on_start(self, event: str, value, **kw) -> None:
        phase = _PHASES.get(event)
        if phase is not None:
            self._stack().append(_Frame(phase, str(kw.get("fun_name"))))

    def _open_compile(self) -> _Frame | None:
        stack = self._stack()
        return stack[-1] if stack and stack[-1].phase == "compile" else None

    def _on_event(self, event: str, **_kw) -> None:
        if event in (HIT_EVENT, MISS_EVENT):
            frame = self._open_compile()
            if frame is not None:
                if event == HIT_EVENT:
                    frame.hits += 1
                else:
                    frame.misses += 1

    def _on_seconds(self, event: str, secs: float, **_kw) -> None:
        if event == FETCH_EVENT:
            frame = self._open_compile()
            if frame is not None:
                frame.fetch_s += secs

    def _on_end(self, event: str, start: float, end: float, **kw) -> None:
        phase = _PHASES.get(event)
        if phase is None:
            return
        stack = self._stack()
        name = str(kw.get("fun_name"))
        frame = (stack.pop() if stack and stack[-1].phase == phase
                 else _Frame(phase, name))
        parent = stack[-1] if stack else None
        dur = end - start
        if phase == "trace":
            if parent is not None:      # inline: the enclosing program's
                parent.inner_s += frame.inner_s
                return
            name = f"jit({name})"
        elif parent is not None:
            parent.inner_s += dur
        t0 = start + self._offset
        own = max(0.0, dur - frame.inner_s)
        with self._lock:
            _fold(self.programs, phase, name, own, frame.fetch_s,
                  frame.hits, frame.misses)
            if len(self.intervals) < self._capacity:
                self.intervals.append((t0, t0 + dur, phase, name, own,
                                       frame.fetch_s, frame.hits,
                                       frame.misses))
            else:
                self.dropped += 1
            late = (phase == "compile" and self.ready_at is not None
                    and t0 >= self.ready_at)
            first = late and name not in self._recompiled
            if first:
                self._recompiled.add(name)
        if late:
            from deepvision_tpu.obs.metrics import default_registry

            default_registry().counter("compiles_after_ready").inc()
            if first:
                print(f"{READY_TAG}warning: {name} compiled after the "
                      "process was ready (obs counter "
                      "compiles_after_ready)", file=sys.stderr, flush=True)

    def declare_ready(self, now: float) -> bool:
        """Mark ``now`` as the instant the process became ready; False
        where it already was."""
        with self._lock:
            if self.ready_at is not None:
                return False
            self.ready_at = now
            return True

    # -- reading ---------------------------------------------------------
    def summary(self, until: float | None = None) -> dict:
        """Totals over programs, the program count (programs compiled
        or fetched) and the ten programs with the most seconds. ``until`` (a
        ``perf_counter`` instant) keeps the events that ended by then;
        ``complete`` says whether the kept intervals cover that cut."""
        with self._lock:
            if until is None:
                programs = {k: dict(v) for k, v in self.programs.items()}
                complete = not self.dropped
            else:
                programs = {}
                for t0, t1, *event in self.intervals:
                    if t1 <= until:
                        _fold(programs, *event)
                complete = not self.dropped or (
                    bool(self.intervals) and self.intervals[-1][1] > until)
        out = {k: sum(p[k] for p in programs.values())
               for k in _COUNTS + _SECONDS}
        seconds = {k: sum(p[s] for s in _SECONDS)
                   for k, p in programs.items()}
        out["programs"] = sum(1 for p in programs.values() if p["compiles"])
        out["top"] = [[k, round(v, 3)] for k, v in sorted(
            seconds.items(), key=lambda kv: -kv[1])[:10]]
        out["complete"] = complete
        return out


_RECORD: CompileRecord | None = None


def compile_record() -> CompileRecord | None:
    """This process's record, from :func:`init_runtime` on."""
    return _RECORD


def process_start() -> float:
    """This process's start as the OS records it, on the
    ``perf_counter`` clock, to the kernel's clock tick (10 ms at the
    usual USER_HZ of 100); where ``/proc`` cannot say, the import of
    this module."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED
    return time.perf_counter() - age


def _union_s(intervals) -> float:
    total, reach = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > reach:
            total += t1 - max(t0, reach)
            reach = t1
    return total


def startup_report(until: float | None = None) -> dict | None:
    """The start-up spans' seconds by name (``startup/runtime``, ...)
    beside the compile record's :meth:`CompileRecord.summary`, both cut
    at ``until`` when given; ``complete`` is false where either
    overflowed its cap. None before :func:`init_runtime`."""
    if _RECORD is None:
        return None
    from deepvision_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    report = _RECORD.summary(until)
    for name, t0, dur in tracer.startup_spans():
        if until is None or t0 + dur <= until:
            report[name] = report.get(name, 0.0) + dur
    report["complete"] &= not tracer.startup_dropped
    return report


def mark_ready() -> None:
    """Declare this process ready: its first step is dispatched, or its
    engine warm and taking requests. Prints one ``[startup] {...}`` line
    on stderr, once a process: seconds since the process started, each
    start-up span's seconds, the record's trace, lower, compile and
    fetch seconds and program count, and the seconds no start-up span
    covers. From then on a backend compile counts in the obs counter
    ``compiles_after_ready`` and names its program in a warning line."""
    now = time.perf_counter()
    if _RECORD is None or not _RECORD.declare_ready(now):
        return
    from deepvision_tpu.obs.trace import get_tracer

    start = process_start()
    report = startup_report()
    spans = [(t0, t0 + dur) for _, t0, dur in get_tracer().startup_spans()]
    line = {"since_start_s": now - start,
            **{k: v for k, v in report.items() if k.startswith("startup/")},
            **{k: report[k] for k in _SECONDS + ("programs",)},
            "unspanned_s": now - start - _union_s(spans)}
    print(READY_TAG + json.dumps(
        {k: round(v, 3) if isinstance(v, float) else v
         for k, v in line.items()}), file=sys.stderr, flush=True)


def _print_compile_line() -> None:
    s = _RECORD.summary()
    print(COMPILE_TAG + json.dumps(
        {"compile_s": round(s["compile_s"] + s["fetch_s"], 2),
         "cache_hits": s["cache_hits"], "cache_misses": s["cache_misses"],
         "top": s["top"]}), file=sys.stderr, flush=True)


def init_runtime(platform: str | None = None, *,
                 require_tpu: bool = False) -> dict:
    """An entry point's first touch of JAX: pin ``platform`` when one
    was asked for, place the compile cache, initialise the backend and
    say on stderr what answered — one ``[device] {...}`` line, the same
    in ``train.py``, ``serve.py`` and ``bench.py``, so a run that fell
    back to the CPU cannot pass for a chip run. From here on the
    process keeps its :class:`CompileRecord`, and at exit a ``[compile]
    {...}`` line totals the seconds spent in XLA compilation (or in
    fetching executables from the persistent cache), the cache's hits
    and misses, and the ten programs with the most seconds. The call is
    the ``startup/runtime`` span: from ``import jax`` to the devices
    answering.

    ``require_tpu`` makes anything but a TPU an error (measurement
    paths: a number from the CPU under a device metric's name is worse
    than no number). -> ``{"platform", "kind", "count"}``."""
    global _RECORD
    from deepvision_tpu.obs.trace import startup_span

    with startup_span("runtime"):
        import jax

        if platform:
            jax.config.update("jax_platforms", platform)
        cache = compile_cache_dir()
        if cache is not None:
            jax.config.update("jax_compilation_cache_dir", cache)
        if _RECORD is None:  # once a process: JAX's listeners are global
            _RECORD = CompileRecord().install()
            atexit.register(_print_compile_line)
        devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    print(DEVICE_TAG + json.dumps(info), file=sys.stderr, flush=True)
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: JAX answered with {info['count']} "
            f"{info['platform']!r} device(s) (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); this path measures "
            "the chip and does not fall back to another backend")
    return info


def tagged_json(text: str, tag: str) -> dict | None:
    """The JSON object of the LAST line of ``text`` that starts with
    ``tag`` (the ``[device]`` / ``[compile]`` lines above), or None."""
    for line in reversed(text.splitlines()):
        if line.startswith(tag):
            return json.loads(line[len(tag):])
    return None


_PROBE = ("import json, jax; d = jax.devices(); "
          "print(json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))")


def probe_devices(timeout_s: float = 120.0) -> dict:
    """What JAX finds on this machine, asked in a child process that
    has exited — and released the chip — by the time this returns.
    The parent never imports JAX. -> ``{"platform", "kind", "count"}``;
    a child that cannot initialise a backend raises RuntimeError with
    the end of its stderr."""
    try:
        proc = subprocess.run([sys.executable, "-c", _PROBE],
                              capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(
            f"device probe did not answer within {timeout_s:.0f}s "
            "(is another process holding the chip?)") from e
    if proc.returncode != 0:
        raise RuntimeError(
            f"device probe failed (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-800:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def chip_env(slot: int, chips: int) -> dict[str, str]:
    """Environment that confines one child process to chip ``slot`` of
    a host with ``chips`` TPU chips (libtpu reads these at backend
    initialisation; verified on a four-chip v5e host, see PERF.md):
    the child sees exactly one device and leaves the others free for
    its siblings. Raises when the host has no chip left for the slot —
    a second process on a held chip fails or hangs, so the refusal has
    to come before the spawn."""
    if not 0 <= slot < chips:
        raise ValueError(
            f"process {slot + 1} needs a chip of its own and this host "
            f"has {chips}: a TPU chip belongs to one process at a time")
    return {
        "TPU_VISIBLE_CHIPS": str(slot),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
