"""Opt-in ``jax.profiler`` sessions + device-memory gauges.

Two hooks the loops consume:

- :class:`ProfileWindow` — the ``train.py --profile-steps A:B``
  mechanism: start a ``jax.profiler`` trace just before global step A,
  stop it after step B, exactly once per run. Profiling every step of a
  long run is useless (gigabytes of XPlane) — the window captures the
  handful of steady-state steps that actually get read. All profiler
  errors degrade to a one-line warning, never a crashed run.
- :func:`profile_session` — whole-process bracket for ``serve.py
  --profile-dir`` (start at boot, stop at shutdown).
- :func:`device_memory_stats` / :func:`sample_memory_gauges` — HBM
  accounting from ``jax.local_devices()[i].memory_stats()``, surfaced
  as ``mem_*`` gauges in the obs registry and as per-epoch ``mem_*``
  logged metrics. CPU backends report no memory_stats — the samplers
  return ``{}`` there (graceful no-op; the gauges only exist where a
  real device backs them, so the driver's on-chip run is where these
  numbers appear).
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

from deepvision_tpu.obs.metrics import Registry, default_registry

__all__ = [
    "ProfileWindow",
    "device_memory_stats",
    "profile_session",
    "sample_memory_gauges",
]

# memory_stats() fields promoted to metrics (names vary by backend;
# the first three are the PJRT-stable core: live HBM, high-water mark,
# cap). `peak_bytes_in_use` counts live arrays only; an executable's
# temporaries show in `peak_bytes_reserved` (8.8 GB of a ResNet-50 b256
# step on a v5e, PERF.md Findings, PR 23): a process's peak is the two
# together
_MEM_FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
               "peak_bytes_reserved")


def device_memory_stats() -> dict[str, float]:
    """``{"mem_bytes_in_use_dev0": ..., ...}`` across local devices;
    ``{}`` when the backend exposes no memory stats (CPU)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return {}
    out: dict[str, float] = {}
    for i, d in enumerate(devices):
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for field in _MEM_FIELDS:
            if field in stats:
                out[f"mem_{field}_dev{i}"] = float(stats[field])
    return out


def sample_memory_gauges(registry: Registry | None = None) -> dict:
    """Sample device memory into ``mem_*`` gauges on ``registry``
    (default: the process registry) and return the sampled dict — the
    same dict the Trainer logs per epoch as ``mem_*`` metrics."""
    stats = device_memory_stats()
    if stats:
        reg = registry if registry is not None else default_registry()
        for name, value in stats.items():
            reg.gauge(name).set(value)
    return stats


@contextlib.contextmanager
def profile_session(logdir: str | Path | None):
    """Bracket a whole region with one ``jax.profiler`` trace; yields
    True while a trace is live, False when disabled/unavailable."""
    if not logdir:
        yield False
        return
    started = False
    try:
        import jax

        Path(logdir).mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(logdir))
        started = True
        print(f"[obs] jax.profiler trace -> {logdir}", file=sys.stderr,
              flush=True)
    except Exception as e:
        print(f"[obs] profiler unavailable ({e!r}); continuing without",
              file=sys.stderr, flush=True)
    try:
        yield started
    finally:
        if started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                print(f"[obs] profiler stop failed ({e!r})",
                      file=sys.stderr, flush=True)


class ProfileWindow:
    """``--profile-steps A:B``: profile global steps A..B (inclusive),
    once. ``on_step(step)`` is called with the 0-based global index of
    the step ABOUT to run; the trace starts when ``step == A`` arrives
    and stops as soon as a step past B is seen (or at :meth:`close`)."""

    def __init__(self, spec: str, logdir: str | Path):
        try:
            a, _, b = spec.partition(":")
            self.start, self.stop = int(a), int(b)
        except ValueError:
            raise ValueError(
                f"--profile-steps wants 'A:B' (ints), got {spec!r}"
            ) from None
        if self.start < 0 or self.stop < self.start:
            raise ValueError(
                f"--profile-steps wants 0 <= A <= B, got {spec!r}")
        self.logdir = Path(logdir)
        self.active = False
        self.done = False

    def on_step(self, step: int) -> None:
        if self.done:
            return
        if not self.active and step >= self.start:
            self.active = self._start()
            self.done = not self.active  # profiler unavailable: give up
        elif self.active and step > self.stop:
            self._stop()

    def close(self) -> None:
        """Stop a still-open window (run ended inside [A, B])."""
        if self.active:
            self._stop()
        self.done = True

    def _start(self) -> bool:
        try:
            import jax

            self.logdir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.logdir))
            print(f"[obs] profiling steps {self.start}..{self.stop} -> "
                  f"{self.logdir}", flush=True)
            return True
        except Exception as e:
            print(f"[obs] profiler unavailable ({e!r}); --profile-steps "
                  "ignored", flush=True)
            return False

    def _stop(self) -> None:
        try:
            import jax

            jax.profiler.stop_trace()
            print(f"[obs] profile window closed -> {self.logdir}",
                  flush=True)
        except Exception as e:
            print(f"[obs] profiler stop failed ({e!r})", flush=True)
        self.active = False
        self.done = True
