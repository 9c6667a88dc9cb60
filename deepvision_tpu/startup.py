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
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEVICE_TAG = "[device] "
COMPILE_TAG = "[compile] "


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


# totals of this process's XLA compilations, fed by jax.monitoring
_TALLY: dict = {}


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _TALLY["compile_s"] += duration_secs


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _TALLY["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _TALLY["cache_misses"] += 1


def _print_tally() -> None:
    print(COMPILE_TAG + json.dumps(
        {**_TALLY, "compile_s": round(_TALLY["compile_s"], 2)}),
        file=sys.stderr, flush=True)


def init_runtime(platform: str | None = None, *,
                 require_tpu: bool = False) -> dict:
    """An entry point's first touch of JAX: pin ``platform`` when one
    was asked for, place the compile cache, initialise the backend and
    say on stderr what answered — one ``[device] {...}`` line, the same
    in ``train.py``, ``serve.py`` and ``bench.py``, so a run that fell
    back to the CPU cannot pass for a chip run. At exit a ``[compile]
    {...}`` line totals the seconds spent in XLA compilation (or in
    fetching executables from the persistent cache) and the cache's
    hits and misses.

    ``require_tpu`` makes anything but a TPU an error (measurement
    paths: a number from the CPU under a device metric's name is worse
    than no number). -> ``{"platform", "kind", "count"}``."""
    import jax
    from jax import monitoring

    if platform:
        jax.config.update("jax_platforms", platform)
    cache = compile_cache_dir()
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", cache)
    if not _TALLY:  # once per process: the listener registry is global
        _TALLY.update(compile_s=0.0, cache_hits=0, cache_misses=0)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        atexit.register(_print_tally)

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
