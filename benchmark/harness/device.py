"""The device a run is on: what answered, its peaks, its memory, and
how many programs it compiled."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def require_tpu(chips: int) -> dict:
    """First touch of JAX, through the program's own start-up (which
    places the persistent compile cache inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` places it): a run without a TPU, or
    with fewer chips than the cell asks for, ends here with a non-zero
    exit and no result. -> {"platform", "kind", "count"}."""
    from deepvision_tpu.startup import init_runtime

    info = init_runtime(require_tpu=True)
    cache_every_program()
    if info["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s) and JAX found "
                         f"{info['count']}")
    return info


def cache_every_program() -> None:
    """Keep every compiled program in the persistent cache, the
    sub-second ones too (JAX's default leaves out what compiled in under
    a second, which is some 30 s of each warm process, PERF.md PR 21):
    set-up is what every run of every later check pays."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peaks(kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["peaks"]
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)}): add "
                       "the chip with its source")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip: live arrays at their peak plus
    what the runtime reserved for the executables' temporaries.

    On this runtime ``peak_bytes_in_use`` counts arrays only: after
    ResNet-50 b256 train steps it read 0.47 GB while the executable's
    ``memory_analysis()`` gave 8.81 GB of temporaries, and
    ``peak_bytes_reserved`` read 8.78 GB (PERF.md, Findings, PR 23). The
    two are disjoint, so their sum is the peak a deployment has to fit."""
    worst = 0
    for d in devices:
        s = d.memory_stats() or {}
        worst = max(worst, int(s.get("peak_bytes_in_use", 0))
                    + int(s.get("peak_bytes_reserved", 0)))
    return worst


class CompileTally:
    """Seconds and count of XLA compilations in this process, from
    ``jax.monitoring`` (a fetch from the persistent cache counts its
    seconds too). ``count`` at two instants brackets a window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration_secs
            self.count += 1
