"""From a profiler trace (``.xplane.pb``) to device numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else (no
TensorFlow). What it takes from a trace of a TPU run (looked at by hand
first, PR 23): each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event for every HLO operation that ran, with its
start and duration on the device; ``XLA Modules`` holds one event for
every executable launched; the host's threads are the lines of plane
``/host:CPU`` (with the Python tracer's function events among them).
Times are nanoseconds from the start of the profile.

- busy: the union of the ``XLA Ops`` intervals of a chip;
- window: from the first operation's start to the last one's end;
- idle gaps: what lies between, each named after the host event that
  overlaps it most (the host's and the device's clocks agree to about a
  millisecond, so only gaps of a millisecond and more are named);
- operations that end after the host called ``stop_trace`` are left out:
  writing the profile stalls the host, and the device with it;
- convolution time: operations that XLA:TPU runs on the matrix unit:
  bare ``convolution`` ops and ``kind=kOutput`` fusions (a convolution
  or matrix multiply with the elementwise work fused behind it).

``tests/benchmark/test_reduce.py`` checks it against the small trace
recorded beside this file.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_NAME = re.compile(r"^%?([^ =]+)")
_CONV = re.compile(r"kind=kOutput|kind=kConv| convolution\(")
GAP_FLOOR_NS = 1_000_000


def newest_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_name(event_name: str) -> str:
    """``%fusion.15 = bf16[...] fusion(...)`` -> ``fusion.15``."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name[:64]


def is_convolution(event_name: str) -> bool:
    return bool(_CONV.search(event_name))


def union(intervals: list) -> tuple:
    """Sorted ``(start, end)`` pairs -> (covered length, gaps between)."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def read(path: str) -> dict:
    """-> {"devices": {plane: {"ops": [(start, dur, name)], "modules":
    [...]}}, "host": [(start, dur, name)]}, times in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [(e.start_ns, e.duration_ns, e.name)
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [(e.start_ns, e.duration_ns, e.name)
                                      for e in line.events]
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.duration_ns, e.name)
                            for e in line.events)
    return {"devices": devices, "host": host}


def _name_gaps(gaps: list, host: list) -> dict:
    """Idle seconds by what the host was doing: each gap goes to the
    host event that overlaps it most among those no longer than three
    times the gap (a thread parked in a wait for the whole run says
    nothing about one gap), else to the tightest event covering it."""
    named: dict = {}
    big = [(s, e) for s, e in gaps if e - s >= GAP_FLOOR_NS]
    # an event shorter than half the floor cannot say much about a gap
    spans = sorted(h for h in host if h[1] >= GAP_FLOOR_NS // 2)
    for s, e in big:
        best, best_overlap = None, 0
        cover, cover_len = "no host event", None
        for hs, hd, hn in spans:
            if hs >= e:
                break
            he = hs + hd
            if he <= s:
                continue
            overlap = min(e, he) - max(s, hs)
            if hd <= 3 * (e - s) and overlap > best_overlap:
                best, best_overlap = hn, overlap
            if hs <= s and he >= e and (cover_len is None
                                        or hd < cover_len):
                cover, cover_len = hn, hd
        best = best if best is not None else cover
        named[best] = named.get(best, 0.0) + (e - s) / 1e9
    small = sum(e - s for s, e in gaps if e - s < GAP_FLOOR_NS) / 1e9
    if small:
        named["gaps under 1 ms"] = small
    return named


def reduce(path: str, top: int = 10) -> dict:
    """:func:`reduce_trace` of the file at ``path``."""
    return reduce_trace(read(path), top)


def reduce_trace(trace: dict, top: int = 10) -> dict:
    """The trace's numbers, averaged over the chips that ran anything:
    ``busy_s``, ``window_s``, ``conv_s``, ``device_ops`` (the ``top``
    operations by summed time) and ``idle_gaps`` (by host activity)."""
    # writing the profile out stalls the host's threads for seconds and
    # the device with them: nothing after the call to stop counts
    stops = [s for s, _d, n in trace["host"] if "stop_trace" in n]
    cut = min(stops) if stops else None
    busy = window = conv = 0.0
    ops_time: dict = {}
    gaps_named: dict = {}
    used = 0
    for dev in trace["devices"].values():
        ops = dev["ops"] or dev["modules"]
        if cut is not None:
            ops = [o for o in ops if o[0] + o[1] <= cut]
        if not ops:
            continue
        used += 1
        b, gaps = union([(s, s + d) for s, d, _ in ops])
        busy += b / 1e9
        window += (max(s + d for s, d, _ in ops)
                   - min(s for s, _, _ in ops)) / 1e9
        for _s, d, n in ops:
            key = op_name(n)
            ops_time[key] = ops_time.get(key, 0.0) + d / 1e9
            if is_convolution(n):
                conv += d / 1e9
        for k, v in _name_gaps(gaps, trace["host"]).items():
            gaps_named[k] = gaps_named.get(k, 0.0) + v
    if not used:
        return {"chips": 0}
    rank = lambda d: [[k, v / used] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"chips": used, "busy_s": busy / used, "window_s": window / used,
            "conv_s": conv / used, "device_ops": rank(ops_time),
            "idle_gaps": rank(gaps_named)}
