"""From a traced run's ``.xplane.pb`` to what the program's own spans say
about the device's idle time, and what its named scopes say about the
busy time.

Since PR 25 the program puts its spans into the profiler
(``deepvision_tpu/obs/trace.py``: a span is a ``TraceAnnotation`` named
``<cat>/<name>`` with its scalar args as stats) and names the two halves
of every served program (``serve/models.py``: ``jax.named_scope``), so
one trace holds, on one clock:

- the dispatcher's phases as host events ``serve/wait``,
  ``serve/fill_window``, ``serve/pack``, ``serve/device_put``,
  ``serve/device`` (stats ``rows``, ``bucket``) and ``serve/resolve``:
  flat and consecutive on the dispatcher thread;
- the device's ``XLA Ops`` and ``XLA Modules`` (``xplane.read``);
- for each operation the ``op_name`` of its HLO metadata, which holds
  the scope (``jit(served_forward)/served/postprocess/...``). It is the
  stat ``tf_op`` of the operation's *event metadata* (looked at by hand
  in a chip trace, PR 25). ``jax.profiler.ProfileData`` (jax 0.9.0) does
  not hand it out: an ``XLA Ops`` event's ``stats`` are the event's own
  (``device_offset_ps``, ``device_duration_ps``, ``Time Scale
  Multiplier``), a plane's ``stats`` are the chip's peaks, and neither a
  plane, a line nor an event has an accessor for the metadata or its id;
  no ``xplane_pb2`` is installed outside TensorFlow. So the few fields
  wanted are read from the file's protobuf wire format here, and an
  operation is found by its event's name, all ``ProfileData`` gives.

Window, busy time and idle gaps are ``xplane.reduce_trace``'s: nothing
that ends after the host called ``stop_trace`` counts, the window runs
from the first operation's start to the last one's end, a gap is what
the union of the operations leaves. A gap is split over the phases it
lies under (the intersection of the gaps with a phase's intervals), so
the phases' idle seconds and the rest under no phase sum to the idle
time. A program without spans (the parent of PR 25, a training cell)
gives ``None``: a reader then returns ``None`` and its metric is left
out of the line.

``tests/benchmark/test_host_spans.py`` checks it against
``recorded_spans.xplane.pb``, recorded beside this file on one v5e chip
from the tests' tiny serving configuration.
"""

from __future__ import annotations

import functools
import glob
import os

from benchmark.harness import cells
from benchmark.reduce import xplane

SPAN_PREFIX = "serve/"
DEVICE_SPAN = "serve/device"
SCOPES = ("served/forward", "served/postprocess")
# where harness/runner.py keeps the traced run's profile until the
# readers are through: .bench_trace/<cell>/plugins/profile/<time>/
TRACE_ROOT = cells.ROOT / ".bench_trace"


def newest_trace(spec: dict, root=None) -> str | None:
    """The profile of the traced run that reads the metric ``spec``: the
    newest under ``.bench_trace/<cell>`` of the cells that
    ``BENCHMARK.json`` lists for the metric (a run empties its cell's
    directory first and removes it after, so another cell's crashed or
    concurrent run is not read as this one's); of every cell where the
    metric lists none."""
    root = TRACE_ROOT if root is None else root
    listed = [m.get("workloads") for m in cells.load_spec()["per_layer"]
              if m["name"] == spec["name"]]
    dirs = ([os.path.join(str(root), cell) for cell in listed[0]]
            if listed and listed[0]
            else glob.glob(os.path.join(str(root), "*")))
    found = [p for d in dirs if (p := xplane.newest_xplane(d))]
    return max(found, key=os.path.getmtime) if found else None


def of_traced_run(spec: dict) -> dict | None:
    """:func:`reduce` of the profile of the run that reads the metric
    ``spec``, read once per process."""
    path = newest_trace(spec)
    return reduce(path) if path else None


@functools.lru_cache(maxsize=2)
def reduce(path: str) -> dict | None:
    """:func:`reduce_spans` of the file at ``path``."""
    return reduce_spans(xplane.read(path), read_spans(path),
                        read_op_scopes(path))


def read_spans(path: str) -> list:
    """The program's spans among the host's events, with their stats:
    sorted ``(start_ns, duration_ns, name, stats)``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((e.start_ns, e.duration_ns, e.name, dict(e.stats))
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda s: s[:3])


# ------------------------------------------- op_name of every operation


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a view of its bytes (a sub-message
    is parsed by the caller that wants it, skipped at no cost else)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, value


def _map_entry(buf) -> tuple:
    """A map entry: -> (key as int, the value message's bytes)."""
    fields = dict(_fields(buf))
    return fields.get(1, 0), fields.get(2, b"")


def read_op_scopes(path: str) -> dict:
    """``{device plane: {operation's event name: op_name}}`` from the
    ``tf_op`` stat of the planes' event metadata. Two programs in one
    trace may each hold an operation of one name (the name is the HLO
    instruction's text, shapes included, so two buckets' programs share
    few): where their scopes differ the name is given to neither
    (``""``), so that no operation is put in the wrong half. The recorded
    trace has 293 names twice, the two ends of an async pair, none with
    a scope. Field numbers are
    those of ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5; XEventMetadata
    name 2, stats 5; XStatMetadata name 2; XStat metadata_id 1,
    str_value 5, ref_value 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(_map_entry(value)[1])
            elif field == 5:
                key, meta = _map_entry(value)
                stat_names[key] = bytes(dict(_fields(meta)).get(
                    2, b"")).decode()
        if not name.startswith("/device:TPU:"):
            continue
        scopes = {}
        for meta in events:
            event_name, op = "", None
            for field, value in _fields(meta):
                if field == 2:
                    event_name = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    op = (bytes(stat[5]).decode() if 5 in stat
                          else stat_names.get(stat.get(7), ""))
            if op:
                seen = scopes.get(event_name)
                scopes[event_name] = (
                    op if seen is None or _scope_of(seen) == _scope_of(op)
                    else "")
        out[name] = scopes
    return out


# ------------------------------------------------------------ reduction


def intersect(a: list, b: list) -> float:
    """Summed length of the intersection of two lists of sorted,
    non-overlapping ``(start, end)`` intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _scope_of(op_name: str) -> str | None:
    return next((s for s in SCOPES if s in op_name), None)


def reduce_spans(trace: dict, spans: list, op_scopes: dict) -> dict | None:
    """The spans' and the scopes' numbers, averaged over the chips that
    ran anything (a span's idle seconds are those of the average chip):

    - ``window_s``, ``busy_s``, ``idle_s``: as ``xplane.reduce_trace``;
    - ``phases``: ``{span name: {"count", "total_s", "mean_s",
      "idle_s"}}`` of the spans that ended before ``stop_trace``;
    - ``idle_unattributed_s``: idle seconds under no span;
    - ``executions``: per ``serve/device`` span ``{"rows", "bucket",
      "span_s", "module_s"}``, ``module_s`` the ``XLA Modules`` time of
      the launches whose middle lies inside the span (per chip);
    - ``scope_busy_s``: ``{scope: seconds}``, the union of the operations
      whose ``op_name`` holds one of :data:`SCOPES`.

    ``None`` where the trace holds no span of the program or no device
    operation."""
    stops = [s for s, _d, n in trace["host"] if "stop_trace" in n]
    cut = min(stops) if stops else None
    if cut is not None:
        spans = [s for s in spans if s[0] + s[1] <= cut]
    if not spans:
        return None
    by_name: dict = {}
    for start, dur, name, _stats in spans:
        by_name.setdefault(name, []).append((start, start + dur))
    flat = {name: _flat(ivs) for name, ivs in by_name.items()}
    any_span = _flat([iv for ivs in by_name.values() for iv in ivs])

    busy = window = unattributed = 0.0
    idle_under = dict.fromkeys(by_name, 0.0)
    scope_busy = dict.fromkeys(SCOPES, 0.0)
    module_time = [0.0] * len(by_name.get(DEVICE_SPAN, ()))
    used = 0
    for plane, dev in trace["devices"].items():
        ops = dev["ops"] or dev["modules"]
        if cut is not None:
            ops = [o for o in ops if o[0] + o[1] <= cut]
        if not ops:
            continue
        used += 1
        b, gaps = xplane.union([(s, s + d) for s, d, _ in ops])
        busy += b
        window += (max(s + d for s, d, _ in ops)
                   - min(s for s, _, _ in ops))
        for name, ivs in flat.items():
            idle_under[name] += intersect(gaps, ivs)
        unattributed += sum(e - s for s, e in gaps) - intersect(
            gaps, any_span)
        # the union, as for the busy time: a `while` lies on the line
        # over the operations of its body, all in one scope
        names = op_scopes.get(plane, {})
        by_scope: dict = {}
        for s, d, n in ops:
            scope = _scope_of(names.get(n, ""))
            if scope:
                by_scope.setdefault(scope, []).append((s, s + d))
        for scope, ivs in by_scope.items():
            scope_busy[scope] += xplane.union(ivs)[0]
        for s, d, _n in dev["modules"]:
            for k, (lo, hi) in enumerate(by_name.get(DEVICE_SPAN, ())):
                if lo <= s + d / 2 < hi:
                    module_time[k] += d
                    break
    if not used:
        return None
    ns = 1e9 * used
    phases = {}
    for name, ivs in by_name.items():
        total = sum(e - s for s, e in ivs) / 1e9
        phases[name] = {"count": len(ivs), "total_s": total,
                        "mean_s": total / len(ivs),
                        "idle_s": idle_under[name] / ns}
    executions = [
        {"rows": stats.get("rows"), "bucket": stats.get("bucket"),
         "span_s": dur / 1e9, "module_s": module_time[k] / ns}
        for k, (_start, dur, _name, stats) in enumerate(
            s for s in spans if s[2] == DEVICE_SPAN)]
    return {"chips": used, "window_s": window / ns, "busy_s": busy / ns,
            "idle_s": (window - busy) / ns, "phases": phases,
            "idle_unattributed_s": unattributed / ns,
            "executions": executions,
            "scope_busy_s": {k: v / ns for k, v in scope_busy.items()}}


def _flat(intervals: list) -> list:
    """Intervals that may overlap (spans of several threads) as sorted,
    non-overlapping ones: what ``xplane.union``'s gaps leave."""
    _covered, gaps = xplane.union(intervals)
    edges = ([min(s for s, _ in intervals)]
             + [edge for gap in gaps for edge in gap]
             + [max(e for _, e in intervals)])
    return list(zip(edges[::2], edges[1::2]))
