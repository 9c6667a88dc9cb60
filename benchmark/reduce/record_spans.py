"""Records ``recorded_spans.xplane.pb``: a few batches of the tests' tiny
serving configuration (``tests/benchmark/fixtures``: YOLOv3 at 96 px,
one bucket of 4) through the program's ``InferenceEngine`` under the
profiler, the Python tracer off as in a traced run of a serving cell.

    python3 benchmark/reduce/record_spans.py [out_dir]     # on the chip

Three batches: four rows at once, an idle stretch (``serve/wait``), one
row alone, three rows. The profile lands in ``out_dir`` (default
``chiprun_out/record_spans``), and beside it ``recorded_spans.xplane.pb``:
the same without the plane ``/host:metadata`` (the program's HLO, half of
the file) and without the host's events other than the program's spans
and ``stop_trace`` (8,000 ``Transpose`` events of the input's relayout
among them): no reduction reads either. Copy that one beside this file
and bring the numbers of ``tests/benchmark/test_host_spans.py`` up to
date. Run it again when a span or a scope of the served path is renamed.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _put_varint(out: bytearray, value: int) -> None:
    while value > 0x7F:
        out.append(value & 0x7F | 0x80)
        value >>= 7
    out.append(value)


def _put_int(out: bytearray, number: int, value: int) -> None:
    """Append one varint field."""
    _put_varint(out, number << 3)
    _put_varint(out, value)


def _put(out: bytearray, number: int, body) -> None:
    """Append one length-delimited field."""
    _put_varint(out, number << 3 | 2)
    _put_varint(out, len(body))
    out += body


def slim(data: bytes) -> bytes:
    """A serialized ``XSpace`` without its plane ``/host:metadata``, and
    with the plane ``/host:CPU`` holding only the events named
    ``serve/...`` or ``...stop_trace`` and their metadata; everything
    else is copied as it is. (Field numbers: see
    ``host_spans.read_op_scopes``; XPlane lines 3, XLine events 4,
    XEvent metadata_id 1.)"""
    from benchmark.reduce.host_spans import SPAN_PREFIX, _fields, _map_entry

    out = bytearray()
    for number, plane in _fields(memoryview(data)):
        if number != 1:             # hostnames, errors: strings
            _put(out, number, plane)
            continue
        fields = list(_fields(plane))
        name = next(bytes(v).decode() for f, v in fields if f == 2)
        if name == "/host:metadata":
            continue
        if name != "/host:CPU":
            _put(out, 1, plane)
            continue
        kept = set()
        for f, v in fields:
            if f == 4:
                key, meta = _map_entry(v)
                event = bytes(dict(_fields(meta)).get(2, b"")).decode()
                if event.startswith(SPAN_PREFIX) or "stop_trace" in event:
                    kept.add(key)
        body = bytearray()
        for f, v in fields:
            if isinstance(v, int):
                _put_int(body, f, v)
            elif f == 4 and _map_entry(v)[0] not in kept:
                continue
            elif f == 3:
                line = bytearray()
                for lf, lv in _fields(v):
                    if isinstance(lv, int):
                        _put_int(line, lf, lv)
                    elif lf != 4 or dict(_fields(lv)).get(1) in kept:
                        _put(line, lf, lv)
                _put(body, 3, line)
            else:
                _put(body, f, v)
        _put(out, 1, body)
    return bytes(out)


def main(out_dir: str) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    import jax

    from benchmark.drivers import serve_open_loop
    from benchmark.harness import cells
    from benchmark.reduce import host_spans, xplane

    fixtures = os.path.join(ROOT, "tests", "benchmark", "fixtures",
                            "benchmark")
    with open(os.path.join(fixtures, "configs", "yolov3_tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(fixtures, "traffic", "serve_tiny.json")) as f:
        traffic = {**json.load(f), "buckets": [4]}
    ref = cells.reference_for(cfg, "yolov3_tiny")
    engine, _served, _weights, images = serve_open_loop.bring_up(
        cfg, traffic, ref, seed=25)

    shutil.rmtree(out_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for rows, pause_s in ((4, 0.12), (1, 0.0), (3, 0.0)):
        # at once: the batch window (2 ms) holds them together
        futures = [engine.submit(images[i]) for i in range(rows)]
        for f in futures:
            f.result(timeout=600)
        time.sleep(pause_s)
    jax.profiler.stop_trace()
    engine.close()

    full = xplane.newest_xplane(out_dir)
    path = os.path.join(out_dir, "recorded_spans.xplane.pb")
    with open(full, "rb") as f, open(path, "wb") as g:
        g.write(slim(f.read()))
    reduced = host_spans.reduce(path)
    print(json.dumps({"device": jax.devices()[0].device_kind, "path": path,
                      "bytes": os.path.getsize(path), "reduced": reduced}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(ROOT, "chiprun_out", "record_spans")))
