"""A named scope's share of its roofline in a traced generation span:
the least seconds the chip needs for the work the model counts in the
span, over the seconds the scope's operations take in it.

``scope_roofline``'s reading for a serving cell whose work is prefill
tokens and decode rows rather than samples: the metric's file names the
scope (``scope``), the configuration whose shapes give the work
(``config``) and the module under ``reduce/`` that counts it (``cost``,
with ``least_seconds(cfg, prefill_tokens, decode_rows, peaks)``). The
counts are ``facts["gen"]``, the generator's counters from the
profiler's start to the last answer, so that work and time cover one
span (the traced tail decodes more and prefills less than the window);
the scope's time is ``op_scope_share.scope_share`` of the trace's busy
seconds. ``None``, never 0, where no operation carries the scope, where
no trace was taken or where the chip's peaks are unknown.
"""

import importlib
import json

from benchmark.harness import cells
from benchmark.readers import op_scope_share
from benchmark.readers.scope_roofline import _peaks_row
from benchmark.reduce import host_spans


def read(facts: dict, spec: dict):
    g, trace = facts.get("gen"), facts.get("trace")
    if not g or not g.get("peak_flops") or not trace \
            or not trace.get("busy_s"):
        return None
    peaks = _peaks_row(g["peak_flops"])
    path = host_spans.newest_trace(spec)
    if not peaks or not path:
        return None
    share = op_scope_share.scope_share(path, spec["scope"])
    if share is None:
        return None
    entry = {c["name"]: c for c in cells.load_spec()["configs"]}[
        spec["config"]]
    with open(cells.ROOT / entry["file"]) as f:
        cfg = json.load(f)
    cost = importlib.import_module(f"benchmark.reduce.{spec['cost']}")
    least = cost.least_seconds(cfg, g["prefill_tokens"], g["decode_rows"],
                               peaks)
    return 100.0 * least / (share * trace["busy_s"])
