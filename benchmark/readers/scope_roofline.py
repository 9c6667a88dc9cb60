"""A named scope's share of its roofline: the least seconds the chip
needs for the work the model counts in it, over the busy seconds its
operations take, both a second of the traced window.

The metric's file names the scope (``scope``), the configuration whose
shapes give the work (``config``, an entry of ``BENCHMARK.json``) and
the module under ``reduce/`` that counts it (``cost``, with
``least_seconds(cfg, reference, peaks)``). The work is the model's, so
the metric reads the same whatever implements the scope: a parent
without the kernel reads too. The rate is the window's samples a second
(``facts["train"]``), the time ``op_scope_share.scope_share`` of the
traced window's busy share. ``None``, never 0, where no operation
carries the scope, where no trace was taken or where the chip's peaks
are unknown.
"""

import importlib
import json

from benchmark.harness import cells, device
from benchmark.readers import op_scope_share
from benchmark.reduce import host_spans


def _peaks_row(bf16_flops_per_s: float):
    """The row of ``peaks.json`` that the driver took ``peak_flops``
    from (``facts`` carries the number, not the device's kind)."""
    with open(device.PEAKS_FILE) as f:
        rows = json.load(f)["peaks"].values()
    return next((r for r in rows
                 if r["bf16_flops_per_s"] == bf16_flops_per_s), None)


def read(facts: dict, spec: dict):
    t, trace = facts.get("train"), facts.get("trace")
    if not t or not t.get("peak_flops") or not trace \
            or not trace.get("busy_s"):
        return None
    peaks = _peaks_row(t["peak_flops"])
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
    least = cost.least_seconds(
        cfg, cells.reference_for(cfg, spec["config"]), peaks)
    samples_per_s = t["images"] / t["window_s"] / t["chips"]
    scope_s_per_s = share * trace["busy_s"] / trace["window_s"]
    return 100.0 * least * samples_per_s / scope_s_per_s
