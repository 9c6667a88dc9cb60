"""One run of one cell: driver, trace reduction, readers, result line.

``run.py`` looks for the chip and calls :func:`execute`; the tests call
:func:`execute` directly on the CPU with a tiny configuration, which is
the whole of a run but the look for a chip.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time
from pathlib import Path

from benchmark.harness import cells, checks
from benchmark.harness.device import CompileTally


@dataclasses.dataclass
class Run:
    cell: cells.Cell
    seed: int
    seconds: float
    trace: bool
    device: dict                 # {"platform", "kind", "count"}
    process_start: float         # time.time() when the process began
    compiles: CompileTally
    trace_dir: str
    reference: object            # the configuration's plain reference

    def setup_seconds(self) -> float:
        """Process start to now: called where the window opens."""
        return time.time() - self.process_start


def trace_dir_for(cell_name: str, root: Path = cells.ROOT) -> str:
    """A fixed place inside the checkout, emptied before each traced run
    (a trace is tens of MB; only the newest is kept)."""
    d = root / ".bench_trace" / cell_name
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    return str(d)


def execute(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
            device: dict, process_start: float,
            root: Path = cells.ROOT, compared: list | None = None) -> dict:
    """-> the result object (the last line of standard output). The
    checks are also appended to ``compared`` (``run.py`` prints them as
    the last lines of standard error)."""
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              device=device, process_start=process_start,
              compiles=CompileTally(),
              trace_dir=trace_dir_for(cell.name, root) if trace else "",
              reference=cells.reference_for(cell.config, cell.config_name,
                                            root))
    facts = cells.driver_for(cell, root).run(run)
    if facts["compiles_in_window"]:
        raise SystemExit(
            f"{facts['compiles_in_window']} program(s) compiled inside the "
            "measured window: the warm-up missed a shape; no result")

    result = {"correct": checks.verdict(facts["checks"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"])}
    dev = {**device, "memory_peak_bytes": int(facts["memory_peak_bytes"])}
    if not trace:
        wanted = {m["name"]: m for m in cell.end_to_end}
        missing = set(wanted) - set(facts["end_to_end"])
        if missing:
            raise SystemExit(f"driver reported no {sorted(missing)}")
        result["metrics"] = {
            name: {"value": float(facts["end_to_end"][name]),
                   "unit": m["unit"]} for name, m in wanted.items()}
    else:
        from benchmark.reduce import xplane

        path = xplane.newest_xplane(run.trace_dir)
        reduced = xplane.reduce(path) if path else {"chips": 0}
        if not reduced.get("busy_s"):
            raise SystemExit("the traced window holds no device operation")
        facts["trace"] = reduced
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        metrics = {}
        for m in cell.per_layer:
            spec = cells.metric_file(m["name"], root)
            value = cells.reader_for(spec, root).read(facts, spec)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    result["device"] = dev
    result["notes"] = facts.get("notes", {})
    result["checks"] = checks.as_json(facts["checks"])
    if compared is not None:
        compared.extend(facts["checks"])
    return result


def print_result(result: dict) -> None:
    print(json.dumps(result), flush=True)
