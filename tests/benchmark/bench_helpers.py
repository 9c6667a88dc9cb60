"""Shared pieces of the benchmark's own tests (CPU, tiny sizes). Importing
this puts the checkout on ``sys.path``, so import it first."""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
FIXTURES = Path(__file__).resolve().parent / "fixtures"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# a --trace 0 line's keys in order: the numbers compared come last
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "notes",
             "checks"]


def tiny_cell(config: str, traffic_file: Path, e2e, per_layer=()):
    from benchmark.harness import cells

    with open(FIXTURES / "benchmark" / "configs" / f"{config}.json") as f:
        cfg = json.load(f)
    with open(traffic_file) as f:
        traffic = json.load(f)
    return cells.Cell(
        name=f"{config}.{traffic_file.stem}", chips=1, config_name=config,
        traffic_name=traffic_file.stem, config=cfg, traffic=traffic,
        end_to_end=[{"name": n, "unit": u} for n, u in e2e],
        per_layer=[{"name": n, "unit": u} for n, u in per_layer])


def execute(cell, seconds=0.5, seed=2 ** 31 + 17, trace=False):
    from benchmark.harness import runner

    return runner.execute(cell, seed=seed, seconds=seconds, trace=trace,
                          device=CPU, process_start=time.time())
