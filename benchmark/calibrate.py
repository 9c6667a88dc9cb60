"""Readings that a limit of ``correct`` is set from, taken on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 [--control 1] [--faults 1] [--sweep 90,110]

For every seed, in one process: the program's numbers against the plain
reference (the lower reading comes from these), and with ``--control``
the reference computed in the configuration's control numerics put in
the program's place (the upper reading), with ``--faults`` the planted
faults of the cell's kind; each reading is judged by the run's own
checks and limits, so a control or a fault shows ``"correct": false``.
``--sweep`` offers a serving cell each of these rates for ``--seconds``
to find its knee. The benchmark's own runs never run this; it prints one
JSON line a seed and reading, and writes them to
``chiprun_out/calibrate/<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark.harness import cells
    from benchmark.harness.device import require_tpu

    cell = cells.load_cell(args.workload)
    require_tpu(cell.chips)
    driver = cells.driver_for(cell)
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a") as out:
        for reading in driver.calibrate(
                cell, [int(s) for s in args.seeds.split(",")],
                control=bool(args.control), faults=bool(args.faults),
                seconds=args.seconds,
                sweep=[float(r) for r in args.sweep.split(",") if r]):
            line = json.dumps(reading)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
