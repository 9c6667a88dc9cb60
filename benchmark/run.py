"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of ``BENCHMARK.json`` on the chips of
the machine it is started on. Fails, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for. The last line of standard
output is the result object; the numbers that decided ``correct`` are
the last lines of standard error and the last key of that object.
``benchmark/README.md`` says how a cell, a configuration, a traffic kind
and a metric are added as files.
"""

from __future__ import annotations

import time

_PROCESS_START = time.time()

import argparse  # noqa: E402
import atexit  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark.harness import cells, checks, runner
    from benchmark.harness.device import require_tpu

    cell = cells.load_cell(args.workload)
    # registered before the program's own exit line ("[compile] ..."),
    # so that it runs after it: the numbers compared are the last lines
    # of standard error
    compared: list = []
    atexit.register(lambda: compared and checks.print_checks(compared))
    device = require_tpu(cell.chips)
    result = runner.execute(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        device=device,
        process_start=_PROCESS_START, compared=compared)
    runner.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
