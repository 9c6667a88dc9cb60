"""The comparison that decides ``correct``.

A check is one number beside its limit. ``correct`` is true when every
check of the run holds and the run has at least one. The numbers come
from comparing what the timed path produced with the plain reference;
the limits are in the configuration's file under ``limits`` and were set
from readings on the chip (``PERF.md`` section 2 gives them).
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import sys

import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # a NaN never passes
        return bool(self.value <= self.limit)


def verdict(checks: list) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def as_json(checks: list) -> dict:
    """The result line's last key: each number with its limit."""
    def num(v):
        return v if math.isfinite(v) else str(v)

    return {c.name: {"value": num(float(c.value)), "limit": c.limit,
                     "ok": c.ok} for c in checks}


def print_checks(checks: list, stream=None) -> None:
    """The run's last lines on standard error."""
    stream = stream or sys.stderr
    for c in checks:
        print(f"[check] {c.name} = {float(c.value):.6g} limit {c.limit:g} "
              f"{'ok' if c.ok else 'FAILED'}", file=stream)
    print(f"[check] correct = {verdict(checks)}", file=stream, flush=True)


def require_same_tree(program, reference, what: str) -> None:
    """The reference's leaves go into the program's tree by name: end
    the run where the two trees differ in structure or in a shape."""
    import jax

    if jax.tree.structure(program) != jax.tree.structure(reference):
        raise SystemExit(f"the program's {what} tree and the "
                         "reference's differ")
    bad = [(a.shape, b.shape) for a, b in zip(
        jax.tree.leaves(program), jax.tree.leaves(reference))
        if a.shape != b.shape]
    if bad:
        raise SystemExit(f"{what} shapes differ: {bad[:3]}")


def rel_gap(value: float, reference: float) -> float:
    return abs(float(value) - float(reference)) / max(abs(float(reference)),
                                                      1e-30)


def leaf_norms(flat: dict) -> dict:
    """{path: l2 norm} of a flat ``{path: array}`` tree, in float64."""
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in flat.items()}


def worst_leaf_gap(prog: dict, ref: dict, skip=()):
    """Widest gap between the program's and the reference's norm of one
    leaf: |‖p‖ - ‖r‖| over max(‖r‖, the median leaf's ‖r‖), some leaves'
    norms being all but zero. -> (gap, path of the worst leaf)."""
    if set(prog) != set(ref):
        raise ValueError("program and reference trees differ: "
                         f"{sorted(set(prog) ^ set(ref))[:4]}")
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(prog[k] - r) / max(r, median, 1e-30)
        if not gap <= worst:        # NaN counts as the worst
            worst, where = gap, k
    return worst, where
