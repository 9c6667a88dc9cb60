"""Per-instruction HBM traffic budget from the optimized HLO.

Usage: python tools/hbm_budget.py [model] [batch_per_chip] [top_n]

VERDICT r3 asked for "a per-tensor traffic budget showing 76 GB is
already minimal for this architecture" (or a reduction). This tool
derives that budget mechanically instead of by hand: it lowers +
compiles the real train step (same construction as bench.py /
tools/profile_step.py, including the shipped model_kwargs), walks the
post-fusion entry computation of the optimized HLO, and charges each
top-level instruction its operand + output bytes — the same accounting
XLA's aggregate "bytes accessed" cost analysis uses, but itemized, so
the traffic can be attributed per op category and per tensor shape.

Fusions stream their internals through VMEM, so top-level operands /
outputs are exactly the HBM-visible traffic (modulo operands that stay
resident in VMEM across consumers, which the roofline treats as free).
Async copy pairs (`copy-start`/`copy-done`) are charged once, at the
start, as read+write of the copied buffer; the `-done` halves and
`async-done` markers carry no additional bytes.

Categories are keyed on the fusion's root/op kind: convolution (MXU
work), reduce (BN statistics + loss), scatter/select-and-scatter
(maxpool backward), elementwise fusion (BN apply / ReLU / optimizer),
copy/transpose, and everything else. The report prints:

  - total bytes/step and the XLA cost-analysis number side by side,
  - bytes + % per category,
  - the top-N single instructions by bytes with their output shapes,
  - an "HBM crossings" figure per distinctive >=1MB tensor shape: how
    many times a [256,56,56,256]-class tensor crosses HBM (tuple
    outputs are split into their elements, so a conv epilogue writing
    `(f32[256], ..., bf16[256,56,56,256])` counts against the big
    activation shape, not the first scalar element).

Since ISSUE 10 the accounting half of this file is a LIBRARY consumed
by the compiled-IR contract gate (``tools/jaxlint/ircheck.py``): the
HBM-budget regression ledger compares :func:`hbm_gb_per_step` against
the per-model baselines in ``jaxlint.toml`` so the 76 GB number can
only go down. Import :func:`hbm_gb_per_step`, :func:`strip_layouts`
and :func:`budget_report`; the CLI below stays the human entry point.
:func:`device_peaks` is the one table of chip peaks every MFU and
roofline figure divides by.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _dims_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0  # token[] / opaque
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def shape_bytes(shape_str: str) -> int:
    """Bytes of one (possibly tuple) HLO shape string."""
    return sum(_dims_bytes(dt, dims)
               for dt, dims in _SHAPE_RE.findall(shape_str))


def shape_elements(shape_str: str) -> list[tuple[str, int]]:
    """(canonical element shape, bytes) per tensor element of a shape
    string — one entry per tuple element, one total for plain shapes."""
    return [(f"{dt}[{dims}]", _dims_bytes(dt, dims))
            for dt, dims in _SHAPE_RE.findall(shape_str)]


# Peak bf16 FLOP/s and HBM GB/s by ``device_kind`` (Google Cloud TPU
# documentation, per-chip system-architecture tables). "TPU v5 lite" is
# what jax 0.9.0 / libtpu 0.0.34 reports for a v5e chip (chip run, PR 21).
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819.0),
    "TPU v5e": (197e12, 819.0),
    "TPU v5p": (459e12, 2765.0),
    "TPU v4": (275e12, 1228.0),
    "TPU v6e": (918e12, 1640.0),
    "TPU v6 lite": (918e12, 1640.0),
}


def device_peaks(kind: str) -> tuple[float, float]:
    """-> (peak bf16 FLOP/s, peak HBM GB/s) of one chip of ``kind``.
    An unknown kind is an error, not a default: a utilization figure
    over an assumed peak is a made-up number."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {kind!r} in "
            f"tools/hbm_budget.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)}); add the chip with its source "
            "before reporting MFU or roofline shares on it") from None


def hbm_gb_per_step(compiled) -> float:
    """XLA's aggregate "bytes accessed" for one compiled step, in GB —
    the number the jaxlint.toml HBM-budget regression ledger pins."""
    return float(compiled.cost_analysis()["bytes accessed"]) / 1e9


def strip_layouts(hlo_text: str) -> str:
    """Drop TPU layout/tiling annotations printed after every shape
    (``f32[8,8]{1,0:T(8,128)}``) so shape parsing is uniform with the
    CPU format."""
    return re.sub(r"(?<=\])\{[^{}]*\}", "", hlo_text)


# one instruction definition: "  %name = <shape> opcode(...)..."
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*((?:\([^)]*\))|(?:[\w\[\],{}:\s/#*]+?))\s+"
    r"([\w\-]+)\(", re.M)
_OPERAND_RE = re.compile(r"%[\w.\-]+")

# pure plumbing: no HBM traffic of its own
_SKIP_OPCODES = ("parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast", "copy-done", "async-done")


def parse_entry(hlo_text: str):
    """Yield (name, shape_str, opcode, operand_names, line) for the entry
    computation's top-level instructions."""
    m = re.search(r"^ENTRY [^\n{]*\{\n(.*?)^\}", hlo_text, re.S | re.M)
    if not m:
        raise ValueError("no ENTRY computation found")
    for line in m.group(1).splitlines():
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name, shape, opcode = im.group(1), im.group(2), im.group(3)
        # operands: %refs in the line tail — a superset is fine because
        # we resolve against known definition names only.
        ops = _OPERAND_RE.findall(line[im.end():])
        yield name, shape.strip(), opcode, ops, line


def categorize(opcode: str, line: str) -> str:
    if opcode == "convolution":
        return "convolution"
    if opcode in ("copy-start", "copy"):
        return "async/aliasing copy"
    if opcode == "fusion":
        if "kind=kInput" in line and "reduce" in line:
            return "reduce-fusion (BN stats / loss)"
        if "scatter" in line:
            return "scatter-fusion"
        if "kind=kOutput" in line:
            return "output-fusion (conv epilogue)"
        return "loop-fusion (elementwise)"
    if opcode in ("reduce", "reduce-window"):
        return "reduce"
    if opcode == "select-and-scatter":
        return "select-and-scatter (maxpool bwd)"
    if opcode in ("transpose", "reshape"):
        return "copy/layout"
    if opcode == "custom-call":
        return "custom-call"
    return opcode


@dataclass
class BudgetReport:
    """Itemized HBM-traffic accounting of one optimized-HLO entry."""

    total_bytes: int = 0
    cat_bytes: dict = field(default_factory=lambda: defaultdict(int))
    # (bytes, instr name, shape string, category), unsorted
    items: list = field(default_factory=list)
    # canonical >=1MB element shape -> HBM crossings / bytes each
    shape_passes: dict = field(default_factory=lambda: defaultdict(int))
    shape_bytes: dict = field(default_factory=dict)


def budget_report(hlo_text: str) -> BudgetReport:
    """Walk the entry computation of (layout-stripped) optimized HLO and
    charge each top-level instruction its operand + output bytes."""
    defs: dict[str, str] = {}  # name -> shape string
    rows = []
    for name, shape, opcode, ops, line in parse_entry(hlo_text):
        defs[name] = shape
        rows.append((name, shape, opcode, ops, line))
    def_bytes = {n: shape_bytes(s) for n, s in defs.items()}

    rep = BudgetReport()

    def count_passes(shape_str: str):
        for canon, b in shape_elements(shape_str):
            if b >= 1 << 20:
                rep.shape_passes[canon] += 1
                rep.shape_bytes[canon] = b

    for name, shape, opcode, ops, line in rows:
        if opcode in _SKIP_OPCODES:
            continue
        out_b = shape_bytes(shape)
        if opcode == "copy-start":
            # async copy: tuple output is (dest, src-alias, sync); charge
            # one read + one write of the copied buffer, nothing at -done
            copied = shape_elements(shape)[0] if shape_elements(shape) else None
            b = 2 * (copied[1] if copied else 0)
            if copied and copied[1] >= 1 << 20:
                rep.shape_passes[copied[0]] += 2
                rep.shape_bytes[copied[0]] = copied[1]
        else:
            in_b = sum(def_bytes.get(o, 0) for o in dict.fromkeys(ops))
            b = out_b + in_b
            count_passes(shape)
            for o in dict.fromkeys(ops):
                if def_bytes.get(o, 0) >= 1 << 20:
                    count_passes(defs[o])
        rep.total_bytes += b
        cat = categorize(opcode, line)
        rep.cat_bytes[cat] += b
        rep.items.append((b, name, shape, cat))
    return rep


def render_report(rep: BudgetReport, *, top_n: int = 25,
                  out=sys.stdout) -> None:
    total = max(rep.total_bytes, 1)
    print("\n== bytes by category ==", file=out)
    for cat, b in sorted(rep.cat_bytes.items(), key=lambda kv: -kv[1]):
        print(f"  {b/1e9:7.2f} GB  {b/total*100:5.1f}%  {cat}", file=out)
    print(f"\n== top {top_n} instructions by operand+output bytes ==",
          file=out)
    for b, name, shape, cat in sorted(rep.items, key=lambda t: -t[0])[:top_n]:
        print(f"  {b/1e6:9.1f} MB  {cat:<34s} {name:<28s} {shape[:60]}",
              file=out)
    print("\n== HBM crossings per >=1MB tensor shape (passes over HBM) ==",
          file=out)
    for s, n in sorted(rep.shape_passes.items(),
                       key=lambda kv: -kv[1] * rep.shape_bytes[kv[0]])[:20]:
        print(f"  x{n:<4d} {rep.shape_bytes[s]/1e6:9.1f} MB each  {s}",
              file=out)


def main():
    model_name = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    top_n = int(sys.argv[3]) if len(sys.argv) > 3 else 25

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from tools.profile_step import build

    state, db, compiled = build(model_name, batch)
    hlo = strip_layouts(compiled.as_text())
    rep = budget_report(hlo)

    print(json.dumps({
        "model": model_name, "batch_per_chip": batch,
        "sum_operand_output_gb": round(rep.total_bytes / 1e9, 1),
        "xla_cost_analysis_gb": round(hbm_gb_per_step(compiled), 1),
        "note": "sum counts VMEM-resident re-reads too; XLA's number is "
                "the authoritative roofline input",
    }))
    render_report(rep, top_n=top_n)


if __name__ == "__main__":
    main()
