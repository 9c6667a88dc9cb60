"""The served forward's own share of the chip's bf16 peak, from the
trace: forward model FLOPs (the plain reference's) of the useful rows of
the traced executions over their ``XLA Modules`` time. Each execution's
rows are the ``rows`` stat of its ``serve/device`` span; the module time
holds the padded rows' work and the in-graph post-process, the numerator
only the useful rows, so it cannot pass 100. ``None`` where no traced
execution carries its rows."""

from benchmark.reduce import host_spans


def read(facts: dict, spec: dict):
    s = facts.get("serve")
    reduced = host_spans.of_traced_run(spec)
    if not reduced or not s or not s.get("peak_flops"):
        return None
    ran = [e for e in reduced["executions"]
           if e["rows"] is not None and e["module_s"] > 0]
    if not ran:
        return None
    return 100.0 * s["flops_per_image"] * sum(e["rows"] for e in ran) / (
        sum(e["module_s"] for e in ran) * s["chips"] * s["peak_flops"])
