"""Mean length of one of the program's spans in the traced window, in
milliseconds: the metric's file names the span (``span``, as the profile
has it: ``serve/pack``). ``None`` where the trace holds no such span (a
program from before the spans, a cell that does not serve)."""

from benchmark.reduce import host_spans


def read(facts: dict, spec: dict):
    reduced = host_spans.of_traced_run(spec)
    phase = (reduced or {"phases": {}})["phases"].get(spec["span"])
    return 1e3 * phase["mean_s"] if phase else None
