"""Share of the traced device window in which the chip was idle while
the program was inside one of the spans the metric's file names
(``spans``): an idle gap is split over the spans it lies under.
``None`` where the trace holds none of the program's spans, never 0."""

from benchmark.reduce import host_spans


def read(facts: dict, spec: dict):
    reduced = host_spans.of_traced_run(spec)
    if not reduced or not reduced["window_s"]:
        return None
    idle = sum(reduced["phases"][name]["idle_s"] for name in spec["spans"]
               if name in reduced["phases"])
    return 100.0 * idle / reduced["window_s"]
