"""Share of device busy time in the operations of one named scope of the
served program (``scope`` in the metric's file: ``served/postprocess``).
``None`` where no operation of the trace carries a scope: a program from
before the scopes, or one fetched from a compile cache that such a
program filled (the cache's key leaves the names out)."""

from benchmark.reduce import host_spans


def read(facts: dict, spec: dict):
    reduced = host_spans.of_traced_run(spec)
    if not reduced or not reduced["busy_s"] \
            or not any(reduced["scope_busy_s"].values()):
        return None
    return 100.0 * reduced["scope_busy_s"][spec["scope"]] / reduced["busy_s"]
