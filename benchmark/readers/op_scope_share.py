"""Share of device busy time in the operations of one named scope of the
program (``scope`` in the metric's file, say ``lm/attn/select``): the
union of the intervals of the ``XLA Ops`` events whose ``op_name`` holds
the scope, over the union of all of them. Backward and recomputed
operations carry the scope in their ``op_name``
(``transpose(jvp(...))/lm/attn/select/...``) and count with it.

Does its own reduction, for any cell and any scope
(``reduce/host_spans.reduce_spans`` knows the serving program's two).
Window and cut are ``xplane.reduce_trace``'s: nothing that ends after
the host called ``stop_trace`` counts. ``None`` where no operation of
the trace carries this scope: a program from before the scope, or one
fetched from a compile cache that such a program filled.
"""

import functools

from benchmark.reduce import host_spans, xplane


def read(facts: dict, spec: dict):
    path = host_spans.newest_trace(spec)
    if not path:
        return None
    share = scope_share(path, spec["scope"])
    return None if share is None else 100.0 * share


@functools.lru_cache(maxsize=2)
def _trace(path: str):
    return xplane.read(path), host_spans.read_op_scopes(path)


def scope_share(path: str, scope: str):
    """Busy seconds of ``scope`` over all busy seconds, summed over the
    chips that ran anything."""
    trace, op_names = _trace(path)
    stops = [s for s, _d, n in trace["host"] if "stop_trace" in n]
    cut = min(stops) if stops else None
    busy = scoped = 0.0
    for plane, dev in trace["devices"].items():
        ops = dev["ops"]
        if cut is not None:
            ops = [o for o in ops if o[0] + o[1] <= cut]
        names = op_names.get(plane, {})
        busy += xplane.union([(s, s + d) for s, d, _ in ops])[0]
        scoped += xplane.union([(s, s + d) for s, d, n in ops
                                if scope in names.get(n, "")])[0]
    return scoped / busy if busy and scoped else None
