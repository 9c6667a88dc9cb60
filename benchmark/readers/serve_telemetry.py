"""A number the driver took from ``engine.stats()["telemetry"]`` as a
delta over the window; the metric's file names which (``key``)."""


def read(facts: dict, spec: dict):
    return (facts.get("serve") or {}).get(spec["key"])
