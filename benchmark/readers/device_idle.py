"""Share of the traced steady window with no operation on the device."""


def read(facts: dict, spec: dict):
    tr = facts.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
