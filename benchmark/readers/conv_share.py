"""Share of device busy time on the matrix unit, from the trace."""


def read(facts: dict, spec: dict):
    tr = facts.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("conv_s"):
        return None
    return 100.0 * tr["conv_s"] / tr["busy_s"]
