"""The serving step's share of the chip's bf16 peak: the served
forward's model FLOPs over the time the engine itself counted for its
batches (``device_time``: dispatch to the results on the host, so the
H2D of the batch and the fetch are in it, as they are in what a request
waits for). A metric of the serving engine, not of the compiled forward
alone: that one needs the rows of each traced execution (PERF.md
section 7).

Useful rows only: a padded row is work the chip did for nobody. Not
rate x FLOPs over the window: at a fixed offered rate that would read
the rate, whatever the program does."""


def read(facts: dict, spec: dict):
    s = facts.get("serve")
    if not s or not s.get("peak_flops") or not s.get("device_s"):
        return None
    return 100.0 * s["flops_per_image"] * s["rows"] / (
        s["device_s"] * s["chips"] * s["peak_flops"])
