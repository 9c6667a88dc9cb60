"""The whole training step's share of the chip's bf16 peak.

Model FLOPs come from the plain reference's forward at the cell's
shapes (x 3 for forward and backward), never from ``cost_analysis()``
nor from the program's modules; the rate is all images of the window
over all of its time."""


def read(facts: dict, spec: dict):
    t = facts.get("train")
    if not t or not t.get("peak_flops"):
        return None
    rate = t["images"] / t["window_s"]
    return 100.0 * t["flops_per_image"] * rate / (t["chips"]
                                                  * t["peak_flops"])
