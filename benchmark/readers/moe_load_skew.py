"""Tokens of the busiest held expert over those of the average one, from
the counts the driver took off the window's last step."""


def read(facts: dict, spec: dict):
    t = facts.get("train") or {}
    if not t.get("moe_expert_tokens_mean"):
        return None
    return t["moe_expert_tokens_max"] / t["moe_expert_tokens_mean"]
