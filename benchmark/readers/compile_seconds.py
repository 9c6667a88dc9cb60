"""Seconds of XLA compilation before the window opened."""


def read(facts: dict, spec: dict):
    return facts.get("compile_s")
