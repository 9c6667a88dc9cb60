"""A wait of the input pipeline as a share of the window: the driver of
a fed cell puts ``data/prefetch.FeedTelemetry``'s deltas over the window
under ``facts["feed"]`` (seconds a key, and ``window_s``); the metric's
file names which (``key``). A cell that feeds nothing has no ``feed``
section and the metric is left out."""


def read(facts: dict, spec: dict):
    feed = facts.get("feed")
    if not feed or not feed.get("window_s") or spec["key"] not in feed:
        return None
    return 100.0 * feed[spec["key"]] / feed["window_s"]
