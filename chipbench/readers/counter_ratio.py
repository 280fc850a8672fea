"""Ratio of two of the program's counters over the window, e.g.
``tokens_generated / decode_steps``: slots occupied per decode step."""


def read(metric, obs):
    c = obs["readings"].get("counters") or {}
    p = metric["params"]
    if not c.get(p["over"]):
        return None
    return c.get(p["count"], 0) / c[p["over"]]
