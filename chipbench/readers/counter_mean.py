"""Mean of a quantity the program sums in one counter (microseconds, say)
per event it counts in another, over the window: ``params.sum /
params.per``.  Unlike ``counter_ratio`` it returns None where the program
does not keep the summed counter at all, so a program from before the
counter reports nothing, and not 0, under the metric's name."""


def read(metric, obs):
    c = obs["readings"].get("counters") or {}
    p = metric["params"]
    if p["sum"] not in c or not c.get(p["per"]):
        return None
    return c[p["sum"]] / c[p["per"]]
