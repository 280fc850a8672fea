"""Device time of a jitted program by role: its mean ``duration``, the mean
``gap`` between two runs (less the time another role's program ran in it,
``minus``), or its mean start-to-start ``period``.  Milliseconds."""
from .. import trace_reduce as tr
from . import per_device


def read(metric, obs):
    p = metric["params"]

    def one(dev, roles):
        mods = roles.get(p["role"], [])
        if p["stat"] == "duration":
            return tr.mean_duration_ms(mods)
        if p["stat"] == "period":
            return tr.mean_period_ms(mods)
        return tr.mean_gap_ms(mods, roles.get(p.get("minus"), ()))
    return per_device(obs, one)
