"""The program's own spans in the host plane of the trace (``mx:<kind>.
<phase>``, entered by ``mxnet_tpu.telemetry`` beside its ring record), per
run of a role's program on the first device.

Both stats look at the stretch of whole runs: from the start of the role's
first whole run to the start of its last (``modules_by_role`` leaves out
the two runs the tracer cut), which holds ``runs - 1`` periods, the ones
``trace_reduce.mean_period_ms`` averages.  That stretch lies inside the
device-traced window (``trace_reduce.window_ns``).

``ms_per_run``: summed duration of the host spans called ``params.span``,
on any thread line, that start inside the stretch, over its periods.
``idle_outside_ms_per_run``: the first device's idle time in the stretch
(``trace_reduce.idle_gaps``) that lies inside no host span whose name
starts with ``params.prefix`` (by overlap; ``params.exclude`` names spans
that do not count, the step's envelope), over the same periods.
Milliseconds.  None where the trace has no device plane or the program
entered no such span, as a program from before these spans does not.
"""
from .. import trace_reduce as tr


def stretch_of_runs(trace, obs, role):
    """(lo, hi, periods) of the role's whole runs on the first device, or
    None where there are fewer than two."""
    if not trace or not trace["devices"]:
        return None
    mods = tr.modules_by_role(trace["devices"][0],
                              obs["readings"]["roles"]).get(role, [])
    if len(mods) < 2:
        return None
    return mods[0][1], mods[-1][1], len(mods) - 1


def read(metric, obs):
    p, trace = metric["params"], obs["trace"]
    stretch = stretch_of_runs(trace, obs, p["role"])
    if stretch is None:
        return None
    lo, hi, periods = stretch
    events = [e for line in trace["host"].values() for e in line]
    if p["stat"] == "ms_per_run":
        mine = [e[2] for e in events
                if e[0] == p["span"] and lo <= e[1] < hi]
        return sum(mine) / periods / 1e6 if mine else None
    if p["stat"] == "idle_outside_ms_per_run":
        skip = set(p.get("exclude", ()))
        named = tr.union((e[1], e[2]) for e in events
                         if e[0].startswith(p["prefix"]) and e[0] not in skip)
        if not named:
            return None
        outside = 0.0
        for a, b in tr.idle_gaps(trace["devices"][0]):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                outside += (b - a) - tr.covered(named, a, b)
        return outside / periods / 1e6
    return None
