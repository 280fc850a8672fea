"""From a profiler trace to numbers: the one reduction every PR is read by.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small dict (the
"reduced trace"), and every other function here works on that dict alone,
so the arithmetic is checked on the recorded one under ``testdata/``:

    {"devices": [{"name": "/device:TPU:0",
                  "modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[label, start_ns, dur_ns, kind, mosaic], ...]}],
     "host": {"<thread line>": [[name, start_ns, dur_ns], ...]}}

Times are nanoseconds on the profiler's one clock.  ``label`` is an HLO
op's name without its number, with the first shape it writes, so that the
twelve per-layer copies of one fusion group under one label.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

_NAME = re.compile(r"%?([^ =]+?)(?:\.\d+)? = (.*)", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_KIND = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_label(hlo):
    """('multiply_reduce_fusion f32[128,12,512,64]', 'fusion', mosaic?)
    from one 'XLA Ops' event name, which is the op's whole HLO line."""
    m = _NAME.match(hlo)
    if not m:
        return hlo[:60], "", 0
    name, rest = m.groups()
    shape = _SHAPE.search(rest)
    kind = _KIND.search(rest)
    mosaic = int('custom_call_target="tpu_custom_call"' in rest)
    label = name + (" " + shape.group(0) if shape else "")
    return label, (kind.group(1) if kind else ""), mosaic


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = {"devices": [], "host": {}}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == OPS_LINE:
                    for e in line.events:
                        label, kind, mosaic = op_label(e.name)
                        dev["ops"].append([label, e.start_ns, e.duration_ns,
                                           kind, mosaic])
            trace["devices"].append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                if events:
                    trace["host"].setdefault(line.name or "main",
                                             []).extend(events)
    trace["devices"].sort(key=lambda d: d["name"])
    return trace


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------
def union(intervals):
    """Sorted, merged [start, end] list of (start, duration) pairs."""
    merged = []
    for s, d in sorted(intervals):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(merged, lo, hi):
    """Nanoseconds of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def window_ns(trace):
    """[first start, last end] over the device planes' events: the time in
    which the device was traced.  The host's tracer starts earlier and
    stops later than the device's, and that margin is not idle time.  A
    trace with no device plane has the extent of its host events."""
    lo, hi = float("inf"), float("-inf")
    streams = [ev for d in trace["devices"] for ev in (d["modules"], d["ops"])]
    if not any(streams):
        streams = list(trace["host"].values())
    for events in streams:
        for e in events:
            lo, hi = min(lo, e[1]), max(hi, e[1] + e[2])
    return (lo, hi) if hi > lo else (0.0, 0.0)


def busy_and_window_s(trace):
    """(seconds in which an op ran on the device, averaged over the
    device planes; seconds of the traced window)."""
    lo, hi = window_ns(trace)
    if not trace["devices"] or hi <= lo:
        return 0.0, max(0.0, (hi - lo) / 1e9)
    busy = [covered(union((o[1], o[2]) for o in d["ops"]), lo, hi)
            for d in trace["devices"]]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


# ---------------------------------------------------------------------------
# modules: the jitted programs, by role
# ---------------------------------------------------------------------------
def modules_by_role(dev, roles):
    """{role: [[name, start, dur], ...]} for one device plane.  A role is
    {"prefix": "jit_step"} or, where two programs share a name,
    {"prefix": "jit_pure", "pick": "most_frequent" | "rest"}: the decode
    program runs at every token and so more often than any prefill.  The
    plane's first and last module are left out: the tracer starts and
    stops in the middle of a run, and records the part it saw."""
    out = {}
    whole = sorted(dev["modules"], key=lambda m: m[1])[1:-1]
    for role, rule in roles.items():
        mine = [m for m in whole if m[0].startswith(rule["prefix"])]
        pick = rule.get("pick")
        if pick and mine:
            counts = {}
            for m in mine:
                counts[m[0]] = counts.get(m[0], 0) + 1
            top = max(sorted(counts), key=counts.get)
            mine = [m for m in mine
                    if (m[0] == top) == (pick == "most_frequent")]
        out[role] = sorted(mine, key=lambda m: m[1])
    return out


def mean_duration_ms(mods):
    return sum(m[2] for m in mods) / len(mods) / 1e6 if mods else None


def mean_period_ms(mods):
    """Mean start-to-start time of consecutive runs of one program."""
    if len(mods) < 2:
        return None
    return (mods[-1][1] - mods[0][1]) / (len(mods) - 1) / 1e6


def mean_gap_ms(mods, others=()):
    """Mean time between the end of one run and the start of the next,
    with the part of it in which ``others`` (say, prefills between two
    decode steps) ran taken out."""
    if len(mods) < 2:
        return None
    other = union((m[1], m[2]) for m in others)
    total = 0.0
    for a, b in zip(mods, mods[1:]):
        lo, hi = a[1] + a[2], b[1]
        total += max(0.0, hi - lo - covered(other, lo, hi))
    return total / (len(mods) - 1) / 1e6


def ops_ms_per_module(dev, mods, keep):
    """Milliseconds per run of ``mods`` spent in the ops that ``keep``
    (a predicate on an op row) selects, counting only ops inside a run."""
    if not mods:
        return None
    spans = union((m[1], m[2]) for m in mods)
    total, i = 0.0, 0
    for o in sorted((o for o in dev["ops"] if keep(o)), key=lambda o: o[1]):
        while i < len(spans) and spans[i][1] <= o[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= o[1]:
            total += o[2]
    return total / len(mods) / 1e6


def exposed_collective_ms(dev, mods):
    """Per run of ``mods``: time in collective ops during which no other
    op ran on this device."""
    if not mods:
        return None
    coll = [o for o in dev["ops"] if o[3].startswith(COLLECTIVES)]
    compute = union((o[1], o[2]) for o in dev["ops"]
                    if not o[3].startswith(COLLECTIVES))
    exposed = sum(o[2] - covered(compute, o[1], o[1] + o[2]) for o in coll)
    return exposed / len(mods) / 1e6


# ---------------------------------------------------------------------------
# the breakdown the next issue's writer reads
# ---------------------------------------------------------------------------
def top_device_ops(trace, top=10):
    """[[label xCOUNT, seconds], ...]: device time by op label, summed
    over the window and averaged over the device planes."""
    if not trace["devices"]:
        return []
    total, count = {}, {}
    for d in trace["devices"]:
        for label, _s, dur, kind, _m in d["ops"]:
            key = f"{label} {kind}".strip()
            total[key] = total.get(key, 0.0) + dur
            count[key] = count.get(key, 0) + 1
    n = len(trace["devices"])
    rows = sorted(total, key=total.get, reverse=True)[:top]
    return [[f"{k} x{count[k] // n}", total[k] / n / 1e9] for k in rows]


def idle_gaps(dev, min_ns=20e3):
    """[lo, hi] gaps of at least ``min_ns`` between ops on one device."""
    busy = union((o[1], o[2]) for o in dev["ops"])
    return [[a[1], b[0]] for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= min_ns]


def top_idle_gaps(trace, top=10):
    """[[what the host was doing, seconds], ...]: each idle gap of the
    first device goes to the shortest host span that covers at least half
    of it, which is the innermost one that explains it."""
    if not trace["devices"]:
        return []
    host = sorted(((e[1], e[1] + e[2], f"{line}: {e[0]}")
                   for line, events in trace["host"].items()
                   for e in events))
    total = {}
    for lo, hi in idle_gaps(trace["devices"][0]):
        best, best_len = "no host span covers half of it", float("inf")
        for s, e, name in host:
            if s >= hi:
                break
            if min(hi, e) - max(lo, s) >= 0.5 * (hi - lo) \
                    and e - s < best_len:
                best, best_len = name, e - s
        total[best] = total.get(best, 0.0) + (hi - lo)
    rows = sorted(total, key=total.get, reverse=True)[:top]
    return [[k[:120], total[k] / 1e9] for k in rows]
