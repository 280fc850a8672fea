"""Device milliseconds a run of a role's program by the model's parts.

The program names its parts where the work is written
(``mxnet_tpu.telemetry.part``: ``jax.named_scope("mx.<part>")``, a second
one inside it for a sub-part), XLA carries the name stack as each
instruction's ``op_name`` into the executable, and the profiler writes it
into the metadata of the device's events (the stat ``tf_op``).  The
reduced trace (``trace_reduce.load_xplane``) keeps an op's label and drops
the rest, so this reader opens the raw ``.xplane.pb`` the ``Tracer`` left
under ``common.OUT_DIR/trace/<cell>/`` itself: ``load_rows`` turns it into
rows, and everything else here works on rows alone, so the arithmetic is
checked on hand-made rows and on rows cut from a chip trace
(``testdata/parts_dsv32_rows.json.gz``):

    [{"name": "/device:TPU:0",
      "modules": [[name, start_ns, dur_ns], ...],
      "ops": [[label, start_ns, dur_ns, path], ...]}]

``path`` is the op's name stack, ``jit(pure_decode)/mx.attention/
mx.ring_write/scatter`` or ``jit(step)/transpose(jvp(mx.ffn))/dot_general``.

* An op's **part** is the innermost component that is ``mx.<part>`` with
  ``<part>`` one of ``PARTS`` once the transforms around it are peeled
  (``jvp(``, ``transpose(``, ``jit(``, ...): a loop that attention owns
  may hold the indexer's work, and the body's own name wins.  Its
  **sub-part** is the next ``mx.`` component after that.  ``unscoped`` if
  there is none.  It is **backward** if any component starts with
  ``transpose(``; **forward** if it has a part, is not backward and is not
  under ``optimizer``.
* A fusion carries one name, its root's: members fused in from another
  part are counted with the root's part.
* An op's **self time** is its duration less the durations of the events
  nested directly inside it on the line: a ``while``, a ``conditional`` or
  a ``call`` is an event that spans its body's, and counts only what the
  body leaves.  The parts of a run then add up to its busy time.

``params``: ``role`` (as ``trace_reduce.modules_by_role``), ``part`` (a
part, ``part/sub-part`` or ``unscoped``; every scoped op, if absent) and
``direction`` (``forward`` | ``backward``, optional).  The value is the
summed self time of the selected ops that start inside the role's whole
runs over the number of those runs, in ms, averaged over the device
planes.  None where no raw trace matches ``obs["trace"]`` (the recorded
reduced traces under ``testdata/`` have none), where the role has no whole
run in the slice, or where no op of the role's runs carries a part: a
program from before the scopes, or an executable a stale compile cache
handed back; the ``parts`` record then says ``"parts": "none"``.

Once a role it prints one record before the result line:
``{"phase": "parts", "role", "runs", "module_ms", "busy_ms", "ms_per_run":
{part or part/sub-part: ms}, "forward_ms", "backward_ms", "unscoped_ms",
"unnamed_ms" (of it, ops with no name stack at all), "top_unscoped":
[[label, ms] x5], "parse_s", "trace_bytes"}``.
"""
import functools
import glob
import importlib.util
import os
import sys
import time

from .. import common
from .. import trace_reduce as tr

PARTS = ("embed", "attention", "indexer", "conv", "ffn", "experts", "head",
         "loss", "optimizer")
UNSCOPED = "unscoped"

_loaded = {}         # raw trace's path -> (rows, seconds to parse, bytes)
_reduced = {}        # (raw trace's path, role) -> reduce_role's result


# ---------------------------------------------------------------------------
# rows -> numbers
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def classify(path):
    """(part or "unscoped", sub-part or None, "forward" | "backward" |
    None) of one name stack."""
    part = sub = None
    backward = False
    for comp in path.split("/"):
        backward = backward or comp.startswith("transpose(")
        # transpose(jvp(mx.ffn)) -> mx.ffn
        comp = comp.rsplit("(", 1)[-1].rstrip(")")
        if not comp.startswith("mx."):
            continue
        name = comp[3:]
        if name in PARTS:
            part, sub = name, None
        elif part is not None and sub is None:
            sub = name
    if part is None:
        return UNSCOPED, None, None
    if backward:
        return part, sub, "backward"
    return part, sub, None if part == "optimizer" else "forward"


def self_times(ops):
    """[(op row, self ns)] in start order: each op's duration less those
    of the events nested directly inside it on the line."""
    out, stack = [], []
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        end = op[1] + op[2]
        while stack and stack[-1][0] <= op[1]:
            stack.pop()
        if stack:
            stack[-1][1][1] -= op[2]
        entry = [op, op[2]]
        out.append(entry)
        stack.append((end, entry))
    return [(op, max(0, own)) for op, own in out]


def reduce_device(dev, mods):
    """What one device plane spent in the whole runs ``mods`` of one
    program: ``{"runs", "module_ms", "by_key": {(part, sub, direction):
    ns}, "unscoped": {label: ns}, "unnamed": ns}``, or None without a run.
    An op counts if it starts inside a run; ``unnamed`` is the part of the
    unscoped time whose ops carry no name stack at all (the compiler's own
    moves between memories), which no scope in the program can reach."""
    if not mods:
        return None
    spans = tr.union((m[1], m[2]) for m in mods)
    by_key, unscoped, unnamed, i = {}, {}, 0, 0
    for op, own in self_times(dev["ops"]):
        while i < len(spans) and spans[i][1] <= op[1]:
            i += 1
        if i == len(spans) or spans[i][0] > op[1]:
            continue
        key = classify(op[3])
        by_key[key] = by_key.get(key, 0) + own
        if key[0] == UNSCOPED:
            unscoped[op[0]] = unscoped.get(op[0], 0) + own
            unnamed += 0 if op[3] else own
    return {"runs": len(mods), "module_ms": tr.mean_duration_ms(mods),
            "by_key": by_key, "unscoped": unscoped, "unnamed": unnamed}


def select_ms(reduced, part=None, direction=None):
    """Milliseconds a run in the ops ``part`` and ``direction`` select."""
    want = part.split("/") if part else None
    total = 0
    for (p, sub, d), ns in reduced["by_key"].items():
        if want is None:
            if p == UNSCOPED:
                continue
        elif p != want[0] or (len(want) > 1 and sub != want[1]):
            continue
        if direction and d != direction:
            continue
        total += ns
    return total / reduced["runs"] / 1e6


def summary(reduced):
    """The ``parts`` record's numbers of one device plane's reduction."""
    runs, ms = reduced["runs"], {}
    for (p, sub, _d), ns in reduced["by_key"].items():
        if p == UNSCOPED:
            continue
        ms[p] = ms.get(p, 0.0) + ns / runs / 1e6
        if sub:
            ms[p + "/" + sub] = ms.get(p + "/" + sub, 0.0) + ns / runs / 1e6
    top = sorted(reduced["unscoped"].items(), key=lambda kv: -kv[1])[:5]
    return {"runs": runs, "module_ms": reduced["module_ms"],
            "busy_ms": sum(reduced["by_key"].values()) / runs / 1e6,
            "ms_per_run": dict(sorted(ms.items())),
            "forward_ms": select_ms(reduced, direction="forward"),
            "backward_ms": select_ms(reduced, direction="backward"),
            "unscoped_ms": select_ms(reduced, UNSCOPED),
            "unnamed_ms": reduced["unnamed"] / runs / 1e6,
            "top_unscoped": [[k, ns / runs / 1e6] for k, ns in top]}


def reduce_role(rows, roles, role):
    """``reduce_device`` of every device plane for one role's whole runs,
    or None if a plane has none."""
    out = [reduce_device(dev, tr.modules_by_role(dev, roles).get(role, []))
           for dev in rows]
    return None if not out or any(r is None for r in out) else out


def has_parts(reduced):
    return any(key[0] != UNSCOPED for r in reduced for key in r["by_key"])


def value(reduced, params):
    """The metric's value from ``reduce_role``'s result: the mean over the
    device planes, None if a plane has no whole run of the role or none of
    the role's ops carries a part."""
    if reduced is None or not has_parts(reduced):
        return None
    values = [select_ms(r, params.get("part"), params.get("direction"))
              for r in reduced]
    return sum(values) / len(values)


def read_rows(rows, roles, params):
    return value(reduce_role(rows, roles, params["role"]), params)


# ---------------------------------------------------------------------------
# the raw trace -> rows
# ---------------------------------------------------------------------------
def xplane_pb2():
    """The ``XSpace`` protobuf's module.  A device event's name stack is a
    stat of its *metadata* (``tf_op``), which ``jax.profiler.ProfileData``
    does not show (an event's own stats are its device offset and duration;
    its name is the HLO line without its ``metadata={...}``: PR 37's step
    0), so the file is read as the protobuf it is.  tensorflow ships the
    generated module; it is loaded from its file, which needs protobuf
    alone, and tensorflow itself is not imported."""
    name = "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    if name in sys.modules:
        return sys.modules[name]
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.submodule_search_locations:
        return None
    path = os.path.join(list(found.submodule_search_locations)[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("_chipbench_xplane_pb2",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_rows(path):
    """Rows of every device plane of a raw trace, or None where no
    ``xplane_pb2`` can be had.  Times are nanoseconds as
    ``trace_reduce.load_xplane`` has them, less its rounding down."""
    pb2 = xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    rows = []
    for plane in space.planes:
        if not plane.name.startswith(tr.DEVICE_PLANE):
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}

        def path_of(meta):
            for stat in meta.stats:
                if stat_name.get(stat.metadata_id) == "tf_op":
                    value = stat.str_value or stat_name.get(stat.ref_value, "")
                    return value.rsplit(":", 1)[0]      # "<stack>:<type>"
            return ""
        named = {k: (tr.op_label(m.name)[0], path_of(m))
                 for k, m in plane.event_metadata.items()}
        dev = {"name": plane.name, "modules": [], "ops": []}
        for line in plane.lines:
            def events():
                for e in line.events:
                    yield e.metadata_id, \
                        line.timestamp_ns + e.offset_ps / 1000.0, \
                        e.duration_ps / 1000.0
            if line.name == tr.MODULES_LINE:
                dev["modules"] = [[plane.event_metadata[k].name, t, d]
                                  for k, t, d in events()]
            elif line.name == tr.OPS_LINE:
                dev["ops"] = [[named[k][0], t, d, named[k][1]]
                              for k, t, d in events()]
        rows.append(dev)
    rows.sort(key=lambda d: d["name"])
    return rows


def newest_xplane():
    found = [p for d in glob.glob(os.path.join(common.OUT_DIR, "trace", "*"))
             for p in [tr.find_xplane(d)] if p]
    return max(found, key=os.path.getmtime) if found else None


def rows_for(trace):
    """``(path, rows)`` of the raw trace that ``trace`` was reduced from,
    or None: the newest one is taken only if its first device plane has as
    many module events and the same first start."""
    if not trace or not trace["devices"]:
        return None
    path = newest_xplane()
    if path is None:
        return None
    if path not in _loaded:
        t0 = time.perf_counter()
        rows = load_rows(path)
        _loaded[path] = (rows, time.perf_counter() - t0,
                         os.path.getsize(path))
    rows = _loaded[path][0]
    mine = rows[0]["modules"] if rows else []
    theirs = trace["devices"][0]["modules"]
    if len(mine) != len(theirs) or not mine \
            or abs(mine[0][1] - theirs[0][1]) >= 1.0:
        return None
    return path, rows


def read(metric, obs):
    found = rows_for(obs["trace"])
    if found is None:
        return None
    path, rows = found
    params = metric["params"]
    key = (path, params["role"])
    if key not in _reduced:         # once a role: reduce, and say
        reduced = _reduced[key] = reduce_role(
            rows, obs["readings"]["roles"], params["role"])
        record = {"phase": "parts", "role": params["role"],
                  "parse_s": _loaded[path][1], "trace_bytes": _loaded[path][2]}
        if reduced is not None:
            record.update(summary(reduced[0]))
            if not has_parts(reduced):
                record["parts"] = "none"
        common.say(**record)
    return value(_reduced[key], params)
