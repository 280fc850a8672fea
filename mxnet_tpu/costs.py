"""mxnet_tpu.costs — compute-cost observability: per-program FLOP/byte
ledger, MFU accounting, and block-level attribution inside captured
programs.

The compute twin of the per-program *memory* ledger (``mxnet_tpu.memory``):
where that module answers "what is resident and which program owns the
peak", this one answers "where do the FLOPs go and how close to the
hardware roof is this program running".

* **Per-program cost ledger** — every compile / AOT / ProgramCache
  warm-load records ``Compiled.cost_analysis()`` (XLA's own HLO cost
  model: ``flops``, ``bytes accessed``, ``transcendentals`` — works on
  CPU, so tier-1 asserts it) into a ledger keyed by the ProgramCache key,
  alongside the memory ledger.  Capture is **compile-time only**: the hot
  path never analyzes anything.  Warm (deserialized) executables are
  flagged ``analysis='warm'`` — like the memory ledger's alias caveat,
  a deserialized executable's analysis comes from a reconstructed
  module and is not guaranteed identical to the fresh compile's — and a
  later fresh compile of the same key upgrades the entry (counted by
  ``costs/ledger_upgrades``).
* **MFU per execution** — when a flush / serving dispatch runs a program
  the ledger knows, its wall duration turns into achieved FLOP/s and
  **MFU** against the ``device_kind``-keyed peak table (:data:`PEAKS`;
  ``MXNET_PEAK_FLOPS`` overrides; the CPU has none), surfaced as
  ``costs/*`` metrics and as ``flops=``/``mfu=`` attributes on
  ``step_flush`` and serving ``execute`` spans
  (``tools/trace_report.py`` grows the columns).
* **Block-level attribution** — at segment compile time the engine hands
  over the captured op list (each op knows its fun, input avals and the
  originating HybridBlock from the recording-time block scope);
  per-equation flop estimates from a ``jax.make_jaxpr`` walk fold up to
  blocks, producing the per-block cost table for the ONE fused step that
  ``tools/cost_report.py`` renders (top-K blocks by flops + a roofline
  verdict from ledger bytes).  VJP ops are CSE-corrected: the captured
  program re-traces each op's forward inside its VJP and XLA CSEs the
  duplicate, so the estimator subtracts the primal's flops from each
  backward op (docs/OBSERVABILITY.md).
* **Forensics** — :func:`crash_report_payload` is the ``costs`` section
  of crash reports (schema v4): hottest programs by flops and the
  last-step MFU, federated per-replica through the existing /statusz
  path like every other section.

Always-on by design (``MXNET_COSTS``, default on): capture happens at
compile time and execution accounting is a dict lookup plus four float
ops inside the telemetry-gated span block.  Metric tables and the
cost_report recipe: docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict

from . import telemetry as _telemetry
from .util import getenv

__all__ = [
    "enabled", "enable", "attribution_enabled", "record_program",
    "ledger", "ledger_entry", "ledger_flops", "hottest_programs",
    "ledger_upgrades", "PEAKS", "peak_flops", "peak_bytes_per_s",
    "peak_info",
    "record_execution", "execution_attrs", "last_execution",
    "record_pass", "pass_ledger",
    "attribute_segment", "attribution", "attributions",
    "estimate_fun_cost", "jaxpr_cost",
    "crash_report_payload", "report_payload", "reset",
]


# ---------------------------------------------------------------------------
# on/off switches
# ---------------------------------------------------------------------------
def _read_env():
    return bool(getenv("MXNET_COSTS"))


_active = _read_env()


def enabled():
    """Cost capture + execution accounting on?  (``MXNET_COSTS``, default
    on.  Capture is compile-time-only either way; this also gates the
    per-flush ledger lookup.)"""
    return _active


def enable(flag=True):
    """Override the env switch for this process (``enable(None)``
    re-reads ``MXNET_COSTS``)."""
    global _active
    _active = _read_env() if flag is None else bool(flag)


def attribution_enabled():
    """Block-level attribution at segment compile time on?
    (``MXNET_COST_ATTRIBUTION``, default on; implies :func:`enabled`.)"""
    return _active and bool(getenv("MXNET_COST_ATTRIBUTION"))


# ---------------------------------------------------------------------------
# Peak table: per-chip bf16 peak FLOP/s and HBM bytes/s, keyed by jax's
# ``Device.device_kind`` (spellings: jax/_src/pallas/mosaic/tpu_info.py).
# Source of the figures: Google Cloud TPU documentation, system
# architecture pages for each generation.  A kind that is not here is an
# error, not a default — add its row with a source, or set
# MXNET_PEAK_FLOPS / MXNET_PEAK_BYTES_PER_S.  The host CPU has no row: a
# CPU run has no utilization (docs/OBSERVABILITY.md).
# ---------------------------------------------------------------------------
_V5E = (197e12, 819e9)
_V5P = (459e12, 2765e9)
PEAKS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5": _V5P,
    "TPU v5p": _V5P,
}

_peak = [None]                      # (flops, bytes_per_s, source) | None


def _resolve_peak():
    """Resolve the peak-FLOP/bandwidth pair once.  Env overrides win; the
    backend's device_kind is consulted ONLY when a backend is already
    live (the same no-backend-contact discipline as
    ``memory._probe_backend`` — resolving a peak must never initialize a
    device).  None while unresolved, and always on the CPU."""
    p = _peak[0]
    if p is not None:
        return p
    env_f = float(getenv("MXNET_PEAK_FLOPS"))
    env_b = float(getenv("MXNET_PEAK_BYTES_PER_S"))
    flops = bw = source = None
    from jax._src import xla_bridge as _xb
    if _xb._backends:
        import jax
        d = jax.local_devices()[0]
        if d.platform != "cpu":
            row = PEAKS.get(d.device_kind)
            if row is not None:
                flops, bw = row
                source = f"table:{d.device_kind}"
            elif not env_f > 0:
                from .base import MXNetError
                raise MXNetError(
                    f"device_kind {d.device_kind!r} is not in "
                    f"mxnet_tpu.costs.PEAKS ({sorted(PEAKS)}): add its row "
                    f"with a source, or set MXNET_PEAK_FLOPS")
    if env_f > 0:
        flops = env_f
        source = "env" if source is None else f"env(+{source})"
    if flops is None:
        return None                 # CPU, or no backend yet: no peak
    if env_b > 0:
        bw = env_b
    p = _peak[0] = (float(flops), None if bw is None else float(bw), source)
    return p


def peak_flops():
    """Peak FLOP/s for MFU accounting (None until a backend is live or
    ``MXNET_PEAK_FLOPS`` is set)."""
    p = _resolve_peak()
    return p[0] if p else None


def peak_bytes_per_s():
    """Peak memory bandwidth for the roofline ridge (None while
    unresolved)."""
    p = _resolve_peak()
    return p[1] if p else None


def peak_info():
    """``{"flops", "bytes_per_s", "source"}`` or None while unresolved."""
    p = _resolve_peak()
    return {"flops": p[0], "bytes_per_s": p[1], "source": p[2]} \
        if p else None


# ---------------------------------------------------------------------------
# per-program cost ledger
# ---------------------------------------------------------------------------
_LEDGER_CAP = 4096
_lock = threading.Lock()
_ledger: OrderedDict = OrderedDict()    # key -> entry dict
_by_prefix: dict = {}                   # key[:12] -> key (pc:* span labels)
_unkeyed = itertools.count(1)
_upgrades = [0]
_flops_max = [0.0]


def _cost_dict(compiled):
    """The flat cost dict out of ``Compiled.cost_analysis()`` (jax returns
    a list with one dict per program on some versions, a bare dict on
    others), or None when the backend has no cost model."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca if isinstance(ca, dict) else None


def record_program(compiled, key=None, label="", kind="op", warm=False):
    """Record one compiled executable's ``cost_analysis()`` into the
    ledger under its ProgramCache ``key`` (or a synthetic key).  Called at
    every compile / AOT compile / warm load — compile-time only, never on
    the execution hot path.  Defensive: a backend without a cost model
    returns None and costs nothing.  Returns a copy of the entry.

    ``warm=True`` marks a DESERIALIZED executable: its analysis comes
    from a reconstructed module (the memory ledger's alias caveat has a
    cost twin — e.g. donation aliasing is absent, and some PjRt backends
    return nothing at all for loaded executables), so the entry is
    flagged ``analysis='warm'`` and a later fresh compile of the same key
    upgrades the numbers (counted by ``costs/ledger_upgrades``); a fresh
    entry is never downgraded."""
    if compiled is None or not _active:
        return None
    try:
        ca = _cost_dict(compiled)
        if ca is None:
            return None
        flops = float(ca.get("flops", 0.0) or 0.0)
        byts = float(ca.get("bytes accessed", 0.0) or 0.0)
        transc = float(ca.get("transcendentals", 0.0) or 0.0)
    except Exception:               # noqa: BLE001 — analysis is best-effort
        return None
    if key is None:
        key = f"unkeyed:{next(_unkeyed)}"
    key = str(key)
    with _lock:
        e = _ledger.get(key)
        if e is None:
            e = _ledger[key] = {
                "key": key, "label": label or "", "kind": kind or "op",
                "flops": flops, "bytes_accessed": byts,
                "transcendentals": transc,
                "analysis": "warm" if warm else "fresh",
                "compiles": 1, "executions": 0,
                "last_dur_us": None, "last_mfu": None, "best_mfu": None,
                "ts": time.time(),
            }
            _by_prefix[key[:12]] = key
            while len(_ledger) > _LEDGER_CAP:
                old_key, _old = _ledger.popitem(last=False)
                _by_prefix.pop(old_key[:12], None)
                _attr.pop(old_key, None)
        else:
            e["compiles"] += 1
            if label and not e["label"]:
                e["label"] = label
            if not warm and e.get("analysis") == "warm":
                # fresh compile of a key first seen as a warm load:
                # upgrade the numbers (the explicit upgrade the memory
                # ledger makes for its alias table — counted)
                e.update(flops=flops, bytes_accessed=byts,
                         transcendentals=transc, analysis="fresh")
                _upgrades[0] += 1
        if e["flops"] > _flops_max[0]:
            _flops_max[0] = e["flops"]
        return dict(e)


def _lookup(handle):
    """Ledger entry by key or ``pc:<key12>`` span label (caller holds no
    lock; returns the LIVE entry under ``_lock``)."""
    if not handle:
        return None
    h = str(handle)
    e = _ledger.get(h)
    if e is None and h.startswith("pc:"):
        full = _by_prefix.get(h[3:15])
        e = _ledger.get(full) if full else None
    if e is None and len(h) >= 12:
        full = _by_prefix.get(h[:12])
        e = _ledger.get(full) if full else None
    return e


def ledger():
    """All ledger entries (insertion order, oldest first)."""
    with _lock:
        return [dict(e) for e in _ledger.values()]


def ledger_entry(handle):
    """One entry by ProgramCache key / ``pc:<key12>`` label / key prefix,
    or None."""
    with _lock:
        e = _lookup(handle)
        return dict(e) if e else None


def ledger_flops(handle):
    """Flops for a program the ledger knows, else None."""
    with _lock:
        e = _lookup(handle)
        return e["flops"] if e else None


def hottest_programs(n=5):
    """Top-N ledger entries by flops — 'which compiled program owns the
    compute' (crash-report ``costs.ledger.hottest``)."""
    with _lock:
        es = sorted(_ledger.values(), key=lambda e: -e["flops"])
        return [dict(e) for e in es[:int(n)]]


def ledger_upgrades():
    """Warm-entry upgrades performed (fresh compile replacing a
    warm-loaded entry's numbers)."""
    return _upgrades[0]


# ---------------------------------------------------------------------------
# rewrite-pass ledger (mxnet_tpu.compile.passes)
# ---------------------------------------------------------------------------
_passes: list = []
_PASS_CAP = 256


def record_pass(pass_name, label="", flops_before=0.0, flops_after=0.0,
                bytes_before=0.0, bytes_after=0.0, seconds=0.0,
                validated=None, tolerance=0.0):
    """One validated rewrite of a captured program: the before->after
    bytes/FLOPs estimate per pass (the pass-pipeline side of the ledger
    — compile-time only, like :func:`record_program`; XLA's own
    ``cost_analysis`` of the REWRITTEN program still lands there when it
    is AOT-compiled).  Rendered by ``tools/cost_report.py`` from
    :func:`report_payload`'s ``passes`` section."""
    entry = {
        "pass": str(pass_name), "label": label or "",
        "flops_before": float(flops_before),
        "flops_after": float(flops_after),
        "bytes_before": float(bytes_before),
        "bytes_after": float(bytes_after),
        "seconds": round(float(seconds), 4),
        "validated": validated, "tolerance": float(tolerance),
        "ts": time.time(),
    }
    with _lock:
        _passes.append(entry)
        del _passes[:-_PASS_CAP]
    return dict(entry)


def pass_ledger():
    """Every recorded pass rewrite (oldest first, bounded)."""
    with _lock:
        return [dict(e) for e in _passes]


# ---------------------------------------------------------------------------
# MFU accounting per execution
# ---------------------------------------------------------------------------
_executions = [0]
_flops_total = [0.0]
_bytes_total = [0.0]
_last = [None]          # {"key", "flops", "dur_us", "achieved_flops", "mfu"}


def record_execution(handle, dur_us):
    """Account one execution of a ledger-known program: ``dur_us`` wall
    microseconds turn into achieved FLOP/s and MFU.  Returns
    ``{"flops", "mfu"}`` (mfu omitted while the peak is unresolved) or
    None when the program is unknown / accounting is off.  Cheap by
    design — a dict lookup and four float ops — and called only from
    span-recording blocks, so ``MXNET_TELEMETRY=0`` also zeroes it.

    Caveat: on async backends a step-flush wall is DISPATCH time (the
    execution overlaps later python), so the figure is an upper bound
    there; serving execute walls include the host readback and are
    honest.  ``tools/trace_report.py``'s per-step mfu column rescales to
    the step wall (docs/OBSERVABILITY.md)."""
    if not _active or not dur_us or dur_us <= 0:
        return None
    with _lock:
        e = _lookup(handle)
        if e is None or not e["flops"]:
            return None
        flops = e["flops"]
        byts = e["bytes_accessed"]
        achieved = flops / (dur_us * 1e-6)
        peak = _resolve_peak()
        mfu = (achieved / peak[0]) if peak else None
        e["executions"] += 1
        e["last_dur_us"] = round(float(dur_us), 1)
        if mfu is not None:
            e["last_mfu"] = round(mfu, 4)
            if e["best_mfu"] is None or mfu > e["best_mfu"]:
                e["best_mfu"] = round(mfu, 4)
        _executions[0] += 1
        _flops_total[0] += flops
        _bytes_total[0] += byts
        _last[0] = {"key": e["key"], "flops": flops,
                    "dur_us": round(float(dur_us), 1),
                    "achieved_flops": achieved,
                    "mfu": None if mfu is None else round(mfu, 4)}
    out = {"flops": int(flops)}
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    return out


def execution_attrs(handle, dur_us):
    """Span-attribute helper: :func:`record_execution` returning ``{}``
    instead of None so callers can ``extra.update(...)`` unconditionally."""
    return record_execution(handle, dur_us) or {}


def last_execution():
    """The most recent accounted execution (the crash report's
    'last-step MFU'), or None."""
    l = _last[0]
    return dict(l) if l else None


# ---------------------------------------------------------------------------
# per-equation flop estimation (the jaxpr walk)
# ---------------------------------------------------------------------------
# primitives XLA's cost model books under `transcendentals`, not `flops`
_TRANSCENDENTALS = frozenset((
    "exp", "exp2", "expm1", "log", "log2", "log1p", "tanh", "logistic",
    "erf", "erfc", "erf_inv", "sin", "cos", "tan", "asin", "acos", "atan",
    "atan2", "sinh", "cosh", "asinh", "acosh", "atanh", "pow",
    "integer_pow", "sqrt", "rsqrt", "cbrt",
))
# shape/layout plumbing: zero flops
_ZERO_FLOP = frozenset((
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad", "rev",
    "convert_element_type", "bitcast_convert_type", "gather", "scatter",
    "iota", "copy", "device_put", "stop_gradient", "eq", "ne", "lt", "le",
    "gt", "ge", "and", "or", "not", "xor", "is_finite", "sign",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "argmax", "argmin", "reduce_precision", "squeeze", "expand_dims",
    "split", "select_n", "clamp", "sort", "random_seed", "random_wrap",
    "random_bits", "random_fold_in", "threefry2x32",
))


def _aval_size(aval):
    n = 1
    try:
        for d in aval.shape:
            n *= int(d)
    except Exception:               # noqa: BLE001 — scalar / odd aval
        return 1
    return n


def _eqn_cost(eqn):
    """(flops, transcendentals) estimate for one jaxpr equation, mirroring
    XLA's HloCostAnalysis conventions (dot/conv = 2xMACs, elementwise =
    one flop per output element, transcendentals booked separately)."""
    prim = eqn.primitive.name
    # higher-order primitives: recurse into the inner jaxpr
    if prim in ("jit", "custom_jvp_call", "custom_vjp_call", "remat2",
                "closed_call"):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if inner is None:
            return 0.0, 0.0
        return jaxpr_cost(getattr(inner, "jaxpr", inner))
    if prim == "scan":
        inner = eqn.params.get("jaxpr")
        if inner is None:
            return 0.0, 0.0
        f, t = jaxpr_cost(getattr(inner, "jaxpr", inner))
        n = int(eqn.params.get("length", 1) or 1)
        return f * n, t * n
    if prim in ("while", "cond"):
        # count one body/branch pass — honest lower bound, same spirit as
        # XLA's cost model which cannot know trip counts either
        inners = [v for k, v in eqn.params.items()
                  if "jaxpr" in k and v is not None]
        best = (0.0, 0.0)
        for inner in inners:
            try:
                c = jaxpr_cost(getattr(inner, "jaxpr", inner))
                if c[0] >= best[0]:
                    best = c
            except Exception:       # noqa: BLE001
                continue
        return best
    if prim == "dot_general":
        try:
            (lc, _rc), _batch = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            out = eqn.outvars[0].aval
            contract = 1
            for d in lc:
                contract *= int(lhs.shape[d])
            return 2.0 * _aval_size(out) * contract, 0.0
        except Exception:           # noqa: BLE001
            return 0.0, 0.0
    if prim == "conv_general_dilated":
        try:
            rhs = eqn.invars[1].aval
            out = eqn.outvars[0].aval
            dn = eqn.params["dimension_numbers"]
            out_feat_dim = dn.rhs_spec[0]
            k_per_out = 1
            for i, d in enumerate(rhs.shape):
                if i != out_feat_dim:
                    k_per_out *= int(d)
            return 2.0 * _aval_size(out) * k_per_out, 0.0
        except Exception:           # noqa: BLE001
            return 0.0, 0.0
    if prim in _ZERO_FLOP:
        return 0.0, 0.0
    if prim in _TRANSCENDENTALS:
        return 0.0, float(sum(_aval_size(o.aval) for o in eqn.outvars))
    if prim.startswith("reduce_"):
        # reductions pay one op per INPUT element
        return float(sum(_aval_size(v.aval) for v in eqn.invars
                         if hasattr(v, "aval"))), 0.0
    # default: elementwise — one flop per output element
    return float(sum(_aval_size(o.aval) for o in eqn.outvars)), 0.0


def jaxpr_cost(jaxpr):
    """Fold :func:`_eqn_cost` over a (possibly nested) jaxpr —
    ``(flops, transcendentals)``."""
    flops = transc = 0.0
    for eqn in jaxpr.eqns:
        try:
            f, t = _eqn_cost(eqn)
        except Exception:           # noqa: BLE001 — estimation, never fatal
            f = t = 0.0
        flops += f
        transc += t
    return flops, transc


_est_cache: dict = {}       # (fkey, aval sig, used mask) -> (flops, transc)
_EST_CACHE_CAP = 2048


def estimate_fun_cost(fun, kwargs, args, cache_key=None,
                      used_outputs=None):
    """(flops, transcendentals) of ``fun(*args, **kwargs)`` via an
    abstract ``jax.make_jaxpr`` trace.  ``args`` are avals /
    ShapeDtypeStructs / python scalars.  Cached by ``cache_key`` when
    hashable (repeated layers share one trace).

    ``used_outputs``: per-flattened-output liveness mask — dead outputs
    (and everything only they depend on) are dropped with jax's own DCE
    before counting, mirroring what XLA does to the compiled program
    (e.g. the first layer's input-gradient in a captured step feeds
    nothing and is never executed)."""
    if cache_key is not None:
        try:
            cache_key = (cache_key, used_outputs)
            hit = _est_cache.get(cache_key)
        except TypeError:
            cache_key, hit = None, None
        if hit is not None:
            return hit
    import jax
    if kwargs:
        closed = jax.make_jaxpr(lambda *xs: fun(*xs, **kwargs))(*args)
    else:
        closed = jax.make_jaxpr(fun)(*args)
    jaxpr = closed.jaxpr
    if used_outputs is not None and not all(used_outputs) \
            and len(used_outputs) == len(jaxpr.outvars):
        try:
            from jax._src.interpreters import partial_eval as _pe
            jaxpr, _used_ins = _pe.dce_jaxpr(jaxpr, list(used_outputs))
        except Exception:       # noqa: BLE001 — DCE is a refinement only
            pass
    out = jaxpr_cost(jaxpr)
    if cache_key is not None:
        if len(_est_cache) >= _EST_CACHE_CAP:
            for k in list(_est_cache)[:_EST_CACHE_CAP // 4]:
                del _est_cache[k]
        _est_cache[cache_key] = out
    return out


# ---------------------------------------------------------------------------
# block-level attribution of captured segments
# ---------------------------------------------------------------------------
_ATTR_CAP = 64
_attr: OrderedDict = OrderedDict()  # program key -> attribution table


def _is_vjp_key(fkey):
    return isinstance(fkey, tuple) and len(fkey) > 1 \
        and fkey[0] == "__vjp__"


def attribute_segment(op_descs, key=None, kind="lazy_segment", label="",
                      total_flops=None):
    """Fold per-op flop estimates up to originating HybridBlocks for one
    captured segment — called by the engine at segment COMPILE time
    (zero hot-path cost; a flushed cache-hit segment never re-attributes).

    ``op_descs``: one ``(name, block, fun, kwargs, args, fkey,
    used_outputs)`` per recorded op, in record order — ``args`` are the
    op's input avals (ShapeDtypeStructs for slots/externals, python
    scalars verbatim), ``block`` is the recording-time block-scope path
    (None for ops recorded outside any block, e.g. the trainer's fused
    update), and ``used_outputs`` is the per-output liveness mask (an
    output is used when its slot feeds a later op or survives as a
    program output — dead branches are DCE'd before counting, exactly as
    XLA drops them: e.g. the first layer's input-gradient).

    VJP ops (``fkey = ("__vjp__", fwd_fkey, present, diff_pos, ...)``)
    re-trace their forward inside ``jax.vjp``; the captured program CSEs
    the retained primal against the recorded forward op and DCEs the
    dead parts, so the backward estimate is
    ``min(raw - fwd, dce(used))``: ``raw - fwd`` subtracts the full
    primal (right when the transpose keeps primal residual computation
    XLA then CSEs — the fwd estimate is looked up by ``(fwd_fkey,
    forward arg signature)``, recovered by dropping the cotangent prefix
    of the VJP's args), while ``dce(used)`` drops dead cotangent
    branches AND the dead primal (right for matmul-style transposes
    whose primal result feeds nothing).  The minimum is correct for
    both; without any correction a dense stack over-counts ~4/3x.

    Returns the attribution table (also retrievable via
    :func:`attribution`): rows keyed by ``(block, op)`` with flops /
    transcendentals / op count, plus the per-block fold and the coverage
    ratio against ``total_flops`` (the program's ``cost_analysis()``
    figure) when known."""
    if key is None:
        key = f"unkeyed:{next(_unkeyed)}"
    key = str(key)
    rows: OrderedDict = OrderedDict()   # (block, opname) -> row
    fwd_by_fkey: dict = {}
    attributed = 0.0
    transc_total = 0.0
    estimated = failed = 0
    for name, block, fun, kwargs, args, fkey, used in op_descs:
        try:
            sig = tuple(
                (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape")
                else ("py", repr(a)) for a in args)
            ck = None
            if fkey is not None:
                try:
                    ck = (fkey, sig)
                    hash(ck)
                except TypeError:
                    ck = None
            raw, tr = estimate_fun_cost(fun, kwargs, args, cache_key=ck)
            fl = raw
            if used is not None and not all(used):
                fl, tr = estimate_fun_cost(fun, kwargs, args,
                                           cache_key=ck,
                                           used_outputs=tuple(used))
            direction = "forward"
            if _is_vjp_key(fkey):
                direction = "backward"
                present = fkey[2] if len(fkey) > 2 else ()
                n_cots = sum(1 for p in present if p)
                corr = fwd_by_fkey.get((fkey[1], sig[n_cots:]))
                if corr is None:
                    # signature-exact forward not seen (shape drift):
                    # fall back to any forward of the same fun
                    corr = fwd_by_fkey.get(fkey[1], 0.0)
                dce_fl, dce_tr = estimate_fun_cost(
                    fun, kwargs, args, cache_key=ck,
                    used_outputs=tuple(used) if used is not None
                    else None)
                fl = min(max(raw - corr, 0.0), dce_fl)
                tr = dce_tr
            elif fkey is not None:
                # the CSE subtraction target is the FULL primal cost,
                # independent of the forward op's own dead outputs
                fwd_by_fkey[(fkey, sig)] = raw
                fwd_by_fkey[fkey] = raw
            estimated += 1
        except Exception:           # noqa: BLE001 — estimation best-effort
            failed += 1
            continue
        rk = (block or f"({name})", name)
        row = rows.get(rk)
        if row is None:
            row = rows[rk] = {"block": rk[0], "op": name,
                              "direction": direction, "flops": 0.0,
                              "transcendentals": 0.0, "count": 0}
        row["flops"] += fl
        row["transcendentals"] += tr
        row["count"] += 1
        attributed += fl
        transc_total += tr
    blocks: OrderedDict = OrderedDict()
    for row in rows.values():
        b = blocks.get(row["block"])
        if b is None:
            b = blocks[row["block"]] = {"block": row["block"], "flops": 0.0,
                                        "transcendentals": 0.0, "ops": 0}
        b["flops"] += row["flops"]
        b["transcendentals"] += row["transcendentals"]
        b["ops"] += row["count"]
    table = {
        "key": key, "kind": kind, "label": label or "",
        "attributed_flops": attributed,
        "transcendentals": transc_total,
        "ops_estimated": estimated, "ops_failed": failed,
        "rows": sorted(rows.values(), key=lambda r: -r["flops"]),
        "blocks": sorted(blocks.values(), key=lambda b: -b["flops"]),
        "total_flops": total_flops,
        "coverage": (attributed / total_flops)
        if total_flops else None,
        "ts": time.time(),
    }
    with _lock:
        _attr[key] = table
        while len(_attr) > _ATTR_CAP:
            # evict oldest NON-step table first: a shuffled input
            # pipeline compiles a fresh throwaway lazy segment per batch
            # (distinct fingerprints), and those must not churn the ONE
            # captured-step table out of the cache
            victim = next((k for k, t in _attr.items()
                           if t.get("kind") != "step_segment"), None)
            if victim is None:
                _attr.popitem(last=False)
            else:
                _attr.pop(victim)
    return table


def attribution(handle):
    """The attribution table for one program (key / ``pc:<key12>`` /
    prefix), or None."""
    if not handle:
        return None
    h = str(handle)
    with _lock:
        t = _attr.get(h)
        if t is None and h.startswith("pc:"):
            full = _by_prefix.get(h[3:15])
            t = _attr.get(full) if full else None
        if t is None and len(h) >= 12:
            full = _by_prefix.get(h[:12])
            t = _attr.get(full) if full else None
        return dict(t) if t else None


def attributions():
    """All held attribution tables (newest last)."""
    with _lock:
        return [dict(t) for t in _attr.values()]


# ---------------------------------------------------------------------------
# forensics payloads
# ---------------------------------------------------------------------------
def crash_report_payload(hottest=5):
    """The crash-report ``costs`` section (schema v1 of this section;
    report schema v4 — docs/RESILIENCE.md): hottest programs by flops and
    the last accounted execution's MFU."""
    with _lock:
        n_prog = len(_ledger)
    return {
        "schema": 1,
        "enabled": _active,
        "peak": peak_info(),
        "ledger": {"programs": n_prog, "upgrades": _upgrades[0],
                   "hottest": hottest_programs(hottest)},
        "executions": {"count": _executions[0],
                       "flops_total": _flops_total[0],
                       "bytes_accessed_total": _bytes_total[0],
                       "last": last_execution()},
    }


def report_payload(hottest=10):
    """Full payload for ``tools/cost_report.py``: the crash section plus
    every attribution table (the per-block cost tables)."""
    p = crash_report_payload(hottest=hottest)
    p["attributions"] = attributions()
    p["passes"] = pass_ledger()
    return p


def reset():
    """Forget every ledger entry, execution stat and attribution table
    (tests)."""
    global _active
    with _lock:
        _ledger.clear()
        _by_prefix.clear()
        _attr.clear()
        _passes.clear()
        _upgrades[0] = 0
        _flops_max[0] = 0.0
        _executions[0] = 0
        _flops_total[0] = 0.0
        _bytes_total[0] = 0.0
        _last[0] = None
    _est_cache.clear()
    _peak[0] = None
    _active = _read_env()


# ---------------------------------------------------------------------------
# telemetry registration: costs/* through a collector — capture sites are
# compile-time, execution accounting rides the span blocks; the snapshot
# reads plain ints/floats (docs/OBSERVABILITY.md)
# ---------------------------------------------------------------------------
def _telemetry_collect():
    with _lock:
        out = {
            "costs/ledger_programs": len(_ledger),
            "costs/ledger_flops_max": _flops_max[0],
            "costs/ledger_upgrades": _upgrades[0],
            "costs/executions": _executions[0],
            "costs/flops_executed_total": _flops_total[0],
            "costs/bytes_accessed_total": _bytes_total[0],
            "costs/attributions": len(_attr),
        }
        last = _last[0]
    out["costs/last_mfu"] = (last or {}).get("mfu") or 0.0
    out["costs/last_achieved_flops"] = \
        (last or {}).get("achieved_flops") or 0.0
    p = _peak[0]
    out["costs/peak_flops"] = p[0] if p else 0.0
    return out


_telemetry.register_collector("costs", _telemetry_collect, {
    "costs/ledger_programs": ("gauge", "per-program cost-ledger entries"),
    "costs/ledger_flops_max": ("gauge",
                               "largest per-execution flops figure in "
                               "the ledger"),
    "costs/ledger_upgrades": ("counter",
                              "warm cost-ledger entries upgraded by a "
                              "fresh compile of the same key"),
    "costs/executions": ("counter",
                         "executions accounted against the cost ledger "
                         "(step flushes + serving dispatches of "
                         "ledger-known programs)"),
    "costs/flops_executed_total": ("counter",
                                   "total flops of accounted executions "
                                   "(monotonic)"),
    "costs/bytes_accessed_total": ("counter",
                                   "total HLO bytes-accessed of "
                                   "accounted executions (monotonic)"),
    "costs/attributions": ("gauge",
                           "per-block attribution tables held for "
                           "captured segments"),
    "costs/last_mfu": ("gauge",
                       "MFU of the most recent accounted execution "
                       "(0 until the peak-FLOP table resolves)"),
    "costs/last_achieved_flops": ("gauge",
                                  "achieved FLOP/s of the most recent "
                                  "accounted execution"),
    "costs/peak_flops": ("gauge",
                         "resolved peak FLOP/s (0 while unresolved — no "
                         "live backend, or the CPU, and no "
                         "MXNET_PEAK_FLOPS override)"),
})
