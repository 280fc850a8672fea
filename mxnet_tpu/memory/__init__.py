"""mxnet_tpu.memory — device-memory observability: live-array census,
per-program memory ledger, phase-correlated HBM peaks, and OOM forensics.

On a TPU the scarce resource is HBM, yet the rest of the observability
stack (step-phase spans, request traces) measures only *time*.  This
module answers the memory questions:

* **Live-array census** — every device-backed ``NDArray`` (and the raw
  ``jax.Array`` batches the stagers place) registers into a weakref-only
  registry tagged with an *origin class*: ``parameter`` / ``gradient`` /
  ``optimizer_state`` / ``activation`` / ``pending`` (deferred
  lazy-segment placeholders) / ``serving_batch`` / ``prefetch_staged``.
  Per-origin byte totals are maintained incrementally (a register or a
  GC retire is a couple of dict adds), so reading "what is resident
  right now" costs a handful of int reads; :func:`census` additionally
  walks the live set for the origin x dtype x sharding breakdown with
  buffer-identity dedup (aliasing wrappers counted once).  GC'd arrays
  fold into monotonic retired accumulators (the PR-7 retired-accumulator
  contract), and all of it surfaces as ``memory/*`` gauges through a
  zero-hot-path-cost telemetry collector.
* **Per-program memory ledger** — every compile / AOT / ProgramCache
  warm-load records ``Compiled.memory_analysis()`` (XLA's buffer
  assignment: argument / output / temp / peak bytes — works on CPU, so
  tier-1 asserts it) into a ledger keyed by the ProgramCache key.
  ``step_flush`` / serving ``execute`` spans carry a ``bytes`` attribute
  looked up here, so ``tools/trace_report.py`` shows bytes next to
  milliseconds.
* **Phase-correlated peaks** — at every span boundary the backend's
  ``memory_stats()`` (when the platform provides it — never probed
  before the backend initialized) or the census estimate is sampled:
  ``memory/device_bytes_in_use`` chrome-trace counter tracks, per-phase
  peak table, and a bounded sample ring (with per-origin bytes) that
  powers ``tools/memory_report.py``'s leak-detection mode.
* **OOM forensics** — :func:`crash_report_payload` (the ``memory``
  section of crash reports, schema v3) names the top census origins, the
  hottest ledger entries (the peak-owning ProgramCache key), and the
  last phase peaks; :func:`release_cached_memory` is the
  resource-exhausted recovery lever (purge executable caches + jax
  caches + gc) behind ``faults.classify``'s ``resource`` class.

Always-on by design (``MXNET_MEMORY``, default on).
``memory.enable(False)`` turns every census/sampling call into an
attribute check.  Bytes are *global*
logical bytes (a sharded array counts its full global size; divide by
the shard count for per-chip HBM).  Metric tables, the crash-report
schema and the ``memory_report`` recipe: docs/OBSERVABILITY.md and
docs/RESILIENCE.md.
"""
from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import OrderedDict, deque

from .. import telemetry as _telemetry
from ..util import getenv

__all__ = [
    "ORIGINS", "enabled", "enable", "register", "tag", "tag_tree",
    "materialized", "census", "census_bytes_total", "live_bytes",
    "origin_of",
    "allocated_bytes", "retired_bytes", "record_program", "ledger",
    "ledger_peak", "hottest_programs", "ledger_upgrades", "sample_now",
    "samples",
    "phase_peaks", "device_bytes_in_use", "peak_bytes_in_use",
    "release_cached_memory", "crash_report_payload", "reset",
]

#: the census origin classes (docs/OBSERVABILITY.md).  ``pending`` is
#: the engine's segment-level deferred-slot accounting (bytes the live
#: lazy segments will materialize at flush — see
#: :func:`set_pending_bytes_fn`); materialized slots enter the registry
#: as ``activation``.
ORIGINS = ("parameter", "gradient", "optimizer_state", "activation",
           "pending", "serving_batch", "prefetch_staged", "kv_cache")

# dedup priority when one device buffer is reachable through wrappers of
# different origins (census() walk): the most load-bearing class wins
_ORIGIN_RANK = {o: i for i, o in enumerate(
    ("parameter", "optimizer_state", "gradient", "kv_cache",
     "serving_batch", "prefetch_staged", "pending", "activation"))}


# ---------------------------------------------------------------------------
# on/off switch (module attribute read directly by the NDArray hot path)
# ---------------------------------------------------------------------------
def _read_env():
    return bool(getenv("MXNET_MEMORY"))


_census_active = _read_env()


def enabled():
    """Census + span-boundary sampling on?  (``MXNET_MEMORY``, default
    on; the ledger is never gated — recording a compile's memory
    analysis is off the hot path by definition.)"""
    return _census_active


def enable(flag=True):
    """Override the env switch for this process (``enable(None)``
    re-reads ``MXNET_MEMORY``)."""
    global _census_active
    _census_active = _read_env() if flag is None else bool(flag)
    _telemetry.set_memory_sampler(_span_sample if _census_active else None)


# ---------------------------------------------------------------------------
# the census
# ---------------------------------------------------------------------------
class _Entry(weakref.ref):
    """One census entry IS its weakref: a single allocation per array
    (the register path runs per NDArray creation, and extra per-entry
    objects both cost time and drive gc generation churn).  Identity
    hash/eq: ``weakref.ref`` delegates both to the referent, which for a
    raw ``jax.Array`` is unhashable — and the ``_entries`` set is a set
    of entries, not of referents."""

    __slots__ = ("origin", "nbytes", "oid")
    __hash__ = object.__hash__
    __eq__ = object.__eq__
    __ne__ = object.__ne__


_lock = threading.Lock()
_entries: set = set()           # live _Entry refs (callback-pruned)
_by_id: dict = {}               # id(obj) -> _Entry (callback-pruned)
_live = {o: 0 for o in ORIGINS}
_retired_by_origin = {o: 0 for o in ORIGINS}
_allocated = [0]                # monotonic: total bytes ever registered
_retired = [0]                  # monotonic: total bytes of GC'd arrays

_tracer_cls = [None]            # jax Tracer class, resolved lazily


def _is_tracer(x):
    cls = _tracer_cls[0]
    if cls is None:
        try:
            from jax._src.core import Tracer
        except Exception:       # noqa: BLE001 — no jax yet: nothing traces
            return False
        cls = _tracer_cls[0] = Tracer
    return isinstance(x, cls)


_itemsize_cache: dict = {}      # dtype -> itemsize (dtype objects hashable)


def _nbytes_of(obj):
    """Logical byte size of an NDArray / raw array / abstract value, or
    None for tracers and unsized objects.  Never touches the device —
    and never reads ``jax.Array.nbytes`` (a ~5 µs python property; this
    path runs per NDArray creation, so bytes come from the cached
    abstract value instead, ~1 µs)."""
    a = getattr(obj, "_aval", obj)      # NDArray -> raw buffer / pending aval
    if a is None or _is_tracer(a):
        return None
    a = getattr(a, "aval", a)           # jax.Array -> ShapedArray (cheap)
    try:
        shape = a.shape
        dt = a.dtype
    except Exception:           # noqa: BLE001 — unsized: not census-able
        return None
    try:
        isz = _itemsize_cache[dt]
    except (KeyError, TypeError):
        try:
            import numpy as onp
            isz = int(onp.dtype(dt).itemsize)
            _itemsize_cache[dt] = isz
        except Exception:       # noqa: BLE001
            return None
    n = isz
    for d in shape:
        n *= d
    return int(n)


# Dead entries are NOT folded inside the weakref callback: a callback
# can fire synchronously from a cyclic-gc pass triggered by an
# allocation made while THIS module holds ``_lock`` (register/census
# build containers under it) — taking the lock there self-deadlocks.
# The callback only appends to a lock-free deque (reentrancy-safe);
# every reader/register drains it under the lock, which also batches N
# retires into one acquisition.
_dead: deque = deque()


def _on_dead(e):
    _dead.append(e)


def _drain_dead():
    if not _dead:
        return
    with _lock:
        while True:
            try:
                e = _dead.popleft()
            except IndexError:
                break
            if e not in _entries:
                continue
            _entries.discard(e)
            if _by_id.get(e.oid) is e:
                del _by_id[e.oid]
            _live[e.origin] -= e.nbytes
            _retired_by_origin[e.origin] += e.nbytes
            _retired[0] += e.nbytes


def register(obj, origin="activation"):
    """Add one device-backed array (NDArray or raw ``jax.Array``) to the
    census under ``origin``.  Weakref-only: the census never extends a
    lifetime.  Tracers and unsized objects are ignored.  Re-registering
    a live object just (re)tags it."""
    if not _census_active:
        return obj
    _drain_dead()
    oid = id(obj)
    e = _by_id.get(oid)
    if e is not None and e() is obj:
        if e.origin != origin:
            _move_origin(e, origin)
        return obj
    nbytes = _nbytes_of(obj)
    if nbytes is None:
        return obj
    try:
        e = _Entry(obj, _on_dead)
    except TypeError:
        return obj
    e.origin = origin
    e.nbytes = nbytes
    e.oid = oid
    with _lock:
        _entries.add(e)
        _by_id[oid] = e
        _live[origin] += nbytes
        _allocated[0] += nbytes
    p = getattr(obj, "_pending", None)
    if p is not None:
        # a still-deferred NDArray just gained a registry origin (e.g.
        # the trainer tagging pending optimizer-state outputs): its
        # bytes are now counted there, so the segment-level deferred
        # accounting must release the slot (no double count)
        try:
            p[0].discount_slot(p[1])
        except Exception:       # noqa: BLE001 — accounting, never fatal
            pass
    return obj


def _move_origin(e, origin):
    with _lock:
        old = e.origin
        if old == origin:
            return
        e.origin = origin
        _live[old] -= e.nbytes
        _live[origin] += e.nbytes


def tag(obj, origin):
    """(Re)tag one array's census origin, registering it if unseen."""
    return register(obj, origin)


def tag_tree(tree, origin):
    """Map :func:`tag` over the array leaves of nested tuples / lists /
    dicts (optimizer state pytrees, batch structures)."""
    if not _census_active or tree is None:
        return tree
    if isinstance(tree, (tuple, list)):
        for e in tree:
            tag_tree(e, origin)
    elif isinstance(tree, dict):
        for e in tree.values():
            tag_tree(e, origin)
    elif hasattr(tree, "shape"):
        register(tree, origin)
    return tree


# Deferred (pending) bytes are accounted at the SEGMENT level, not per
# placeholder: a per-placeholder weakref entry cost ~3.5 µs + one gc-
# tracked object for every recorded op output — ~500/step of pure churn
# in a captured BERT-base-width step, most of which are adopted into
# already-tracked params/grads or DCE'd without ever owning a device
# buffer.  The engine maintains one pending-bytes counter (incremented
# per recorded slot, decremented at flush) and installs a reader here.
_pending_bytes_fn = [None]


def set_pending_bytes_fn(fn):
    """Install the deferred-bytes reader (``mxnet_tpu.engine`` owns the
    only production caller)."""
    _pending_bytes_fn[0] = fn


def _pending_bytes():
    fn = _pending_bytes_fn[0]
    if fn is None:
        return 0, 0
    try:
        return fn()
    except Exception:           # noqa: BLE001
        return 0, 0


def materialized(nd):
    """Flush-writeback hook: a freshly-materialized slot enters the
    census as an ``activation`` — unless its NDArray is already tracked
    (a parameter/gradient re-adopted through ``adopt_pending`` keeps its
    tag)."""
    if not _census_active:
        return
    e = _by_id.get(id(nd))
    if e is not None and e() is nd:
        return
    register(nd, "activation")


def origin_of(obj):
    """The census origin of a live array, or None if unregistered
    (introspection/tests)."""
    e = _by_id.get(id(obj))
    if e is None or e() is not obj:
        return None
    return e.origin


def live_bytes():
    """Incremental per-origin live byte totals (upper bound: wrappers
    aliasing one buffer each count — :func:`census` dedups).  The
    ``pending`` figure is the engine's deferred-slot accounting: bytes
    the live lazy segments may materialize at their next flush — slots
    adopted into registered arrays are discounted (no double count),
    and slots whose placeholders die before flush are DCE'd, so it is
    an upper bound on what will actually land."""
    _drain_dead()
    with _lock:
        out = dict(_live)
    out["pending"] = out["pending"] + _pending_bytes()[0]
    return out


def census_bytes_total():
    """Total live census bytes (the sampling estimate), deferred
    segment slots included."""
    _drain_dead()
    with _lock:
        t = sum(_live.values())
    return t + _pending_bytes()[0]


def allocated_bytes():
    _drain_dead()
    return _allocated[0]


def retired_bytes():
    _drain_dead()
    return _retired[0]


def _sharding_desc(raw):
    try:
        sh = raw.sharding
        spec = getattr(sh, "spec", None)
        if spec is not None:
            return f"{type(sh).__name__}{tuple(spec)}"
        return type(sh).__name__
    except Exception:           # noqa: BLE001 — host arrays, avals
        return "host"


def census(top_k=None):
    """Walk the live registry: bytes and array counts by origin and by
    origin x dtype x sharding, **deduplicated by buffer identity** (two
    NDArrays sharing one ``jax.Array`` count once, highest-priority
    origin wins).  This is the accurate view crash reports and the
    referee test use; the ``memory/*`` gauges are the cheap incremental
    totals."""
    _drain_dead()
    with _lock:
        snap = [(e(), e.origin, e.nbytes) for e in _entries]
    best: dict = {}             # buffer id -> (rank, origin, obj, nbytes)
    for obj, origin, nbytes in snap:
        if obj is None:
            continue
        raw = getattr(obj, "_data", obj)
        bid = id(raw) if raw is not None else id(obj)
        rank = _ORIGIN_RANK.get(origin, 99)
        cur = best.get(bid)
        if cur is None or rank < cur[0]:
            best[bid] = (rank, origin, obj, nbytes)
    by_origin: dict = {}
    groups: dict = {}
    total = 0
    for _rank, origin, obj, nbytes in best.values():
        total += nbytes
        o = by_origin.setdefault(origin, {"bytes": 0, "arrays": 0})
        o["bytes"] += nbytes
        o["arrays"] += 1
        aval = getattr(obj, "_aval", obj)
        try:
            dtype = str(aval.dtype)
        except Exception:       # noqa: BLE001
            dtype = "?"
        raw = getattr(obj, "_data", obj)
        key = (origin, dtype, _sharding_desc(raw))
        g = groups.setdefault(key, {"origin": origin, "dtype": dtype,
                                    "sharding": key[2], "bytes": 0,
                                    "arrays": 0})
        g["bytes"] += nbytes
        g["arrays"] += 1
    pb, pc = _pending_bytes()
    if pb or pc:
        # deferred slots live in the engine's segment accounting, not as
        # registry entries — surface them as one synthetic group
        o = by_origin.setdefault("pending", {"bytes": 0, "arrays": 0})
        o["bytes"] += pb
        o["arrays"] += pc
        total += pb
        g = groups.setdefault(("pending", "-", "deferred"),
                              {"origin": "pending", "dtype": "-",
                               "sharding": "deferred", "bytes": 0,
                               "arrays": 0})
        g["bytes"] += pb
        g["arrays"] += pc
    top = sorted(({"origin": k, **v} for k, v in by_origin.items()),
                 key=lambda r: -r["bytes"])
    if top_k:
        top = top[:int(top_k)]
    with _lock:
        retired = dict(_retired_by_origin)
    return {
        "total_bytes": total,
        "by_origin": by_origin,
        "top": top,
        "groups": sorted(groups.values(), key=lambda g: -g["bytes"]),
        "allocated_bytes_total": _allocated[0],
        "retired_bytes_total": _retired[0],
        "retired_by_origin": retired,
    }


# ---------------------------------------------------------------------------
# per-program memory ledger
# ---------------------------------------------------------------------------
_LEDGER_CAP = 4096
_ledger_lock = threading.Lock()
_ledger: OrderedDict = OrderedDict()    # key -> entry dict
_by_prefix: dict = {}                   # key[:12] -> key (pc:* span labels)
_unkeyed = itertools.count(1)
_ledger_peak_max = [0]
_ledger_upgrades = [0]


def record_program(compiled, key=None, label="", kind="op", warm=False):
    """Record one compiled executable's ``memory_analysis()`` into the
    ledger under its ProgramCache ``key`` (or a synthetic key when the
    program is not cache-indexed).  Called at every compile, AOT compile
    and warm-load; defensive — a backend without memory analysis returns
    None and costs nothing.  Returns a copy of the ledger entry.

    ``warm=True`` marks a DESERIALIZED executable (ProgramCache /
    persistent-compile-cache load): its ``memory_analysis()`` loses the
    input-output alias table, so a donating program's peak reads
    donated-bytes too high.  Warm entries are flagged
    (``analysis='warm'``) and a later fresh compile of the same key
    upgrades the numbers; an existing fresh entry is never downgraded."""
    if compiled is None:
        return None
    try:
        ma = compiled.memory_analysis()
        arg = int(ma.argument_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        tmp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        gen = int(ma.generated_code_size_in_bytes)
    except Exception:           # noqa: BLE001 — analysis is best-effort
        return None
    # XLA's buffer assignment high-water mark: everything resident while
    # the program runs.  Aliased (donated) argument buffers are reused
    # for outputs, so they count once.
    peak = arg + out + tmp + gen - alias
    if key is None:
        key = f"unkeyed:{next(_unkeyed)}"
    key = str(key)
    with _ledger_lock:
        e = _ledger.get(key)
        if e is None:
            e = _ledger[key] = {
                "key": key, "label": label or "", "kind": kind or "op",
                "argument_bytes": arg, "output_bytes": out,
                "temp_bytes": tmp, "alias_bytes": alias,
                "generated_code_bytes": gen, "peak_bytes": peak,
                "analysis": "warm" if warm else "fresh",
                "compiles": 1, "ts": time.time(),
            }
            _by_prefix[key[:12]] = key
            while len(_ledger) > _LEDGER_CAP:
                old_key, _old = _ledger.popitem(last=False)
                _by_prefix.pop(old_key[:12], None)
        else:
            e["compiles"] += 1
            if label and not e["label"]:
                e["label"] = label
            if not warm and e.get("analysis") == "warm":
                # fresh compile of a key first seen as a warm load:
                # upgrade the (alias-stripped) numbers — explicit and
                # counted (memory/ledger_upgrades), so 'how much of the
                # ledger is still warm-flagged' is an observable, not an
                # implicit side effect
                e.update(argument_bytes=arg, output_bytes=out,
                         temp_bytes=tmp, alias_bytes=alias,
                         generated_code_bytes=gen, peak_bytes=peak,
                         analysis="fresh")
                _ledger_upgrades[0] += 1
        if peak > _ledger_peak_max[0]:
            _ledger_peak_max[0] = peak
        return dict(e)


def ledger():
    """All ledger entries (insertion order, oldest first)."""
    with _ledger_lock:
        return [dict(e) for e in _ledger.values()]


def ledger_peak(handle):
    """Peak bytes for a program by ProgramCache key or by the serving
    ``pc:<key12>`` span label; None when the ledger has not seen it."""
    if not handle:
        return None
    h = str(handle)
    with _ledger_lock:
        e = _ledger.get(h)
        if e is None and h.startswith("pc:"):
            full = _by_prefix.get(h[3:15])
            e = _ledger.get(full) if full else None
        return e["peak_bytes"] if e else None


def hottest_programs(n=5):
    """Top-N ledger entries by peak bytes — 'which compiled program owns
    the peak' (crash-report ``memory.ledger.hottest``)."""
    with _ledger_lock:
        es = sorted(_ledger.values(), key=lambda e: -e["peak_bytes"])
        return [dict(e) for e in es[:int(n)]]


def ledger_upgrades():
    """Warm-entry upgrades performed (a fresh compile replacing the
    alias-stripped numbers of a warm-loaded entry)."""
    return _ledger_upgrades[0]


# ---------------------------------------------------------------------------
# phase-correlated sampling (hooked into telemetry.add_span)
# ---------------------------------------------------------------------------
_sample_lock = threading.Lock()     # guards the ring + phase-peak table
_sample_ring = [None]           # deque, env-sized lazily
_phase_peaks: dict = {}         # phase -> {"peak_bytes", "step", "ts_us"}
_device_bytes = [0]
_peak_bytes = [0]
_nsamples = [0]
_backend_dev = [None]           # None = unresolved, False = unavailable


def _get_ring():
    ring = _sample_ring[0]
    if ring is None:
        ring = _sample_ring[0] = deque(
            maxlen=max(64, int(getenv("MXNET_MEMORY_RING"))))
    return ring


def _probe_backend():
    """Resolve the backend memory_stats() source WITHOUT initializing a
    backend: while jax has no live backend this stays unresolved and the
    census estimate is used (preserving the no-backend-contact contracts
    of the compile-cache paths)."""
    dev = _backend_dev[0]
    if dev is not None:
        return dev
    try:
        from jax._src import xla_bridge as _xb
        if not getattr(_xb, "_backends", None):
            return None         # backend not up yet: stay unresolved
        import jax
        d = jax.local_devices()[0]
        ms = d.memory_stats()
        if ms and "bytes_in_use" in ms:
            _backend_dev[0] = d
            return d
        _backend_dev[0] = False
        return False
    except Exception:           # noqa: BLE001 — probing must never raise
        _backend_dev[0] = False
        return False


def _span_sample(phase, step, ts_us):
    """The telemetry span-boundary hook: one memory sample correlated
    with the span that just closed.  Backend ``memory_stats()`` when the
    platform provides it, else the census estimate."""
    source = "census"
    b = None
    dev = _probe_backend()
    if dev:
        try:
            ms = dev.memory_stats()
            b = int(ms.get("bytes_in_use", 0))
            source = "backend"
            pk = ms.get("peak_bytes_in_use")
            if pk is not None and int(pk) > _peak_bytes[0]:
                _peak_bytes[0] = int(pk)
        except Exception:       # noqa: BLE001
            b = None
    origins = live_bytes()
    if b is None:
        b = sum(origins.values())
    _device_bytes[0] = b
    if b > _peak_bytes[0]:
        _peak_bytes[0] = b
    _nsamples[0] += 1
    rec = {"ts_us": int(ts_us), "step": step, "phase": phase,
           "bytes": b, "source": source, "origins": origins}
    ring = _get_ring()
    with _sample_lock:
        ring.append(rec)
        pk = _phase_peaks.get(phase)
        if pk is None or b > pk["peak_bytes"]:
            _phase_peaks[phase] = {"peak_bytes": b, "step": step,
                                   "ts_us": int(ts_us), "source": source}
    from .. import profiler as _profiler
    if _profiler.is_running():
        _profiler.record_counter("memory/device_bytes_in_use", b)


def sample_now(phase="manual", step=None):
    """Take one sample outside any span (tests, REPL forensics).  Same
    clock as span-boundary samples (``perf_counter_ns``-derived µs), so
    manual samples order correctly against the rest of the ring."""
    if _census_active:
        _span_sample(phase, step, time.perf_counter_ns() // 1000)
    return _device_bytes[0]


def samples(limit=None):
    """The sample ring, oldest first.  Copied under the sample lock — a
    crash report built while another thread closes spans must not race
    the deque (the telemetry ring makes the same guarantee)."""
    ring = _sample_ring[0]
    if ring is None:
        return []
    with _sample_lock:
        out = list(ring)
    if limit:
        out = out[-int(limit):]
    return out


def phase_peaks():
    """Per-phase peak table: ``{phase: {"peak_bytes", "step", "ts_us",
    "source"}}`` over the process life (reset with :func:`reset`)."""
    with _sample_lock:
        return {k: dict(v) for k, v in _phase_peaks.items()}


def device_bytes_in_use():
    """Latest sampled device bytes (backend or census estimate)."""
    return _device_bytes[0]


def peak_bytes_in_use():
    """High-water mark over all samples."""
    return _peak_bytes[0]


def sample_source():
    """'backend' when the platform's memory_stats() feeds the samples,
    'census' when the estimate does."""
    return "backend" if _backend_dev[0] not in (None, False) else "census"


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
def release_cached_memory():
    """Best-effort memory release for RESOURCE_EXHAUSTED recovery: drop
    the engine's executable caches, jax's jit caches, and run a gc pass
    so dead device buffers actually free.  Returns a dict of what was
    released.  Everything recompiles on demand afterwards — correctness
    is unaffected, only warm-start time."""
    freed = {}
    try:
        from .. import engine as _engine
        freed["engine_executables"] = _engine.purge_executable_caches()
    except Exception:           # noqa: BLE001 — recovery must not raise
        freed["engine_executables"] = None
    try:
        import jax
        jax.clear_caches()
        freed["jax_caches"] = True
    except Exception:           # noqa: BLE001
        freed["jax_caches"] = False
    import gc
    freed["gc_collected"] = gc.collect()
    return freed


def crash_report_payload(top_k=5, hottest=5, sample_limit=256):
    """The crash-report ``memory`` section (schema v1 of this section;
    report schema v3 — docs/RESILIENCE.md): census top-K by origin,
    hottest ledger entries (the peak-owning ProgramCache keys), per-phase
    peaks and the recent sample tail."""
    try:
        c = census(top_k=top_k)
    except Exception:           # noqa: BLE001 — reports must never fail
        c = None
    return {
        "schema": 1,
        "enabled": _census_active,
        "census": c,
        "ledger": {"programs": len(_ledger),
                   "hottest": hottest_programs(hottest)},
        "peaks": {"source": sample_source(),
                  "device_bytes_in_use": _device_bytes[0],
                  "peak_bytes_in_use": _peak_bytes[0],
                  "by_phase": phase_peaks()},
        "samples": samples(limit=sample_limit),
    }


def reset():
    """Forget every census entry, ledger entry, sample and peak (tests).
    Pending weakref callbacks from before the reset become no-ops."""
    global _census_active
    _dead.clear()
    with _lock:
        _entries.clear()
        _by_id.clear()
        for o in ORIGINS:
            _live[o] = 0
            _retired_by_origin[o] = 0
        _allocated[0] = 0
        _retired[0] = 0
    with _ledger_lock:
        _ledger.clear()
        _by_prefix.clear()
        _ledger_peak_max[0] = 0
        _ledger_upgrades[0] = 0
    ring = _sample_ring[0]
    with _sample_lock:
        if ring is not None:
            ring.clear()
        _phase_peaks.clear()
    _device_bytes[0] = 0
    _peak_bytes[0] = 0
    _nsamples[0] = 0
    _census_active = _read_env()
    _telemetry.set_memory_sampler(_span_sample if _census_active else None)


# ---------------------------------------------------------------------------
# telemetry registration: memory/* through a collector — the census hot
# path (register / retire / tag) never touches the registry; snapshot
# reads the incremental totals (docs/OBSERVABILITY.md)
# ---------------------------------------------------------------------------
def _telemetry_collect():
    live = live_bytes()
    with _lock:
        arrays = len(_entries)
    out = {"memory/live_bytes_" + o: live[o] for o in ORIGINS}
    out["memory/live_bytes_total"] = sum(live.values())
    out["memory/live_arrays"] = arrays
    out["memory/allocated_bytes_total"] = _allocated[0]
    out["memory/retired_bytes_total"] = _retired[0]
    out["memory/device_bytes_in_use"] = _device_bytes[0]
    out["memory/peak_bytes_in_use"] = _peak_bytes[0]
    out["memory/samples"] = _nsamples[0]
    out["memory/sample_backend"] = int(sample_source() == "backend")
    with _ledger_lock:
        out["memory/ledger_programs"] = len(_ledger)
        out["memory/ledger_peak_bytes"] = _ledger_peak_max[0]
        out["memory/ledger_upgrades"] = _ledger_upgrades[0]
    return out


_telemetry.register_collector("memory", _telemetry_collect, {
    "memory/live_bytes_parameter": ("gauge", "live census bytes: parameters"),
    "memory/live_bytes_gradient": ("gauge", "live census bytes: gradients"),
    "memory/live_bytes_optimizer_state": ("gauge",
                                          "live census bytes: optimizer "
                                          "state"),
    "memory/live_bytes_activation": ("gauge",
                                     "live census bytes: activations"),
    "memory/live_bytes_pending": ("gauge",
                                  "live census bytes: deferred lazy-segment "
                                  "placeholders"),
    "memory/live_bytes_serving_batch": ("gauge",
                                        "live census bytes: staged serving "
                                        "request batches"),
    "memory/live_bytes_prefetch_staged": ("gauge",
                                          "live census bytes: "
                                          "prefetch-staged input batches"),
    "memory/live_bytes_kv_cache": ("gauge",
                                   "live census bytes: device-resident "
                                   "generation KV-cache ring buffers"),
    "memory/live_bytes_total": ("gauge", "live census bytes, all origins"),
    "memory/live_arrays": ("gauge", "live census entries"),
    "memory/allocated_bytes_total": ("counter",
                                     "bytes ever registered (monotonic)"),
    "memory/retired_bytes_total": ("counter",
                                   "bytes of GC'd arrays folded into the "
                                   "retired accumulator (monotonic)"),
    "memory/device_bytes_in_use": ("gauge",
                                   "latest span-boundary sample (backend "
                                   "memory_stats or census estimate)"),
    "memory/peak_bytes_in_use": ("gauge",
                                 "high-water mark over all samples"),
    "memory/samples": ("counter", "span-boundary memory samples taken"),
    "memory/sample_backend": ("gauge",
                              "1 when backend memory_stats() feeds the "
                              "samples, 0 for the census estimate"),
    "memory/ledger_programs": ("gauge", "per-program ledger entries"),
    "memory/ledger_peak_bytes": ("gauge",
                                 "largest program peak in the ledger"),
    "memory/ledger_upgrades": ("counter",
                               "warm (alias-stripped) ledger entries "
                               "upgraded by a fresh compile of the same "
                               "key"),
})

# arm the span-boundary sampler (the hook is a no-op constant when the
# census is off)
_telemetry.set_memory_sampler(_span_sample if _census_active else None)
