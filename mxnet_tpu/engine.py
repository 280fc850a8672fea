"""Execution engine: lazy fused dispatch for the imperative NDArray path
(reference: ``src/engine/`` ThreadedEngine + ``src/imperative/cached_op.cc``,
SURVEY.md N1/§5.2).

The reference needs a 6k-LoC dependency engine because each CUDA kernel is an
independently-launched task whose read/write ordering must be tracked with
per-variable versions.  On this stack XLA/PjRt order operations by data
dependence, so what an *engine* still buys is *dispatch amortization*: an
un-jitted eager op pays full JAX tracing on every call, far more host time
than the device spends on the op.  Two tiers close that gap (the operator-
fusion lever of arXiv:2301.13062 / arXiv:1802.04799):

- **per-op executable cache** (:func:`cached_call`): every eager
  non-recording op executes through a ``jax.jit``-compiled executable keyed
  by ``(fun code, closure, static kwargs, input avals)``.  Expensive
  compiles additionally persist across processes through
  ``mxnet_tpu.compile.ProgramCache``;
- **lazy bulking** (``MXNET_ENGINE_TYPE=LazyEngine`` or a functional
  ``bulk(size)`` scope): chains of non-autograd ops are *recorded* onto
  pending placeholder NDArrays and flushed as ONE fused, signature-cached
  jit program at materialization boundaries — ``asnumpy``/``asscalar``/
  ``item``/``wait_to_read``/``waitall``, value-dependent control flow
  (``__bool__`` etc.), ``autograd.record()`` entry, mutation of a pending
  input, and ``naive_engine_scope``.

``NaiveEngine`` mode (``MXNET_ENGINE_TYPE=NaiveEngine``) still forces fully
synchronous execution — it overrides both tiers.  Flush rules and env vars
are documented in ``docs/ENGINE.md``.
"""
from __future__ import annotations

import threading
import weakref

from . import costs as _costs
from . import memory as _memory
from . import telemetry as _telemetry
from .base import MXNetError
from .util import getenv

__all__ = ["is_sync", "is_lazy", "set_engine_type", "engine_type",
           "naive_engine_scope", "bulk", "wait_for_var", "wait_all",
           "cached_call", "record_lazy", "flush", "flush_all", "flush_array",
           "engine_stats", "reset_op_cache", "lazy_enabled", "op_cache_scope",
           "step_capture_enabled", "capture_active", "seal", "adopt_pending",
           "purge_executable_caches", "donation_enabled",
           "DonatedBuffersLost", "push_block", "pop_block", "current_block",
           "block_scope"]

_state = {"sync": None, "lazy": None}
_tls = threading.local()

# process-wide caches (guarded by _cache_lock; execution happens outside it)
_cache_lock = threading.Lock()
_op_cache: dict = {}            # op key -> _OpEntry
_segment_cache: dict = {}       # segment signature -> compiled callable
_segment_pc_keys: dict = {}     # segment signature -> ProgramCache key (for
                                # invalidating a corrupt persisted artifact)
_shape_cache: dict = {}         # (op key, input aval keys) -> out avals
_op_cache_cap = 1024
_segment_cache_cap = 256
_shape_cache_cap = 4096
_stats = {"op_cache_hits": 0, "op_cache_misses": 0, "op_cache_fallbacks": 0,
          "op_cache_persist_hits": 0, "lazy_ops_recorded": 0,
          "lazy_flushes": 0, "lazy_segment_cache_hits": 0,
          "lazy_segment_cache_misses": 0, "lazy_eager_replays": 0,
          "tape_ops_recorded": 0, "step_flushes": 0,
          "step_capture_fallbacks": 0, "cache_purges": 0,
          "donated_flushes": 0}

# live segments (cross-thread flush / waitall); WeakSet: a segment whose
# every placeholder died needs no flush to stay correct.  The lock guards
# add vs snapshot — a recording thread adding while flush_all() iterates
# would raise 'set changed size during iteration' (GC-driven removals are
# already deferred by WeakSet itself)
_segments_lock = threading.Lock()
_live_segments = weakref.WeakSet()

# deferred-slot memory accounting for the census (mxnet_tpu.memory):
# bytes + slot count the live segments will materialize at flush.  One
# counter updated per recorded slot / per flush — NOT one weakref entry
# per placeholder, which measured ~3.5 µs + a gc-tracked object for
# every op output of a captured step (the mem_overhead_always_on bar)
_pending_acct_lock = threading.Lock()
_pending_bytes = [0]
_pending_slots = [0]


def _pending_acct():
    return _pending_bytes[0], _pending_slots[0]


_memory.set_pending_bytes_fn(_pending_acct)


# ---------------------------------------------------------------------------
# engine-type state
# ---------------------------------------------------------------------------
def _refresh():
    if _state["sync"] is None:
        name = getenv("MXNET_ENGINE_TYPE")
        _state["sync"] = name == "NaiveEngine"
        _state["lazy"] = name == "LazyEngine"


def is_sync() -> bool:
    if getattr(_tls, "sync_depth", 0):
        return True
    _refresh()
    return _state["sync"]


def is_lazy() -> bool:
    """True when the process-level engine type is LazyEngine."""
    _refresh()
    return _state["lazy"]


def engine_type() -> str:
    if is_sync():
        return "NaiveEngine"
    return "LazyEngine" if is_lazy() else "ThreadedEngine"


def set_engine_type(name: str):
    if name == "LazyEngine":
        _state["sync"], _state["lazy"] = False, True
    elif name == "NaiveEngine":
        flush_all()
        _state["sync"], _state["lazy"] = True, False
    else:
        flush_all()
        _state["sync"], _state["lazy"] = False, False


def lazy_enabled() -> bool:
    """Record eager ops lazily right now?  (LazyEngine mode or inside an
    active ``bulk`` scope, and not overridden by NaiveEngine.)"""
    if getattr(_tls, "sync_depth", 0):
        return False
    _refresh()
    if _state["sync"]:
        return False
    return _state["lazy"] or getattr(_tls, "bulk_depth", 0) > 0


def step_capture_enabled() -> bool:
    """Whole-step capture switch (``MXNET_STEP_CAPTURE``, default on)."""
    return bool(getenv("MXNET_STEP_CAPTURE"))


# ---------------------------------------------------------------------------
# block attribution scope: gluon blocks tag the ops recorded inside their
# __call__ with a thread-local path ("hybridsequential0/dense3"), so the
# cost-attribution walk (mxnet_tpu.costs.attribute_segment) can fold
# per-op flop estimates up to the originating HybridBlock.  Kept to one
# list append/pop per block call and one getattr per recorded op — far
# below the record-floor microbench's resolution.
# ---------------------------------------------------------------------------
def push_block(tag):
    """Enter a block scope: ``tag`` joins the calling thread's current
    path ('parent/tag')."""
    st = getattr(_tls, "block_stack", None)
    if st is None:
        st = _tls.block_stack = []
    st.append(st[-1] + "/" + tag if st else tag)


def pop_block():
    """Leave the innermost block scope (safe no-op when empty)."""
    st = getattr(_tls, "block_stack", None)
    if st:
        st.pop()


def current_block():
    """The calling thread's current block-scope path, or None."""
    st = getattr(_tls, "block_stack", None)
    return st[-1] if st else None


class block_scope:
    """Re-enter an ABSOLUTE block path — ``autograd.backward`` uses this
    to attribute each VJP op to the block that recorded its forward
    (backward runs outside any block ``__call__``)."""

    __slots__ = ("_path",)

    def __init__(self, path):
        self._path = path

    def __enter__(self):
        st = getattr(_tls, "block_stack", None)
        if st is None:
            st = _tls.block_stack = []
        st.append(self._path)
        return self

    def __exit__(self, *exc):
        pop_block()
        return False


def capture_active() -> bool:
    """True when autograd should record onto the lazy tape instead of
    flushing: the lazy engine is recording AND whole-step capture is on.
    This is the condition under which ``autograd.record()`` entry is a
    recording *continuation* rather than a flush boundary."""
    return step_capture_enabled() and lazy_enabled()


def donation_enabled() -> bool:
    """ONE buffer-donation policy switch (``MXNET_STEP_DONATE``, default
    on) shared by the captured gluon step (``Trainer._step_captured``
    marks param/optimizer-state externals, :func:`seal` arms them) and
    ``SPMDTrainer``'s fused step (``donate_params=None`` resolves here).
    Donation aliases the dead input buffers into the updated outputs —
    the updated weights land in the old weights' memory instead of
    doubling the footprint (docs/ENGINE.md "Memory-lean fused steps")."""
    return bool(getenv("MXNET_STEP_DONATE"))


class DonatedBuffersLost(MXNetError):
    """A fused donating executable failed AFTER invalidating its donated
    inputs: the param/optimizer-state buffers are freed, so the eager
    replay (and any in-process retry) would read dead memory.  Recovery
    is restore-from-checkpoint — ``faults.ResilientStep`` turns this
    into recover-and-retry when a ``CheckpointManager`` is attached
    (docs/RESILIENCE.md)."""


class naive_engine_scope:
    """Force synchronous execution inside the scope (debugging).  Entering
    is a materialization boundary: pending lazy segments flush first."""

    def __enter__(self):
        flush_all()
        _tls.sync_depth = getattr(_tls, "sync_depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.sync_depth -= 1


class bulk:
    """Reference ``mx.engine.bulk(size)``, made functional: ops inside the
    scope are recorded into pending segments of at most ``size`` ops and
    flushed as single fused jit programs.  ``size<=0`` uses
    ``MXNET_ENGINE_BULK_SIZE``.  Exiting the scope flushes."""

    def __init__(self, size=0):
        self.size = int(size) if int(size) > 0 else \
            int(getenv("MXNET_ENGINE_BULK_SIZE"))

    def __enter__(self):
        _tls.bulk_depth = getattr(_tls, "bulk_depth", 0) + 1
        sizes = getattr(_tls, "bulk_sizes", None)
        if sizes is None:
            sizes = _tls.bulk_sizes = []
        sizes.append(self.size)
        return self

    def __exit__(self, *exc):
        _tls.bulk_depth -= 1
        _tls.bulk_sizes.pop()
        if exc and exc[0] is not None:
            # an exception is unwinding through the scope: still try to
            # materialize work recorded before it, but never let a flush
            # failure mask the in-flight exception
            try:
                flush()
            except Exception:
                pass
            return False
        flush()
        return False


def _segment_limit(seg=None):
    if seg is not None and seg.tape:
        # a segment carrying autograd tape ops is a whole-step capture: the
        # bulk-size cap would chop the step into fragments and force the
        # backward to rematerialize the forward.  The env read is cached
        # per segment — it was one getenv per recorded op on the capture
        # hot path (~100+/step)
        lim = seg._limit
        if lim is None:
            lim = seg._limit = int(getenv("MXNET_STEP_CAPTURE_MAX_OPS"))
        return lim
    sizes = getattr(_tls, "bulk_sizes", None)
    if sizes:
        return sizes[-1]
    if seg is not None:
        lim = seg._limit
        if lim is None:
            lim = seg._limit = int(getenv("MXNET_ENGINE_BULK_SIZE"))
        return lim
    return int(getenv("MXNET_ENGINE_BULK_SIZE"))


def wait_for_var(arr):
    """Reference Engine::WaitForVar (flushes ``arr`` if pending)."""
    arr.wait_to_read()


def wait_all():
    from .ndarray import waitall
    waitall()


# ---------------------------------------------------------------------------
# key construction shared by both tiers
# ---------------------------------------------------------------------------
_intern_lock = threading.Lock()
_intern_table: dict = {}
_intern_next = [0]


def _intern(key):
    """Deep structural key -> small int token.  The deep tuple hash is paid
    ONCE here; every downstream cache key built from the token (op keys,
    whole-step segment signatures — hundreds of entries per captured
    step) hashes as a flat int.  Tokens are monotonic and never reused, so
    a table wipe can only cause a cache miss, never a wrong cache hit."""
    with _intern_lock:
        tok = _intern_table.get(key)
        if tok is None:
            if len(_intern_table) >= 65536:
                _intern_table.clear()
            tok = _intern_next[0]
            _intern_next[0] = tok + 1
            _intern_table[key] = tok
        return tok


def _freeze(obj):
    """Hashable stand-in for cache keys; raises TypeError on values that
    cannot be keyed (device arrays, open handles, ...)."""
    if isinstance(obj, (str, bytes, int, float, bool, complex, type(None),
                        type(Ellipsis), type, frozenset)):
        return obj
    if isinstance(obj, slice):  # unhashable before py3.12
        return ("__slice__", obj.start, obj.stop, obj.step)
    if isinstance(obj, (tuple, list)):
        return (type(obj).__name__,) + tuple(_freeze(o) for o in obj)
    if isinstance(obj, dict):
        return ("__dict__",) + tuple(sorted(
            (k, _freeze(v)) for k, v in obj.items()))
    if callable(obj) and getattr(obj, "__closure__", None) is None:
        return obj  # module-level function: identity-stable
    if callable(obj) and getattr(obj, "__code__", None) is not None:
        # nested closure (an op helper like FullyConnected's f2 captured in
        # f3): key it the same way _fun_key keys the top-level fun — code
        # object + frozen closure + defaults.  Without this every op built
        # from layered closures is unkeyable and falls off both dispatch
        # tiers.  Self-referential closures recurse until RecursionError,
        # which the callers catch as "unkeyable".
        return ("__closure_fn__", obj.__code__,
                tuple(_freeze(c.cell_contents) for c in obj.__closure__),
                _freeze(obj.__defaults__))
    import types
    if isinstance(obj, types.ModuleType):
        # the repo-wide `import jax` *inside* op functions makes the module
        # a closure cell of every op lambda — key it by name
        return ("__module__", obj.__name__)
    import numpy as onp
    if isinstance(obj, onp.number):
        return ("__npnum__", str(obj.dtype), obj.item())
    if isinstance(obj, onp.dtype):
        return ("__npdtype__", str(obj))
    raise TypeError(f"unkeyable op argument of type {type(obj)}")


# _fun_key memo: method-local op lambdas are re-created per call but share
# one code object and capture the same kinds of values (modules, scalars,
# nested helper closures).  The deep ``_freeze`` walk measured ~70 µs per
# record on the captured-step hot path (~50 ops/step of it), so keys are
# memoized by ``(code, id(cell contents)..., id(defaults)..., kwargs ids)``
# — sound ONLY for immutable contents, because the memo returns the frozen
# VALUE key for matching identities: mutable cell contents (a list a fun
# closes over) could change value under a stable id.  ``_memo_safe``
# whitelists the immutable types; anything else takes the slow path every
# time.  Strong refs to the id'd objects ride in the memo entry so ids
# can never be recycled while the entry lives.
_fun_key_memo: dict = {}
_fun_key_memo_cap = 4096
_SAFE_CELL_TYPES = (bool, int, float, complex, str, bytes, type(None),
                    type, frozenset, type(Ellipsis))


def _memo_safe(v):
    # NOTE: nested FunctionType cells are deliberately NOT memo-safe — a
    # function object's identity is stable while its cell contents (and
    # __defaults__) can be reassigned, so an id-keyed memo could serve a
    # stale frozen value for it.  Ops built from layered closures
    # (FullyConnected's f3-over-f2) take the slow freeze path every call.
    if isinstance(v, _SAFE_CELL_TYPES):
        return True
    import types
    return isinstance(v, types.ModuleType)


def _fun_key_slow(fun, static_kwargs):
    try:
        code = getattr(fun, "__code__", None)
        if code is None:
            base = _freeze(fun)          # builtin / callable object
        else:
            closure = tuple(c.cell_contents
                            for c in (fun.__closure__ or ()))
            base = (code, _freeze(closure), _freeze(fun.__defaults__))
        return _intern((base, _freeze(static_kwargs)))
    except Exception:
        return None


def _fun_key(fun, static_kwargs):
    """Key identifying the *computation* a python callable performs, stable
    across re-creation of the callable (method-local lambdas / closures get
    a fresh function object per call but share one code object).  Returns
    None when the op cannot be keyed (unhashable closure contents)."""
    code = getattr(fun, "__code__", None)
    if code is None:
        return _fun_key_slow(fun, static_kwargs)
    cells = fun.__closure__ or ()
    defaults = fun.__defaults__ or ()
    try:
        mk = (code,
              tuple(id(c.cell_contents) for c in cells),
              tuple(id(d) for d in defaults),
              tuple(sorted((k, id(v)) for k, v in static_kwargs.items()))
              if static_kwargs else ())
        hit = _fun_key_memo.get(mk)
    except Exception:
        return _fun_key_slow(fun, static_kwargs)
    if hit is not None:
        return hit[0]
    key = _fun_key_slow(fun, static_kwargs)
    if key is not None:
        try:
            safe = all(_memo_safe(c.cell_contents) for c in cells) \
                and all(_memo_safe(d) for d in defaults) \
                and all(_memo_safe(v) for v in
                        (static_kwargs.values() if static_kwargs else ()))
        except Exception:
            safe = False
        if safe:
            # pin the id'd objects alive for the memo's lifetime
            pins = tuple(c.cell_contents for c in cells) + defaults + \
                (tuple(static_kwargs.values()) if static_kwargs else ())
            with _cache_lock:
                _lru_insert(_fun_key_memo, mk, (key, pins),
                            _fun_key_memo_cap)
    return key


def _aval_key(r):
    """Aval component of a cache key for one raw input.  Dtype objects are
    keyed directly (hashable; ``str(dtype)`` is measurably slow on the
    recording hot path), and device placement through the (cached,
    hashable) ``sharding`` object — enumerating ``r.devices()`` per record
    costs ~10us and whole-step capture keys hundreds of avals per step."""
    import jax
    if isinstance(r, (bool, int, float, complex)):
        # weak-typed scalar: value is a traced argument, only type matters
        return ("__pyscalar__", type(r).__name__)
    if isinstance(r, jax.Array):
        try:
            dev = r.sharding
            hash(dev)
        except Exception:
            dev = ()
        return (tuple(r.shape), r.dtype, bool(r.weak_type), dev)
    return (tuple(r.shape), r.dtype, False, ("host",))


_raw_types = [None]     # (bool, int, float, np scalar/array, jax.Array)
_tracer_cls = [None]    # jax Tracer class, resolved lazily


def _is_raw_supported(r):
    """Concrete, committable values only — a tracer (op called under an
    outer jit trace) must NEVER be captured into a cache key or a deferred
    segment (tracer leak).  Tracers pass ``isinstance(x, jax.Array)``
    (registered virtual subclass), so the tracer check runs second."""
    types = _raw_types[0]
    if types is None:
        import numpy as onp
        import jax
        types = _raw_types[0] = (bool, int, float, onp.number, onp.ndarray,
                                 jax.Array)
    if not isinstance(r, types):
        return False
    cls = _tracer_cls[0]
    if cls is None:
        from jax._src.core import Tracer
        cls = _tracer_cls[0] = Tracer
    return not isinstance(r, cls)


# ---------------------------------------------------------------------------
# tier 1: per-op executable cache
# ---------------------------------------------------------------------------
class _OpEntry:
    __slots__ = ("jit_fn", "compiled", "unsupported")

    def __init__(self, jit_fn):
        self.jit_fn = jit_fn
        self.compiled = {}      # aval key tuple -> AOT executable or None
        self.unsupported = False


_MISSING = object()   # sentinel: no compiled entry yet for this aval sig


def op_cache_enabled() -> bool:
    if getattr(_tls, "op_cache_off", 0):
        return False
    return bool(getenv("MXNET_OP_CACHE"))


class op_cache_scope:
    """Disable (or re-enable) the per-op executable cache in a scope —
    benchmarking aid (``opperf.py --mode eager`` measures the un-jitted
    baseline through this)."""

    def __init__(self, enabled=True):
        self._on = bool(enabled)

    def __enter__(self):
        if not self._on:
            _tls.op_cache_off = getattr(_tls, "op_cache_off", 0) + 1
        return self

    def __exit__(self, *exc):
        if not self._on:
            _tls.op_cache_off -= 1


def _lru_insert(cache, key, value, cap):
    if len(cache) >= cap:
        # drop ~25% oldest-inserted entries (dicts preserve insert order);
        # full LRU bookkeeping on the hot path is not worth its cost
        for k in list(cache)[:max(1, cap // 4)]:
            del cache[k]
    cache[key] = value


def _persist_min_s():
    return float(getenv("MXNET_OP_CACHE_PERSIST_MIN_MS")) / 1e3


# tier names whose ProgramCache entries should carry their own ``kind``
# (everything else is a tier-1 per-op program) — the keyspace table in
# docs/COMPILE.md "The compile pipeline"
_PERSIST_KINDS = {"lazy_segment", "step_segment", "trainer_update",
                  "trainer_sparse_update", "trainer_dense_subset_update"}


def _persist_kind(label):
    return label if label in _PERSIST_KINDS else "op"


def _invalidate_artifact(pc_key):
    """Set aside the persisted ProgramCache blob behind ``pc_key`` (an
    executable observed corrupt at run time); best-effort, None is a no-op."""
    if pc_key is None:
        return
    try:
        from . import compile as _compile
        pc = _compile.default_program_cache()
        if pc is not None:
            pc.invalidate(pc_key)
    except Exception:
        pass


def _aot_compile(jit_fn, raws, label):
    """Lower + compile through the ProgramCache when the compile is worth
    persisting; returns ``(executable_or_None, pc_key_or_None)`` — None
    meaning: call jit_fn.  The key lets a caller that later discovers the
    warm-loaded executable is corrupt (output-arity mismatch) invalidate
    the persisted artifact instead of re-loading it forever."""
    import time
    from . import compile as _compile
    pc = _compile.default_program_cache()
    if pc is None:
        return None, None
    lowered = jit_fn.lower(*raws)
    try:
        key = _compile.fingerprint_lowered(lowered)
    except Exception:
        return None, None
    exe = _compile.load_executable(pc, key, lowered)
    if exe is not None:
        _stats["op_cache_persist_hits"] += 1
        # warm=True: a deserialized executable's memory_analysis has
        # no alias table — the ledger flags it so a donating
        # program's peak is not misread (docs/OBSERVABILITY.md); the
        # cost ledger flags its analysis the same way
        _memory.record_program(exe, key=key, label=label or "",
                               kind=_persist_kind(label), warm=True)
        _costs.record_program(exe, key=key, label=label or "",
                              kind=_persist_kind(label), warm=True)
        return exe, key
    t0 = time.perf_counter()
    with _telemetry.phase("compile", label=label or ""):
        compiled = lowered.compile()
    # per-program memory ledger: argument/output/temp/peak bytes from
    # XLA's buffer assignment, keyed by the ProgramCache key so flush
    # spans and crash reports can name the peak-owning program; the cost
    # ledger captures flops/bytes-accessed under the same key
    _memory.record_program(compiled, key=key, label=label or "",
                           kind=_persist_kind(label))
    _costs.record_program(compiled, key=key, label=label or "",
                          kind=_persist_kind(label))
    if time.perf_counter() - t0 < _persist_min_s():
        # cheap compile: recompiling beats a disk round-trip; jax's own
        # persistent cache (when enabled) still covers it
        return compiled, key
    _pc_store(pc, key, compiled, label)
    return compiled, key


def _pc_warm_load(jit_fn, raws):
    """ProgramCache lookup for one op signature.  Returns
    ``(exe_or_None, lowered_or_None, key, pc)`` — the lowered artifact and
    key are handed back so a slow compile can be persisted without
    re-lowering."""
    from . import compile as _compile
    pc = _compile.default_program_cache()
    if pc is None:
        return None, None, None, None
    lowered = jit_fn.lower(*raws)
    try:
        key = _compile.fingerprint_lowered(lowered)
    except Exception:
        return None, None, None, None
    exe = _compile.load_executable(pc, key, lowered)
    if exe is not None:
        _stats["op_cache_persist_hits"] += 1
        _memory.record_program(exe, key=key, kind="op", warm=True)
        _costs.record_program(exe, key=key, kind="op", warm=True)
    return exe, lowered, key, pc


def _pc_store(pc, key, compiled, label):
    """Serialize an already-compiled executable into the ProgramCache —
    callers must hand over the compiled artifact (never re-compile just to
    persist; for the slow programs worth persisting that doubles the
    dominant cost)."""
    from . import compile as _compile
    _compile.store_executable(
        pc, key, compiled,
        meta={"label": label or "", "kind": _persist_kind(label)})


_vjp_jit_cache: dict = {}
_vjp_jit_cache_cap = 1024


def vjp_jit_fn(fun, static_kwargs, diff_pos, n_args):
    """Stable jitted core for the eager autograd path: ``g(diff_args,
    other_args) == fun(*merged, **static_kwargs)``, cached by ``(fun key,
    diff positions, arity)`` exactly like the per-op executable cache.

    Running ``jax.vjp`` over this jitted core instead of a fresh closure
    keeps the op body ONE compiled unit in both the eager tape and the
    whole-step capture — so FMA/contraction rounding inside multi-
    primitive ops (BatchNorm moments, GELU) is identical across the two
    paths, which is what makes eager-vs-captured training bit-identical.
    Returns ``(jitted, other_pos)`` or ``(None, None)`` for unkeyable or
    previously jit-hostile funs (callers then use the legacy un-jitted
    closure)."""
    key = _fun_key(fun, static_kwargs)
    if key is None:
        return None, None
    ck = (key, diff_pos, n_args)
    with _cache_lock:
        entry = _vjp_jit_cache.get(ck)
    if entry is not None:
        return entry if entry[0] is not None else (None, None)
    import jax
    dset = set(diff_pos)
    other_pos = tuple(i for i in range(n_args) if i not in dset)

    def g(diff_args, other_args):
        full = [None] * n_args
        for p, v in zip(diff_pos, diff_args):
            full[p] = v
        for p, v in zip(other_pos, other_args):
            full[p] = v
        return fun(*full, **static_kwargs)

    entry = (jax.jit(g), other_pos)
    with _cache_lock:
        _lru_insert(_vjp_jit_cache, ck, entry, _vjp_jit_cache_cap)
    return entry


def vjp_jit_blacklist(fun, static_kwargs, diff_pos, n_args):
    """Mark one vjp core jit-hostile (tracing failed but the un-jitted
    closure succeeded): later calls skip straight to the legacy path."""
    key = _fun_key(fun, static_kwargs)
    if key is None:
        return
    with _cache_lock:
        _lru_insert(_vjp_jit_cache, (key, diff_pos, n_args), (None, None),
                    _vjp_jit_cache_cap)


def cached_call(fun, raws, static_kwargs, op_name=""):
    """Execute ``fun(*raws, **static_kwargs)`` through the per-op executable
    cache.  Returns ``(ok, result)``: ``ok=False`` means the op is not
    cacheable (unkeyable closure, jit-hostile fun, non-array arg) and the
    caller must run it directly.

    Steady state runs through the ``jax.jit`` wrapper (its C++ dispatch
    fast path beats an AOT ``Compiled.__call__``); the ProgramCache is
    consulted once per new aval signature to warm-load slow compiles from
    disk, and compiles slower than ``MXNET_OP_CACHE_PERSIST_MIN_MS`` are
    serialized back into it for the next process."""
    import time
    key = _fun_key(fun, static_kwargs)
    if key is None or not all(_is_raw_supported(r) for r in raws):
        _stats["op_cache_fallbacks"] += 1
        return False, None
    with _cache_lock:
        entry = _op_cache.get(key)
    if entry is not None and entry.unsupported:
        _stats["op_cache_fallbacks"] += 1
        return False, None
    if entry is None:
        import jax
        import functools
        jit_fn = jax.jit(functools.partial(fun, **static_kwargs)) \
            if static_kwargs else jax.jit(fun)
        with _cache_lock:
            entry = _op_cache.get(key)
            if entry is None:
                entry = _OpEntry(jit_fn)
                _lru_insert(_op_cache, key, entry, _op_cache_cap)
    avk = tuple(_aval_key(r) for r in raws)
    exe = entry.compiled.get(avk, _MISSING)
    try:
        if exe is not _MISSING:
            _stats["op_cache_hits"] += 1
            return True, (exe(*raws) if exe is not None
                          else entry.jit_fn(*raws))
        _stats["op_cache_misses"] += 1
        try:
            exe, lowered, pkey, pc = _pc_warm_load(entry.jit_fn, raws)
        except Exception:
            exe, lowered, pkey, pc = None, None, None, None
        if exe is not None:
            # disk-warm executable: skips XLA entirely.  Its call path is
            # python-level — acceptable exactly for the slow-to-compile
            # (i.e. heavy) programs that get persisted.
            entry.compiled[avk] = exe
            return True, exe(*raws)
        t0 = time.perf_counter()
        out = entry.jit_fn(*raws)           # one trace+compile for everyone
        if pc is not None and \
                time.perf_counter() - t0 > _persist_min_s():
            # worth persisting: produce a serializable artifact.  This IS
            # a second compile, but only for the rare slow ops — and only
            # in the first process ever to see the signature (later ones
            # warm-load above).  The artifact also serves this process's
            # remaining calls, so the work is not thrown away.
            compiled = lowered.compile()
            _memory.record_program(compiled, key=pkey, label=op_name,
                                   kind="op")
            _costs.record_program(compiled, key=pkey, label=op_name,
                                  kind="op")
            _pc_store(pc, pkey, compiled, op_name)
            entry.compiled[avk] = compiled
            return True, out
        entry.compiled[avk] = None          # steady state: jit fast path
        return True, out
    except Exception:
        # Either a jit-hostile fun (value-dependent control flow, host
        # callbacks, data-dependent shapes) or a genuinely-invalid call.
        # Disambiguate by running un-jitted: a genuine user error raises
        # here too (identical to eager semantics, no blacklist); success
        # means only *tracing* fails — blacklist the key for the process.
        _stats["op_cache_fallbacks"] += 1
        out = fun(*raws, **static_kwargs)
        entry.unsupported = True
        return True, out


# ---------------------------------------------------------------------------
# tier 2: lazy segments
# ---------------------------------------------------------------------------
def _aval_nbytes(aval):
    """Byte size of a ShapeDtypeStruct — the engine builds every slot
    aval itself, so the general ``memory._nbytes_of`` getattr/tracer
    dance (measured ~6 µs; this runs per recorded slot) reduces to one
    itemsize read and a shape walk."""
    try:
        n = aval.dtype.itemsize
        for d in aval.shape:
            n *= d
        return n
    except Exception:           # noqa: BLE001 — odd aval: general path
        return _memory._nbytes_of(aval) or 0


class _PendingOp:
    __slots__ = ("fun", "kwargs", "wiring", "out_slots", "n_outs",
                 "tuple_out", "name", "key", "fkey", "block")

    def __init__(self, fun, kwargs, wiring, out_slots, tuple_out, name, key,
                 fkey=None, block=None):
        self.fun = fun
        self.kwargs = kwargs
        self.wiring = wiring          # [('p', slot) | ('x', ext_index)]
        self.out_slots = out_slots
        self.tuple_out = tuple_out
        self.name = name
        self.key = key                # (_fun_key, wiring tags, ext avals)
        self.fkey = fkey              # pre-intern fun key: the cost
                                      # estimator's dedup handle (vjp ops
                                      # carry ("__vjp__", fwd_fkey, ...))
        self.block = block            # recording-time block-scope path


class _Segment:
    """One recorded chain of deferred ops (thread-confined recording;
    flushing is safe from any thread)."""

    def __init__(self):
        self.ops: list[_PendingOp] = []
        self.externals: list = []     # concrete raws / python scalars
        self.ext_memo: dict = {}      # id(jax.Array raw) -> external index
                                      # (immutable buffers dedup; a buffer
                                      # used by N ops enters the program
                                      # ONCE — required for donation, and
                                      # fewer program parameters besides)
        self.donate_ext: set = set()  # donation-candidate external indices
        self.donate_armed = False     # seal() arms candidates (policy:
                                      # only COMPLETE sealed steps donate)
        self.slots: list = []         # per-slot aval (ShapeDtypeStruct)
        self.arrays: list = []        # per-slot weakref -> NDArray
        self.done = False
        self.tape = False             # carries autograd/whole-step ops
        self._limit = None            # cached op cap (env read once)
        self.pending_nbytes = 0       # census deferred-slot accounting
        self.pending_nslots = 0
        self._discounted: set = set()
        self.lock = threading.RLock()

    def __del__(self):
        # a segment abandoned without ever flushing (all placeholders
        # died) must release its deferred-bytes accounting
        if not self.done:
            try:
                self._release_pending_acct()
            except Exception:   # noqa: BLE001 — interpreter shutdown
                pass

    def _release_pending_acct(self):
        nb, ns = self.pending_nbytes, self.pending_nslots
        if nb or ns:
            self.pending_nbytes = 0
            self.pending_nslots = 0
            with _pending_acct_lock:
                _pending_bytes[0] -= nb
                _pending_slots[0] -= ns

    def discount_slot(self, slot):
        """Census: this slot's output will land in an ALREADY-REGISTERED
        array — a parameter/gradient re-adopted via ``adopt_pending``, or
        a pending NDArray the trainer tagged (optimizer state) — so its
        bytes are counted under that array's origin; remove them from
        the deferred accounting or the census double-counts the whole
        param+grad+state footprint while a capture segment is open.
        Idempotent per slot; clamped so a census toggle mid-segment can
        only under-count, never drift negative."""
        with self.lock:
            if self.done or slot in self._discounted \
                    or self.pending_nslots <= 0:
                return
            self._discounted.add(slot)
            nb = min(_memory._nbytes_of(self.slots[slot]) or 0,
                     self.pending_nbytes)
            self.pending_nbytes -= nb
            self.pending_nslots -= 1
            with _pending_acct_lock:
                _pending_bytes[0] -= nb
                _pending_slots[0] -= 1

    # -- recording ---------------------------------------------------------
    def add_external(self, raw):
        self.externals.append(raw)
        return len(self.externals) - 1

    def new_slot(self, aval, nd):
        self.slots.append(aval)
        self.arrays.append(weakref.ref(nd))
        if _memory._census_active:
            nb = _aval_nbytes(aval)
            self.pending_nbytes += nb
            self.pending_nslots += 1
            with _pending_acct_lock:
                _pending_bytes[0] += nb
                _pending_slots[0] += 1
        return len(self.slots) - 1

    # -- flush -------------------------------------------------------------
    def flush(self):
        with self.lock:
            if self.done:
                return
            self.done = True
            self._release_pending_acct()
            if getattr(_tls, "segment", None) is self:
                _tls.segment = None
            if not self.ops:
                return
            self._execute()

    def _donation(self):
        """The armed donation argnums for this flush: external indices the
        recorder marked dead-after-flush (the trainer's param/optimizer-
        state buffers), active only once :func:`seal` armed them — a
        segment flushed mid-step (cross-thread flush_all, value read
        before the update recorded) executes WITHOUT donation, so buffers
        still reachable through live NDArrays are never invalidated."""
        if self.donate_armed and self.donate_ext:
            return tuple(sorted(self.donate_ext))
        return ()

    def _donated_dead(self, donate):
        """Did a failed executable call already consume (delete) donated
        input buffers?  If so the eager replay would read freed memory."""
        for i in donate:
            r = self.externals[i]
            try:
                if r.is_deleted():
                    return True
            except Exception:   # noqa: BLE001 — non-probeable: assume live
                continue
        return False

    @staticmethod
    def _compiled_arity(fn):
        """Output arity of an AOT/warm-loaded ``Compiled`` (None when not
        introspectable — e.g. the plain jit wrapper)."""
        tree = getattr(fn, "out_tree", None)
        try:
            return tree.num_leaves if tree is not None else None
        except Exception:       # noqa: BLE001
            return None

    def _execute(self):
        import time
        from . import profiler as _profiler
        t0 = time.perf_counter_ns() // 1000
        live = [r() for r in self.arrays]
        donate = self._donation()
        # external avals are embedded in each op's key (every external is
        # referenced by exactly the op(s) that added it), so op keys plus
        # the output-liveness mask — and the donation set, which changes
        # the compiled program's aliasing — fully determine the program
        sig = (tuple(op.key for op in self.ops),
               tuple(a is not None for a in live), donate)
        with _cache_lock:
            fn = _segment_cache.get(sig)
        hit = fn is not None
        if fn is None:
            _stats["lazy_segment_cache_misses"] += 1
            fn = self._compile(sig, live, donate)
        else:
            _stats["lazy_segment_cache_hits"] += 1
        live_slots = [i for i, a in enumerate(live) if a is not None]
        exe_arity = self._compiled_arity(fn)
        if exe_arity is not None and exe_arity != len(live_slots):
            # stale/corrupt warm-loaded executable caught BEFORE running:
            # essential for donating segments — a donating call consumes
            # its inputs even when the outputs are garbage, which would
            # make the eager-replay recovery below impossible.  Drop the
            # cached entry, set the persisted blob aside, compile fresh.
            import warnings
            with _cache_lock:
                _segment_cache.pop(sig, None)
                pc_key = _segment_pc_keys.pop(sig, None)
            _invalidate_artifact(pc_key)
            warnings.warn(
                f"warm-loaded fused segment declares {exe_arity} outputs "
                f"for {len(live_slots)} live slots — invalidated the "
                "persisted artifact and recompiled")
            fn = self._compile(sig, live, donate)
        outs = None
        try:
            # fault point: an injected flush failure exercises the
            # eager-replay recovery below (docs/RESILIENCE.md)
            from . import faults as _faults
            _faults.point("engine.flush")
        except Exception:
            with _cache_lock:
                _segment_cache.pop(sig, None)
            # diagnose with an eager replay that names the failing op
            self._replay_eager()
        else:
            try:
                outs = fn(*self.externals)
            except Exception as e:
                # the executable failed: drop it and replay eagerly.  A
                # replay that ALSO fails names the genuinely-failing op
                # and propagates (the persisted artifact is not the
                # problem).  A replay that succeeds proves the recorded
                # program is fine and the EXECUTABLE is bad — poison its
                # persisted ProgramCache artifact too, else every later
                # flush (and every new process) warm-loads it, fails, and
                # silently loses fusion for good; a transiently-failed
                # fresh compile only costs one re-persist.
                with _cache_lock:
                    _segment_cache.pop(sig, None)
                    pc_key = _segment_pc_keys.pop(sig, None)
                if donate and self._donated_dead(donate):
                    # the failed call already consumed the donated
                    # param/state buffers: no in-process replay can
                    # re-materialize them — surface the typed error
                    # ResilientStep turns into restore-from-checkpoint
                    # recovery (docs/RESILIENCE.md)
                    # donation-recovery: tests/test_donation.py::test_donated_failure_recovers_from_checkpoint
                    _invalidate_artifact(pc_key)
                    raise DonatedBuffersLost(
                        "fused step executable failed after donating its "
                        "param/optimizer-state buffers; in-process replay "
                        "is impossible — restore from the latest "
                        f"checkpoint (cause: {e})") from e
                self._replay_eager()
                _invalidate_artifact(pc_key)
                outs = None
        if outs is not None and len(outs) != len(live_slots):
            # executable/signature mismatch (a stale or corrupt warm-loaded
            # artifact): NEVER zip-truncate the writeback — wrong buffers
            # would land in wrong arrays silently.  Drop the in-memory
            # entry AND the persisted ProgramCache blob, same rationale as
            # the execution-failure path above.
            import warnings
            with _cache_lock:
                _segment_cache.pop(sig, None)
                pc_key = _segment_pc_keys.pop(sig, None)
            if donate and self._donated_dead(donate):
                _invalidate_artifact(pc_key)
                raise DonatedBuffersLost(
                    f"fused segment returned {len(outs)} outputs for "
                    f"{len(live_slots)} live slots after donating its "
                    "input buffers; replay is impossible — restore from "
                    "the latest checkpoint")
            self._replay_eager()
            _invalidate_artifact(pc_key)
            n_outs = len(outs)
            outs = None
            # warn LAST: under -W error the raise must not skip the replay
            # above, or the pending arrays would never materialize
            warnings.warn(
                f"fused segment returned {n_outs} outputs for "
                f"{len(live_slots)} live slots — dropped the cached "
                "executable (and its persisted artifact) and replayed "
                "eagerly")
        if outs is not None:
            for i, o in zip(live_slots, outs):
                nd = live[i]
                p = nd._pending
                if p is None or p[0] is not self or p[1] != i:
                    # this slot's binding is stale: the array was detached
                    # after recording (zero_grad on a pending grad,
                    # backward's overwrite detach) and may since have been
                    # re-adopted into a LATER slot of this same segment
                    # (capture continuation across iterations) — that slot
                    # owns the writeback now; never clobber the newer value
                    continue
                nd._data = o
                nd._pending = None
                nd._pending_aval = None
                if _memory._census_active:
                    # census: "pending" placeholders became activations;
                    # adopt_pending'd params/grads keep their tag
                    _memory.materialized(nd)
        _stats["lazy_flushes"] += 1
        _stats["lazy_ops_recorded"] += len(self.ops)
        if self.tape:
            _stats["step_flushes"] += 1
        if donate and outs is not None:
            _stats["donated_flushes"] += 1
        if _telemetry.enabled() or _profiler.is_running():
            t1 = time.perf_counter_ns() // 1000
            if _profiler.is_running():
                _profiler.record_engine_flush(len(self.ops), hit, t0,
                                              t1 - t0, tape=self.tape)
            # the span names the ProgramCache key the flush ran (None for
            # un-persisted segments): the program-fingerprint correlation
            # that lets trace_report tie a step_flush back to its on-disk
            # executable (docs/OBSERVABILITY.md)
            with _cache_lock:
                pc_key = _segment_pc_keys.get(sig)
            # outs is None exactly when the fused executable never ran or
            # failed and the segment was replayed op-by-op: the span must
            # say fusion was lost (the dur covers the replay), or an
            # operator reading the trace sees a healthy "cache hit" on a
            # step that actually fell back
            extra = {}
            mem_bytes = _memory.ledger_peak(pc_key)
            if mem_bytes:
                # the bytes column next to the milliseconds: the ledger's
                # peak (argument+output+temp) for the program this flush
                # ran (docs/OBSERVABILITY.md memory section)
                extra["bytes"] = mem_bytes
            if outs is not None:
                # the flops/mfu columns next to the bytes: the cost
                # ledger's figure for this program over this flush's wall
                # (skipped on fallback — an eager replay did not run the
                # compiled program the ledger describes).  A cache-MISS
                # flush paid the XLA compile inside this same window, so
                # only flops ride the span there — dividing by
                # compile+execute wall would record garbage-low MFU for
                # every freshly compiled program
                if hit:
                    extra.update(_costs.execution_attrs(pc_key, t1 - t0))
                else:
                    fresh_flops = _costs.ledger_flops(pc_key)
                    if fresh_flops:
                        extra["flops"] = int(fresh_flops)
            if donate:
                extra["donated"] = len(donate)
            _telemetry.add_span("step_flush" if self.tape else "lazy_flush",
                                t0, t1 - t0, ops=len(self.ops),
                                cache_hit=hit, program=pc_key,
                                fallback=outs is None, **extra)
        self.ops = []
        self.externals = []
        self.ext_memo = {}

    def _compile(self, sig, live, donate=()):
        import jax
        ops = list(self.ops)
        n_slots = len(self.slots)
        # liveness must come from the SAME strong-ref snapshot the caller
        # keyed the signature with — re-reading the weakrefs here could
        # disagree after a GC and mis-wire the writeback
        live_slots = [i for i, a in enumerate(live) if a is not None]

        def run(*ext):
            vals = [None] * n_slots
            for op in ops:
                args = [vals[i] if tag == "p" else ext[i]
                        for tag, i in op.wiring]
                out = op.fun(*args, **op.kwargs)
                outs = out if op.tuple_out else (out,)
                for s, o in zip(op.out_slots, outs):
                    vals[s] = o
            return tuple(vals[i] for i in live_slots)

        # donated externals alias into the program's outputs: the updated
        # params/states land in the old buffers' memory (XLA input-output
        # aliasing), halving the weight+state footprint of a captured
        # step.  Externals are identity-deduplicated at record time, so a
        # donated buffer enters the program exactly once — the XLA
        # buffer-assignment precondition.
        # donation-recovery: tests/test_donation.py::test_donated_failure_recovers_from_checkpoint
        fn = jax.jit(run, donate_argnums=donate) if donate else jax.jit(run)
        # route through the ProgramCache for cross-process reuse of hot
        # segment shapes (same persistence-threshold policy as tier 1)
        exe, pc_key = None, None
        try:
            exe, pc_key = _aot_compile(fn, self.externals,
                                       "step_segment" if self.tape
                                       else "lazy_segment")
        except Exception:
            exe, pc_key = None, None
        fn = exe if exe is not None else fn
        with _cache_lock:
            _lru_insert(_segment_cache, sig, fn, _segment_cache_cap)
            if pc_key is not None:
                _lru_insert(_segment_pc_keys, sig, pc_key,
                            _segment_cache_cap)
        # block-level cost attribution — COMPILE time only (a cache-hit
        # flush never reaches here), estimation failures never fail the
        # flush.  Each op hands over its fun, input avals (slot avals /
        # external shapes, scalars verbatim) and the recording-time block
        # path; costs folds per-equation flop estimates up to blocks
        # (docs/OBSERVABILITY.md "Compute-cost observability")
        try:
            if _costs.attribution_enabled():
                import jax as _jax
                # a slot is USED when some op consumes it or its array is
                # a live program output — dead branches (e.g. the first
                # layer's input-gradient, which feeds nothing) are DCE'd
                # by the estimator exactly as XLA drops them
                consumed = {i for op in ops
                            for tag, i in op.wiring if tag == "p"}
                descs = []
                for op in ops:
                    avals = []
                    for tag, i in op.wiring:
                        if tag == "p":
                            avals.append(self.slots[i])
                        else:
                            r = self.externals[i]
                            if hasattr(r, "shape"):
                                avals.append(_jax.ShapeDtypeStruct(
                                    tuple(r.shape), r.dtype))
                            else:
                                avals.append(r)
                    used = tuple(s in consumed or live[s] is not None
                                 for s in op.out_slots)
                    descs.append((op.name, op.block, op.fun, op.kwargs,
                                  avals, op.fkey, used))
                _costs.attribute_segment(
                    descs, key=pc_key,
                    kind="step_segment" if self.tape else "lazy_segment",
                    total_flops=_costs.ledger_flops(pc_key))
        except Exception:       # noqa: BLE001 — attribution is best-effort
            pass
        return fn

    def _replay_eager(self):
        """Run the recorded ops one at a time, un-jitted, so the exception
        surfaces attributed to the op that raised it."""
        from .base import MXNetError
        _stats["lazy_eager_replays"] += 1
        vals = [None] * len(self.slots)
        for op in self.ops:
            args = [vals[i] if tag == "p" else self.externals[i]
                    for tag, i in op.wiring]
            try:
                out = op.fun(*args, **op.kwargs)
            except Exception as e:
                raise MXNetError(
                    f"deferred op {op.name!r} failed during lazy flush: "
                    f"{e}") from e
            outs = out if op.tuple_out else (out,)
            for s, o in zip(op.out_slots, outs):
                vals[s] = o
        for i, (r, v) in enumerate(zip(self.arrays, vals)):
            nd = r()
            if nd is None or v is None:
                continue
            p = nd._pending
            if p is None or p[0] is not self or p[1] != i:
                continue   # detached, or re-adopted into a later slot of
                           # this segment which owns the writeback instead
            nd._data = v
            nd._pending = None
            nd._pending_aval = None
            if _memory._census_active:
                _memory.materialized(nd)


def _current_segment(create=True):
    seg = getattr(_tls, "segment", None)
    if (seg is None or seg.done) and create:
        seg = _tls.segment = _Segment()
        with _segments_lock:
            _live_segments.add(seg)
    return seg


def record_lazy(fun, args, op_name, static_kwargs, key_override=None,
                tape=False, donate=()):
    """Try to defer one op into the current lazy segment.  Returns the
    placeholder output(s), or ``NotImplemented`` when the op cannot be
    deferred (unkeyable fun, non-array arg, eval_shape-hostile fun) — the
    caller then executes it eagerly.

    ``key_override``: hashable stand-in for ``_fun_key(fun, kwargs)`` when
    the callable itself is not stably keyable (the autograd VJP closures
    and the trainer's fused-update closure are rebuilt per call but denote
    the same computation).  ``tape=True`` marks the segment as a
    whole-step capture: it is exempt from the bulk-size cap and its
    flushes count as ``step_flushes``.  ``donate``: positions of args
    whose device buffers the CALLER declares dead after this segment
    flushes (the trainer's param/optimizer-state inputs) — candidates
    only; :func:`seal` arms them, and :func:`donation_enabled` gates the
    whole policy."""
    from .ndarray.ndarray import NDArray

    fkey = key_override if key_override is not None \
        else _fun_key(fun, static_kwargs)
    if fkey is None:
        return NotImplemented

    # Phase 1 (no lock held): materialize inputs pending on OTHER segments.
    # Doing this before taking our segment's lock avoids lock-order cycles
    # between two threads whose segments reference each other's outputs.
    my_seg = getattr(_tls, "segment", None)
    for a in args:
        if isinstance(a, NDArray) and a._data is None and \
                (a._pending is None or a._pending[0] is not my_seg):
            flush_array(a)

    # Phase 2: record under the segment lock — a concurrent flush_all()
    # (record() entry or waitall on another thread) must never execute a
    # segment while an op is being appended to it, or the op is lost and
    # its placeholders orphan.
    while True:
        seg = _current_segment()
        with seg.lock:
            if seg.done:
                continue     # raced with a cross-thread flush: fresh one
            # donation-recovery: tests/test_donation.py::test_donated_failure_recovers_from_checkpoint
            res = _record_into(seg, fun, fkey, args, op_name, static_kwargs,
                               tape=tape, donate=donate)
        return res


def _record_into(seg, fun, fkey, args, op_name, static_kwargs, tape=False,
                 donate=()):
    """Append one op to ``seg`` (caller holds ``seg.lock``)."""
    import jax
    from .ndarray.ndarray import NDArray

    ext_start = len(seg.externals)   # rollback point on bail-out
    wiring = []
    spec = []                        # abstract/concrete values for eval_shape
    memo = seg.ext_memo              # immutable-buffer identity dedup
    memo_added = None
    donate_added = None
    donate = frozenset(donate) if donate else None

    def bail():
        del seg.externals[ext_start:]
        if memo_added:
            for k in memo_added:
                memo.pop(k, None)
        if donate_added:
            seg.donate_ext.difference_update(donate_added)
        return NotImplemented

    def add_ext(r, pos):
        """External wiring for one raw.  jax.Arrays (immutable) dedup by
        buffer identity so a buffer used by N ops enters the compiled
        program once — the precondition for donating it (a buffer passed
        at two program parameters with one donated is an XLA aliasing
        hazard); python scalars and (mutable) numpy arrays append as
        before.  ``_raw_types`` is always resolved here: every array arg
        passed ``_is_raw_supported`` first."""
        nonlocal memo_added, donate_added
        types = _raw_types[0]
        if types is not None and isinstance(r, types[5]):
            oid = id(r)
            idx = memo.get(oid)
            if idx is None:
                idx = seg.add_external(r)
                memo[oid] = idx
                if memo_added is None:
                    memo_added = [oid]
                else:
                    memo_added.append(oid)
            if donate is not None and pos in donate:
                seg.donate_ext.add(idx)
                if donate_added is None:
                    donate_added = {idx}
                else:
                    donate_added.add(idx)
        else:
            idx = seg.add_external(r)
        return idx

    for pos, a in enumerate(args):
        if isinstance(a, NDArray):
            if a._data is None:
                owner = a._pending[0] if a._pending is not None else None
                if owner is seg:
                    wiring.append(("p", a._pending[1]))
                    spec.append(a._pending_aval)
                    continue
                # pending on a segment that was flushed out from under us
                # between phase 1 and taking our lock: materialize it
                flush_array(a)
            r = a._data
            if not _is_raw_supported(r):
                return bail()
            wiring.append(("x", add_ext(r, pos)))
            spec.append(r)
        elif isinstance(a, (bool, int, float)):
            wiring.append(("x", seg.add_external(a)))
            spec.append(a)
        elif _is_raw_supported(a):
            # raw device/host array passed positionally (PRNG keys on the
            # dropout path, CachedOp rng args): a committed concrete value
            # is a legitimate external
            wiring.append(("x", add_ext(a, pos)))
            spec.append(a)
        else:
            return bail()

    # shape inference is pure in (fun, input avals): cache it, because a
    # per-record eval_shape (a full abstract trace) would cost about as
    # much host time as the un-jitted dispatch being amortized away
    shape_key = (fkey, tuple([_aval_key(s) for s in spec]))
    with _cache_lock:
        cached_avals = _shape_cache.get(shape_key, _MISSING)
    if cached_avals is _MISSING:
        try:
            avals = jax.eval_shape(lambda *xs: fun(*xs, **static_kwargs),
                                   *spec)
        except Exception:
            # a genuinely-invalid op raises the same error eagerly (with
            # the caller's traceback); an eval_shape-hostile-but-eager-
            # valid fun must keep working — either way: run it eagerly
            avals = None
        if avals is not None:
            tuple_out = isinstance(avals, (tuple, list))
            flat = list(avals) if tuple_out else [avals]
            if all(hasattr(av, "shape") for av in flat):
                cached_avals = (tuple_out, tuple(
                    jax.ShapeDtypeStruct(tuple(av.shape), av.dtype)
                    for av in flat))
            else:
                cached_avals = None
        else:
            cached_avals = None     # negative-cache: bail fast next time
        with _cache_lock:
            _lru_insert(_shape_cache, shape_key, cached_avals,
                        _shape_cache_cap)
    if cached_avals is None:
        return bail()
    tuple_out, out_avals = cached_avals

    outs, out_slots = [], []
    for aval in out_avals:
        nd = NDArray._new_pending(aval)
        slot = seg.new_slot(aval, nd)
        nd._pending = (seg, slot)
        out_slots.append(slot)
        outs.append(nd)

    # external avals are already in shape_key (same arg order as wiring);
    # interned so the per-flush segment signature hashes as flat ints.
    # External entries carry their INDEX too: identity dedup makes the
    # external layout depend on which args share a buffer (x+x is one
    # external, x+y two), so two structurally-equal op sequences with
    # different sharing must key to different fused programs
    arg_keys = shape_key[1]
    opkey = _intern((fkey, tuple([(t, i) if t == "p"
                                  else (t, i, arg_keys[j])
                                  for j, (t, i) in enumerate(wiring)])))
    seg.ops.append(_PendingOp(fun, static_kwargs, wiring, out_slots,
                              tuple_out, op_name, opkey, fkey=fkey,
                              block=current_block()))
    if tape and not seg.tape:
        seg.tape = True
        seg._limit = None        # re-resolve the cap for a tape segment
    if tape:
        _stats["tape_ops_recorded"] += 1
    if len(seg.ops) >= _segment_limit(seg):
        seg.flush()
    return tuple(outs) if tuple_out else outs[0]


# ---------------------------------------------------------------------------
# flush API — the ONLY sanctioned way to materialize pending arrays
# ---------------------------------------------------------------------------
def flush():
    """Flush this thread's current pending segment plus any segments this
    thread sealed (``seal``) and has not yet materialized."""
    seg = getattr(_tls, "segment", None)
    if seg is not None and not seg.done:
        seg.flush()
    for s in getattr(_tls, "sealed", ()) or ():
        if not s.done:
            s.flush()
    _tls.sealed = []


def seal():
    """Detach this thread's current segment WITHOUT executing it: new ops
    start a fresh segment while the sealed one stays pending until a
    materialization boundary (``flush_array`` on one of its outputs,
    ``flush``/``flush_all``/``waitall``).

    This is how ``gluon.Trainer.step`` ends a whole-step capture: the
    forward/backward/update segment is complete, and the *next* step's
    first op (or the loss read, whichever comes first) triggers the
    compile-and-run — so step N's device work overlaps step N+1's python
    dispatch.  Returns the sealed segment (or None)."""
    seg = getattr(_tls, "segment", None)
    if seg is None or seg.done:
        return None
    _tls.segment = None
    if seg.donate_ext and donation_enabled():
        # the step is COMPLETE: every donation-candidate external (the
        # trainer's param/optimizer-state buffers, rebound to pending
        # outputs via adopt_pending) is now unreachable except through
        # this segment — arm the donation.  A segment flushed before
        # seal (mid-step value read, cross-thread flush_all) keeps its
        # candidates un-armed and executes without donating.
        seg.donate_armed = True
    sealed = [s for s in (getattr(_tls, "sealed", None) or [])
              if not s.done]
    sealed.append(seg)
    _tls.sealed = sealed
    return seg


def adopt_pending(dst, src):
    """Rebind the deferred output ``src`` (a placeholder NDArray freshly
    returned by ``record_lazy``) onto the caller-owned NDArray ``dst``, so
    the segment's flush writes the result into ``dst``'s buffer and the
    object identity users hold (``Parameter._nd``, an attached ``.grad``)
    survives a captured update.  Safe against the segment flushing
    concurrently: in that case ``src`` already materialized and its buffer
    is copied over.  Returns ``dst``."""
    if dst is src:
        return dst
    if dst._pending is not None:
        if dst._pending[0].done and dst._data is None:
            # binding to a DEAD segment that never materialized this slot
            # (a donated flush failed and the state was restored from a
            # checkpoint): nothing can clobber dst anymore and the adopt
            # installs a fresh value — drop the stale binding instead of
            # raising the never-materialized error
            dst._pending = None
            dst._pending_aval = None
        else:
            # dst still pending on an older segment: materialize it first
            # so a late flush of that segment cannot clobber the adopted
            # slot
            flush_array(dst)
    p = src._pending
    if p is not None:
        seg, slot = p
        with seg.lock:
            if src._pending is not None:
                seg.arrays[slot] = weakref.ref(dst)
                dst._data = None
                dst._pending = (seg, slot)
                dst._pending_aval = src._pending_aval
                src._pending = None
                src._pending_aval = None
                if _memory._census_active:
                    # dst is (almost always) a tracked param/grad: its
                    # entry keeps counting these bytes, so the deferred
                    # accounting must let go of the slot
                    seg.discount_slot(slot)
                return dst
    # src already flushed (or was never pending): plain buffer handoff
    dst._data = src._data
    dst._pending = None
    dst._pending_aval = None
    return dst


def flush_array(nd):
    """Materialize one pending NDArray by flushing the segment that owns
    it (works cross-thread)."""
    p = getattr(nd, "_pending", None)
    if p is not None:
        p[0].flush()
    if nd._data is None:
        from .base import MXNetError
        raise MXNetError(
            "pending NDArray was never materialized — its deferred segment "
            "was abandoned by an exception inside a bulk scope")


def flush_all():
    """Flush every live segment in the process (``waitall`` semantics)."""
    with _segments_lock:
        segs = list(_live_segments)
    for seg in segs:
        if not seg.done:
            seg.flush()


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------
def engine_stats():
    """Counters + cache sizes for both dispatch tiers (reset with
    :func:`reset_op_cache`)."""
    with _cache_lock:
        out = dict(_stats)
        out["op_cache_entries"] = len(_op_cache)
        out["segment_cache_entries"] = len(_segment_cache)
    with _segments_lock:
        live = [s for s in _live_segments if not s.done]
    out["live_segments"] = len(live)
    out["pending_ops"] = sum(len(s.ops) for s in live)
    out["engine_type"] = engine_type()
    return out


def bump_stat(name, by=1):
    """Increment one engine counter (used by autograd/trainer capture
    paths so the fallback rate is visible in ``engine_stats``)."""
    _stats[name] = _stats.get(name, 0) + by


def purge_executable_caches():
    """Drop every resident compiled executable (both dispatch tiers plus
    the vjp cores and shape cache) WITHOUT touching the counters — the
    RESOURCE_EXHAUSTED recovery lever (``memory.release_cached_memory``,
    docs/RESILIENCE.md): executables pin device program memory, and after
    a purge everything recompiles (or ProgramCache-warm-loads) on demand.
    Returns the number of entries dropped."""
    with _cache_lock:
        n = (len(_op_cache) + len(_segment_cache) + len(_shape_cache)
             + len(_vjp_jit_cache))
        _op_cache.clear()
        _segment_cache.clear()
        _segment_pc_keys.clear()
        _shape_cache.clear()
        _vjp_jit_cache.clear()
        _fun_key_memo.clear()
        _stats["cache_purges"] += 1
    return n


def reset_op_cache():
    """Drop both executable caches and zero the counters (tests)."""
    with _cache_lock:
        _op_cache.clear()
        _segment_cache.clear()
        _segment_pc_keys.clear()
        _shape_cache.clear()
        _vjp_jit_cache.clear()
        _fun_key_memo.clear()
        for k in _stats:
            _stats[k] = 0


# ---------------------------------------------------------------------------
# telemetry registration: the dispatch engine's counters/gauges in the
# process-wide registry (docs/OBSERVABILITY.md).  A collector, not owned
# metrics: the hot path keeps mutating the plain ``_stats`` dict and the
# registry reads it only at snapshot time — zero added dispatch cost.
# ---------------------------------------------------------------------------
def _telemetry_collect():
    s = engine_stats()
    return {"engine/" + k: v for k, v in s.items() if k != "engine_type"}


_telemetry.register_collector("engine", _telemetry_collect, {
    "engine/op_cache_hits": ("counter", "per-op executable cache hits"),
    "engine/op_cache_misses": ("counter", "per-op executable cache misses"),
    "engine/op_cache_fallbacks": ("counter",
                                  "ops that bypassed the executable cache"),
    "engine/op_cache_persist_hits": ("counter",
                                     "ProgramCache warm loads (disk-warm "
                                     "executables, XLA skipped)"),
    "engine/lazy_ops_recorded": ("counter", "ops deferred into segments"),
    "engine/lazy_flushes": ("counter", "fused segment executions"),
    "engine/lazy_segment_cache_hits": ("counter",
                                       "segment executable cache hits"),
    "engine/lazy_segment_cache_misses": ("counter",
                                         "segment executable cache misses"),
    "engine/lazy_eager_replays": ("counter",
                                  "segments replayed op-by-op after a "
                                  "flush failure"),
    "engine/tape_ops_recorded": ("counter",
                                 "autograd ops captured into whole-step "
                                 "segments"),
    "engine/step_flushes": ("counter", "whole-step capture executions"),
    "engine/step_capture_fallbacks": ("counter",
                                      "captured steps degraded to the "
                                      "eager per-op path"),
    "engine/cache_purges": ("counter",
                            "executable-cache purges (RESOURCE_EXHAUSTED "
                            "recovery)"),
    "engine/donated_flushes": ("counter",
                               "fused segment executions that donated "
                               "param/optimizer-state buffers"),
    "engine/op_cache_entries": ("gauge", "resident per-op executables"),
    "engine/segment_cache_entries": ("gauge",
                                     "resident segment executables"),
    "engine/live_segments": ("gauge", "unflushed recorded segments"),
    "engine/pending_ops": ("gauge", "ops deferred in live segments"),
})
