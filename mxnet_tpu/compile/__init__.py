"""mxnet_tpu.compile — persistent compilation cache + ahead-of-time (AOT)
compilation.

The reference's ``CachedOp`` pays graph construction once per process; the
JAX graft re-paid full trace + XLA compile on **every** process start
(BERT-large: minutes of compile on the dryrun host) and on every serving
shape bucket.  This subsystem makes warm starts cheap everywhere:

* :func:`enable_persistent_cache` turns on JAX's persistent compilation
  cache in the one cache directory (:func:`cache_root`), so every ``jit``
  compile — trainer steps, hybridized blocks, serving buckets — is fetched
  from disk on repeat runs; the trainer's and the serving engines' build
  paths call it, so the normal entry points hit the cache;
* :class:`~.cache.ProgramCache` (``default_program_cache``) is our own
  program-artifact index keyed by StableHLO fingerprint x backend x
  jax/jaxlib/mxnet_tpu versions, holding fully serialized executables for
  the AOT entry points (:meth:`HybridBlock.aot_compile`,
  :meth:`InferenceEngine.precompile`);
* :func:`aot_compile_lowered` + :func:`parallel_compile` are the shared
  AOT core: compile a ``jax.jit(...).lower(...)`` artifact through the
  index, optionally many at once on threads (XLA compilation releases the
  GIL, so multi-bucket serving warmup overlaps).

None of the cache *setup* touches the accelerator: configuring the cache
is pure config/filesystem work.  Everything degrades to a plain recompile
on any cache damage.

Between capture and persistence sits the deterministic rewrite-pass
pipeline (:mod:`.passes` — ``MXNET_COMPILE_PASSES``, per-model
overrides): validated jaxpr rewrites such as ``int8_residency`` run
before lowering, and their pipeline fingerprint joins the ProgramCache
key (``docs/COMPILE_PASSES.md``).

Env surface: jax's own ``JAX_COMPILATION_CACHE_DIR`` places the cache;
``MXNET_COMPILE_CACHE``, ``MXNET_COMPILE_CACHE_MAX_BYTES``,
``MXNET_COMPILE_AOT_WORKERS``, ``MXNET_COMPILE_PASSES`` are registered in
``mxnet_tpu.util``.  See ``docs/COMPILE.md`` and ``docs/COMPILE_PASSES.md``.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time

from .. import util
from .cache import ProgramCache, version_stamp  # noqa: F401

__all__ = ["enable_persistent_cache", "disable_persistent_cache",
           "persistent_cache_enabled", "cache_root",
           "program_cache_dir", "default_program_cache", "ProgramCache",
           "fingerprint_lowered", "aot_compile_lowered", "load_executable",
           "store_executable", "parallel_compile", "aot_workers",
           "cache_info", "version_stamp"]

_state = {"enabled": False, "dir": None, "program_cache": None}
_lock = threading.Lock()


# -- directories ------------------------------------------------------------
# The fallback location is fixed, inside the checkout and git-ignored: the
# directory path is part of XLA's cache key, so a cache that moves (a
# temporary name, a pid, $HOME on a machine that is thrown away) never hits.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")


def cache_root():
    """The one compile-cache directory (not created until first use):
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it — then the
    cache is placed from outside and this code sets no other directory —
    else ``.compile_cache/`` in the checkout.  XLA's persistent cache
    writes its entries directly here; the program index lives in
    ``programs/`` beneath it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def program_cache_dir():
    """Where the mxnet_tpu program-artifact index lives."""
    return os.path.join(cache_root(), "programs")


# -- persistent XLA cache ---------------------------------------------------
def enable_persistent_cache():
    """Turn on JAX's persistent compilation cache in :func:`cache_root` and
    drop the min-compile-time/min-size gates so every program is eligible.

    Pure configuration: no backend is initialized here.  Where
    ``JAX_COMPILATION_CACHE_DIR`` was set when the process started, jax
    already took the directory from it at import and this sets none.
    Idempotent, and cheap when already on (the trainer and engine build
    paths call it); returns the cache directory, or None when
    ``MXNET_COMPILE_CACHE=0`` disables caching globally.
    """
    if not util.getenv("MXNET_COMPILE_CACHE"):
        return None
    d = cache_root()
    with _lock:
        if _state["enabled"] and _state["dir"] == d:
            return d
        import jax
        try:
            os.makedirs(d, exist_ok=True)
        except OSError:
            # unwritable cache root (read-only rootfs): caching is
            # best-effort — degrade to uncached compiles
            return None
        if jax.config.jax_compilation_cache_dir != d:
            # never reached where JAX_COMPILATION_CACHE_DIR placed the
            # cache before the process started: jax read it at import
            jax.config.update("jax_compilation_cache_dir", d)
        _key_jax_cache_by_parts()
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # XLA's cache is left unbounded (jax's default; its own
        # JAX_COMPILATION_CACHE_MAX_SIZE caps it from outside): with a cap
        # every put re-reads the whole directory, and an entry written
        # before the cap was set has no access-time file, which makes
        # every later put fail
        _reset_jax_cache_latch()
        _state["enabled"] = True
        _state["dir"] = d
        return d


def _key_jax_cache_by_parts():
    """jax keys its cache by the program with debug locations stripped,
    as :func:`fingerprint_lowered` does, and ``telemetry.part``'s names
    live there: an executable compiled before a scope existed or moved
    would be a warm hit and name a device trace's operations by the old
    layout.  ``cache_key.custom_hook`` is jax's own place for an addition
    to its key; ``telemetry.PARTS_VERSION`` goes there (and, through
    :func:`version_stamp`, into the ProgramCache's).  Not
    ``jax_compilation_cache_include_metadata_in_key``, which would
    recompile for every edited line.  ``tests/test_parts.py`` holds a jax
    upgrade to it: a bumped version must miss the cache."""
    from jax._src import cache_key as _ck
    from .. import telemetry
    _ck.custom_hook = lambda: f"mx.parts={telemetry.PARTS_VERSION}"


def _reset_jax_cache_latch():
    """jax decides cache-is-used ONCE, at the first compile of the
    process; any jit that ran before enable/disable (e.g. parameter-init
    jits inside ``initialize()``) latches that decision.  Reset it so the
    new cache config takes effect for subsequent compiles."""
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()


def disable_persistent_cache():
    """Switch JAX's persistent compilation cache off (config-only, like
    enable; the directory setting is left alone)."""
    import jax
    with _lock:
        jax.config.update("jax_enable_compilation_cache", False)
        _reset_jax_cache_latch()
        _state["enabled"] = False
        _state["dir"] = None


def persistent_cache_enabled():
    return bool(_state["enabled"])


def default_program_cache():
    """The process-wide :class:`ProgramCache` (created on first use), or
    None when ``MXNET_COMPILE_CACHE=0``."""
    if not util.getenv("MXNET_COMPILE_CACHE"):
        return None
    with _lock:
        pc = _state["program_cache"]
        if pc is None or pc.root != program_cache_dir():
            try:
                pc = _state["program_cache"] = ProgramCache(
                    program_cache_dir(),
                    max_bytes=int(
                        util.getenv("MXNET_COMPILE_CACHE_MAX_BYTES")))
            except OSError:
                return None     # unwritable root: run uncached
        return pc


def cache_info():
    """Introspection snapshot: directories, persistent-cache state, program
    index stats, and the dispatch engine's executable-cache counters (the
    other producer/consumer of the program index — docs/ENGINE.md)."""
    pc = _state["program_cache"]
    from .. import engine as _engine
    return {
        "root": cache_root(),
        "persistent_cache": {"enabled": _state["enabled"],
                             "dir": _state["dir"]},
        "program_cache": None if pc is None else {
            "dir": pc.root, "max_bytes": pc.max_bytes,
            "entries": len(pc.entries()), "bytes": pc.total_bytes(),
            "by_kind": _entries_by_kind(pc),
            "stats": dict(pc.stats)},
        "engine": _engine.engine_stats(),
    }


def _entries_by_kind(pc):
    """Program-index entry count per compile-pipeline tier (``op`` /
    ``lazy_segment`` / ``step_segment`` / ``trainer_*`` / AOT labels) —
    the on-disk view of the keyspace table in docs/COMPILE.md."""
    out = {}
    try:
        for e in pc.entries():
            kind = (e.get("meta") or {}).get("kind") or "aot"
            out[kind] = out.get(kind, 0) + 1
    except Exception:
        pass
    return out


def _record_memory(compiled, key, label, warm=False):
    """Feed the per-program memory AND cost ledgers (mxnet_tpu.memory /
    mxnet_tpu.costs) at every AOT compile / warm-load — byte and flop
    figures stored alongside the ProgramCache key
    (docs/OBSERVABILITY.md).  ``warm=True`` on the deserialized-load
    path: a warm-loaded executable's memory_analysis loses the donation
    alias table (and its cost_analysis comes from a reconstructed
    module), so both ledgers flag those numbers instead of trusting them
    as fresh."""
    try:
        from .. import memory as _memory
        _memory.record_program(compiled, key=key, label=label or "",
                               kind="aot", warm=warm)
    except Exception:   # noqa: BLE001 — the ledger is best-effort
        pass
    try:
        from .. import costs as _costs
        _costs.record_program(compiled, key=key, label=label or "",
                              kind="aot", warm=warm)
    except Exception:   # noqa: BLE001 — the ledger is best-effort
        pass


# -- AOT core ---------------------------------------------------------------
def _lowered_devices(lowered):
    """The devices ``lowered`` was lowered for, in assignment order (jax
    computes them from the argument/out shardings; a plain single-device
    jit gets the default device)."""
    return tuple(lowered._lowering._device_list)


def fingerprint_lowered(lowered, backend=None, extra=None):
    """StableHLO fingerprint of a ``jax.stages.Lowered``: sha256 over the
    module bytecode x backend x device assignment x toolchain versions —
    the ProgramCache key.

    The device ids are part of the key because the StableHLO is not: the
    same one-device program lowered for chip 0 and for chip 3, or a mesh
    program over two different device sets, are different executables.

    ``extra`` folds an additional component into the key — the rewrite
    pipeline's ``PassPipeline.fingerprint()`` rides here, so a program
    compiled under ``MXNET_COMPILE_PASSES`` can never stale-hit its
    unrewritten twin even if a pass happens to leave the StableHLO
    byte-identical (docs/COMPILE_PASSES.md).

    Called only after a successful ``lower()``, so reading the default
    backend here never performs first device contact.
    """
    import jax
    ir = lowered.compiler_ir(dialect="stablehlo")
    try:
        # hash the program, not its provenance: strip debug locations the
        # way jax's own cache key does, so the same net traced from a
        # different call site (or an edited file) still warm-starts
        from jax._src.lib.mlir import passmanager as _pm
        from jax._src.interpreters import mlir as _mlir
        with ir.context:
            clone = ir.operation.clone()
            _pm.PassManager.parse("builtin.module(strip-debuginfo)").run(
                clone)
            blob = _mlir.module_to_bytecode(clone)
    except Exception:
        blob = str(ir).encode()
    h = hashlib.sha256(blob)
    h.update(str(backend or jax.default_backend()).encode())
    h.update(repr([d.id for d in _lowered_devices(lowered)]).encode())
    h.update(repr(sorted(version_stamp().items())).encode())
    if extra:
        h.update(str(extra).encode())
    return h.hexdigest()


def load_executable(cache, key, lowered):
    """Warm load: the executable stored under ``key``, loaded onto the
    devices ``lowered`` was lowered for, or None on a miss.

    The devices are passed explicitly because
    ``serialize_executable.deserialize_and_load`` otherwise assumes EVERY
    device of the backend: a one-device program read back on a four-chip
    host would become a four-shard executable and fail at dispatch.

    A blob that hashes clean but will not load (e.g. a jaxlib rebuild at
    the same version string) is set aside so restarts stop re-paying the
    doomed load.
    """
    blob = cache.get(key)
    if blob is None:
        return None
    try:
        from jax.experimental import serialize_executable as _se
        payload, in_tree, out_tree = pickle.loads(blob)
        return _se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=_lowered_devices(lowered))
    except Exception:
        cache.invalidate(key)
        return None


def store_executable(cache, key, compiled, meta=None):
    """Serialize an already-compiled executable into the index
    (best-effort: a program that cannot be serialized is just not cached)."""
    try:
        from jax.experimental import serialize_executable as _se
        payload, in_tree, out_tree = _se.serialize(compiled)
        cache.put(key, pickle.dumps((payload, in_tree, out_tree)), meta=meta)
    except Exception:
        pass


def aot_compile_lowered(lowered, cache="default", label=None,
                        extra_key=None):
    """Compile a ``Lowered`` through the program-artifact index.

    On an index hit the serialized executable is deserialized and loaded
    (no XLA compile); on a miss it is compiled — also populating JAX's
    persistent cache — then serialized into the index.  Any cache damage
    degrades to a plain compile.  ``extra_key`` joins the fingerprint
    (pass-pipeline callers — see :func:`fingerprint_lowered`).

    Returns ``(compiled, info)`` where ``info`` has ``cache_hit``,
    ``seconds``, ``key``.
    """
    enable_persistent_cache()
    if cache == "default":
        cache = default_program_cache()
    t0 = time.perf_counter()
    key = None
    if cache is not None:
        try:
            key = fingerprint_lowered(lowered, extra=extra_key)
        except Exception:
            key = None
        compiled = None if key is None else \
            load_executable(cache, key, lowered)
        if compiled is not None:
            _record_memory(compiled, key, label, warm=True)
            return compiled, {"cache_hit": True, "key": key,
                              "seconds": time.perf_counter() - t0,
                              "label": label}
    compiled = lowered.compile()
    _record_memory(compiled, key, label)
    if cache is not None and key is not None:
        store_executable(cache, key, compiled, meta={"label": label or ""})
    return compiled, {"cache_hit": False, "key": key,
                      "seconds": time.perf_counter() - t0, "label": label}


def aot_workers(n_jobs):
    """Worker count for parallel AOT compilation: the
    ``MXNET_COMPILE_AOT_WORKERS`` override, else min(jobs, cpu count)."""
    w = int(util.getenv("MXNET_COMPILE_AOT_WORKERS"))
    if w > 0:
        return max(1, min(w, n_jobs))
    return max(1, min(n_jobs, os.cpu_count() or 1))


def parallel_compile(jobs, max_workers=None):
    """Run compile thunks concurrently on threads and return their results
    in order.

    XLA compilation releases the GIL, so distinct programs (e.g. serving
    batch buckets) compile in parallel; tracing/lowering must happen
    BEFORE this call (tracing is Python and mutates block state).  The
    first failure is re-raised after all threads finish.
    """
    jobs = list(jobs)
    if not jobs:
        return []
    if len(jobs) == 1:
        return [jobs[0]()]
    from concurrent.futures import ThreadPoolExecutor
    workers = max_workers if max_workers else aot_workers(len(jobs))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        futs = [ex.submit(j) for j in jobs]
        errs = [f.exception() for f in futs]
        for e in errs:
            if e is not None:
                raise e
        return [f.result() for f in futs]


# ---------------------------------------------------------------------------
# telemetry registration: ProgramCache hits / warm loads / invalidations /
# blob bytes in the process-wide registry (docs/OBSERVABILITY.md).  Reads
# the cache lazily — an unconfigured process reports zeros rather than
# creating the on-disk index just to be scraped.
# ---------------------------------------------------------------------------
def _telemetry_collect():
    pc = _state["program_cache"]
    out = {"compile/persistent_cache_enabled": int(bool(_state["enabled"]))}
    stats = dict(pc.stats) if pc is not None else {}
    for k in ("hits", "misses", "puts", "evictions", "corrupt",
              "version_skips"):
        out["compile/" + k] = stats.get(k, 0)
    if pc is not None:
        try:
            entries = pc.entries()
            out["compile/entries"] = len(entries)
            out["compile/bytes"] = sum(int(e.get("bytes", 0))
                                       for e in entries)
        except Exception:   # noqa: BLE001 — index IO is best-effort
            out["compile/entries"] = 0
            out["compile/bytes"] = 0
    else:
        out["compile/entries"] = 0
        out["compile/bytes"] = 0
    # the rewrite-pass pipeline's counters ride the same collector
    # (compile/passes_* — docs/COMPILE_PASSES.md); the submodule import
    # is cheap and deferred to scrape time
    try:
        from . import passes as _passes
        out.update(_passes.telemetry_stats())
    except Exception:   # noqa: BLE001 — scrape must never fail
        for k in ("runs", "rewrites", "unchanged", "validation_failures",
                  "errors", "bytes_saved"):
            out["compile/passes_" + k] = 0
    return out


from .. import telemetry as _telemetry  # noqa: E402

_telemetry.register_collector("compile", _telemetry_collect, {
    "compile/persistent_cache_enabled": ("gauge",
                                         "jax persistent compilation "
                                         "cache wired"),
    "compile/hits": ("counter", "ProgramCache blob hits"),
    "compile/misses": ("counter", "ProgramCache misses"),
    "compile/puts": ("counter", "ProgramCache blobs stored"),
    "compile/evictions": ("counter", "ProgramCache LRU evictions"),
    "compile/corrupt": ("counter",
                        "ProgramCache invalidations (corrupt or "
                        "undeserializable blobs set aside)"),
    "compile/version_skips": ("counter",
                              "entries ignored for toolchain-version "
                              "mismatch"),
    "compile/entries": ("gauge", "program-index entries on disk"),
    "compile/bytes": ("gauge", "program-index blob bytes on disk"),
    "compile/passes_runs": ("counter", "rewrite-pass pipeline invocations"),
    "compile/passes_rewrites": ("counter",
                                "passes that rewrote a captured program "
                                "and validated clean"),
    "compile/passes_unchanged": ("counter",
                                 "pass runs that matched nothing"),
    "compile/passes_validation_failures": ("counter",
                                           "rewrites discarded by the "
                                           "referee (served unrewritten)"),
    "compile/passes_errors": ("counter",
                              "passes that raised (rewrite discarded)"),
    "compile/passes_bytes_saved": ("counter",
                                   "estimated glue bytes removed by "
                                   "validated rewrites"),
})
