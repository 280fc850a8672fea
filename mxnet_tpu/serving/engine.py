"""InferenceEngine: shape-bucketed compiled-program cache + batch dispatch.

The serving-side twin of ``hybridize()``: every distinct input shape JAX
sees costs one XLA compile, so an engine that served arbitrary batch
sizes would recompile constantly.  Instead requests are padded up to a
small ladder of **batch buckets** (powers of two by default) and each
bucket's program is compiled once, held in an LRU-bounded cache, and
reused — the compiled-program-reuse story of the XLA-fusion analysis
(arXiv:2301.13062) applied to serving.

Three model flavors are accepted:

* :class:`~mxnet_tpu.gluon.block.HybridBlock` — via its
  :meth:`~mxnet_tpu.gluon.block.HybridBlock.inference_fn` fast-path hook
  (params ride as jit *arguments*, not HLO constants);
* :class:`~mxnet_tpu.stablehlo.ServedModel` — an exported StableHLO
  artifact; its shapes are frozen, so the bucket ladder is exactly the
  artifact's warmup-manifest buckets (legacy single-program artifacts:
  the one exported batch), and ``precompile()`` with no arguments warms
  all of them at load;
* a plain callable over raw arrays — used as-is (assumed compiled).
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict

import numpy as onp

from ..base import MXNetError
from .. import telemetry as _telemetry
from .metrics import ServingMetrics

__all__ = ["InferenceEngine"]

_DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32)


class InferenceEngine:
    """Run inference forwards padded to shape buckets.

    Parameters
    ----------
    model : HybridBlock | ServedModel | callable
        The inference program.  A ``HybridBlock`` must be initialized
        (and any deferred shapes resolved) first.
    batch_buckets : sequence of int
        Ascending ladder of batch sizes to compile for.  A batch of n
        pads to the smallest bucket >= n; n larger than the top bucket
        is split into top-bucket chunks.
    max_programs : int
        LRU bound on resident compiled programs ((bucket, input-signature)
        entries).
    metrics : ServingMetrics, optional
        Shared metrics sink (compiles / evictions land here).
    stager : mxnet_tpu.io.BatchStager, optional
        Stage decoded request batches onto the device through the same
        placement policy the training side uses (docs/IO.md): padded
        inputs are uploaded before dispatch, so the jit call never pays
        the host->device transfer inside the program dispatch.  Use a
        default-placement or replicated stager here — a trainer's
        data-axis-sharded stager rejects buckets smaller than the mesh's
        data size, in which case the engine warns once and serves
        unstaged rather than failing requests.
    compile_passes : str | PassPipeline, optional
        Per-model override for the captured-program rewrite pipeline
        (comma-separated pass names; None reads the
        ``MXNET_COMPILE_PASSES`` process default, "" disables).  Applies
        to block-backed engines only — a ``ServedModel``'s StableHLO is
        already frozen (ignored with a warning); unknown pass names
        raise HERE, not mid-request.  The pipeline's fingerprint joins
        the ProgramCache key in :meth:`precompile`, and an
        ``int8_residency`` pipeline flags the engine's batches as the
        int8-resident serving mode (``serving/int8_*`` metrics,
        docs/COMPILE_PASSES.md).
    """

    def __init__(self, model, batch_buckets=_DEFAULT_BUCKETS,
                 max_programs=16, metrics=None, precompile=False,
                 stager=None, compile_passes=None):
        self._stager = stager
        self._metrics = metrics if metrics is not None else ServingMetrics()
        self._lock = threading.Lock()
        # RLock: the first-call trace holds it while the block prog
        # re-acquires it to snapshot params (same thread)
        self._trace_lock = threading.RLock()
        # (bucket, per-input (shape-sans-batch, dtype)) -> [prog, traced?]
        # — keyed by the FULL aval signature, not just the bucket: a new
        # dtype/shape at a seen bucket is a fresh jit trace and must take
        # the trace lock like any first call (same key => identical avals
        # => guaranteed jit cache hit, never a retrace)
        self._programs = OrderedDict()
        self._max_programs = max(1, int(max_programs))
        # program label -> ledger peak bytes (resolved once per bucket
        # entry; the ledger lookup takes a lock the request hot path
        # must not pay per batch)
        self._mem_peaks = {}
        self._prog_flops = {}
        self._kind, self._base = self._resolve(model)
        self._model = model
        from ..compile import passes as _passes
        self._pipeline = _passes.resolve_pipeline(compile_passes)
        if self._pipeline is not None and self._kind != "block":
            import warnings
            warnings.warn(
                f"compile_passes={self._pipeline.spec!r} ignored: rewrite "
                f"passes need a captured jaxpr, and a "
                f"{self._kind}-backed engine has none (export/quantize "
                "the block BEFORE serving to use the pipeline)")
            self._pipeline = None
        self._int8_resident = bool(
            self._pipeline is not None
            and self._pipeline.has_pass("int8_residency"))
        # per-bucket pass reports keyed by program label (statusz surface)
        self._passes_reports: dict = {}
        if self._kind == "served":
            # exported shapes are frozen: the artifact's manifest buckets
            # ARE the ladder (legacy single-program artifacts: one bucket)
            self.batch_buckets = tuple(model.buckets)
        else:
            self.batch_buckets = tuple(sorted(set(int(b)
                                                  for b in batch_buckets)))
            if not self.batch_buckets or self.batch_buckets[0] < 1:
                raise MXNetError(f"bad batch_buckets {batch_buckets!r}")
        if precompile:
            # load-time warmup from the artifact's manifest (served kind
            # knows its own signature; blocks must pass example specs)
            self.precompile()

    @property
    def metrics(self):
        return self._metrics

    @metrics.setter
    def metrics(self, m):
        """Redirect the metrics sink (a DynamicBatcher given an explicit
        ServingMetrics points its engine here so batch/latency counters
        land in ONE snapshot)."""
        self._metrics = m

    @property
    def max_batch(self):
        return self.batch_buckets[-1]

    def _resolve(self, model):
        from ..gluon.block import HybridBlock
        from ..stablehlo import ServedModel
        if isinstance(model, HybridBlock):
            pure_fn, read_params = model.inference_fn()
            return "block", (pure_fn, read_params)
        if isinstance(model, ServedModel):
            return "served", model
        if callable(model):
            return "callable", model
        raise MXNetError(f"cannot serve {type(model).__name__}: expected "
                         "HybridBlock, ServedModel or callable")

    # -- program cache -----------------------------------------------------
    @staticmethod
    def program_label(key):
        """Short stable label for a bucket-program key — the trace-span
        correlation handle (the serving twin of the ``program`` arg on
        ``step_flush`` spans): requests that ran the same compiled
        program carry the same label.  Precompiled entries override this
        with their ProgramCache key."""
        import hashlib
        bucket, sig = key
        digest = hashlib.sha1(repr(sig).encode()).hexdigest()[:10]
        return f"b{bucket}:{digest}"

    def _program(self, key):
        with self._lock:
            entry = self._programs.get(key)
            if entry is not None:
                self._programs.move_to_end(key)
                return entry
        if self._kind == "block":
            import jax
            from .. import compile as _compile
            _compile.enable_persistent_cache()  # lazy buckets warm-start too
            pure_fn, read_params = self._base
            fn = pure_fn if self._pipeline is None \
                else self._rewritten_callable(key)
            jit_fn = jax.jit(fn)
            trace_lock = self._trace_lock

            def prog(*inputs):
                # params re-read per dispatch: a weight hot-swap (same
                # avals) is served immediately as a jit cache hit, never
                # a recompile.  The snapshot happens under the trace
                # lock — another thread's first-call trace swaps the
                # SAME Parameter buffers for tracers, and reading
                # mid-swap would hand foreign tracers to jit
                with trace_lock:
                    raws = read_params()
                return jit_fn(raws, *inputs)
        elif self._kind == "served":
            prog = self._base.program(key[0])
        else:
            prog = self._base
        return self._install_program(key, prog,
                                     traced=self._kind != "block",
                                     count_compile=self._kind == "block")

    def _install_program(self, key, prog, traced, count_compile=False,
                         replace=False, label=None):
        """Insert a program entry under the LRU bound (shared by lazy
        dispatch and :meth:`precompile`)."""
        with self._lock:
            entry = self._programs.get(key)      # lost a race: keep theirs
            if entry is None or replace:
                entry = self._programs[key] = [
                    prog, traced, label or self.program_label(key)]
                if count_compile:
                    self._metrics.inc("compiles")
            self._programs.move_to_end(key)
            while len(self._programs) > self._max_programs:
                self._programs.popitem(last=False)
                self._metrics.inc("cache_evictions")
        return entry

    def bucket_for(self, n):
        for b in self.batch_buckets:
            if b >= n:
                return b
        return self.batch_buckets[-1]

    # -- rewrite-pass pipeline ---------------------------------------------
    def _rewritten_callable(self, key):
        """Capture the block's inference fn at this bucket's avals, run
        the rewrite pipeline (validated against the unrewritten capture
        — a discarded rewrite serves the original program), and return
        the replay callable to jit in pure_fn's place.  Compile-time
        only: the request hot path never sees any of this."""
        import jax
        from ..compile import passes as _passes
        bucket, sig = key
        pure_fn, read_params = self._base
        label = f"passes:{self.program_label(key)}"
        sds = [jax.ShapeDtypeStruct((bucket,) + s, onp.dtype(d))
               for s, d in sig]
        with self._trace_lock:
            # capture swaps Parameter buffers for tracers (inference_fn
            # discipline) — same serialization as any first-call trace
            raws = read_params()
            prog = _passes.CapturedProgram.capture(
                pure_fn, (raws, *sds), label=label)
        rewritten, reports = self._pipeline.run(
            prog, example_args=(raws, *sds), label=label)
        self._passes_reports[label] = reports
        return rewritten.as_callable()

    def compile_passes_info(self):
        """The rewrite pipeline's serving surface (``/statusz``): spec,
        cache-key fingerprint, int8-resident flag, per-bucket reports."""
        if self._pipeline is None:
            return {"spec": "", "fingerprint": None,
                    "int8_resident": False, "programs": {}}
        return {"spec": self._pipeline.spec,
                "fingerprint": self._pipeline.fingerprint(),
                "int8_resident": self._int8_resident,
                "programs": {k: list(v)
                             for k, v in self._passes_reports.items()}}

    # -- execution ---------------------------------------------------------
    @staticmethod
    def _pad(arr, bucket):
        arr = onp.asarray(arr)
        n = arr.shape[0]
        if n == bucket:
            return arr
        pad = onp.zeros((bucket - n,) + arr.shape[1:], dtype=arr.dtype)
        return onp.concatenate([arr, pad], axis=0)

    def run_batch(self, inputs, n_valid=None):
        """Run one stacked batch through the bucketed program.

        ``inputs``: tuple/list of batch-major arrays (all sharing batch
        dim).  Returns a tuple of **numpy** outputs sliced back to the
        live rows.  Batches above the top bucket are chunked.
        """
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        inputs = [onp.asarray(a) for a in inputs]
        n = inputs[0].shape[0]
        if n_valid is None:
            n_valid = n
        if any(a.shape[0] != n for a in inputs):
            raise MXNetError("input batch dims disagree: "
                             f"{[a.shape for a in inputs]}")

        top = self.batch_buckets[-1]
        if n > top:
            chunks = [self.run_batch([a[i:i + top] for a in inputs])
                      for i in range(0, n, top)]
            outs = tuple(onp.concatenate([c[k] for c in chunks], axis=0)
                         for k in range(len(chunks[0])))
            return tuple(o[:n_valid] for o in outs)

        bucket = self.bucket_for(n)
        # .name, not .str: ml_dtypes customs all stringify as void
        # ('<V1'/'<V2'), which would alias distinct dtypes to one program
        sig = tuple((a.shape[1:], a.dtype.name) for a in inputs)
        # one dispatched batch = one "serve" step span (fully bracketed;
        # chunked over-top-bucket batches recursed above each get their
        # own) — the serving twin of the trainer step id
        with _telemetry.step_span("serve"):
            return self._run_bucket(inputs, n_valid, bucket, sig)

    def _run_bucket(self, inputs, n_valid, bucket, sig):
        # worker-side fault point: in a replica-fleet worker this is the
        # request hot path, so `serving.replica@N:crash` / `:hang(...)`
        # kills or wedges one replica mid-request-storm — the chaos lever
        # behind the supervisor-restart / router-retry acceptance proofs
        # (docs/SERVING.md fleet section, docs/RESILIENCE.md registry)
        from .. import faults as _faults
        _faults.point("serving.replica")
        entry = self._program((bucket, sig))
        prog = entry[0]
        padded = [self._pad(a, bucket) for a in inputs]
        t0 = time.perf_counter()
        if self._stager is not None:
            # decoded request batches staged through the shared
            # BatchStager (docs/IO.md) — inside the timed window, so
            # exec_ms keeps counting the upload the request still pays.
            # Serving availability beats staging: a placement the stager
            # cannot satisfy (e.g. a data-sharded mesh layout whose axis
            # does not divide this bucket) degrades to unstaged dispatch
            with _telemetry.phase("stage"):
                try:
                    padded = [self._stager.put(a) for a in padded]
                except Exception as e:      # noqa: BLE001 — keep serving
                    self._stager = None
                    import warnings
                    warnings.warn(
                        f"request-batch staging failed ({e!r}); disabling "
                        "the stager — use a default-placement/replicated "
                        "BatchStager for serving (docs/IO.md)")
        from .. import memory as _memory
        if _memory._census_active:
            # census origin for the decoded+padded request batch (staged
            # or not) — the serving-side resident-bytes class
            for a in padded:
                _memory.tag(a, "serving_batch")
        # the engine hop of a request trace: requests riding this batch
        # (bound by the batcher via telemetry.request_scope) each get an
        # `execute` span naming the compiled program they actually ran —
        # the same program-correlation discipline as the step_flush span
        # (plus the ledger's peak bytes when the program is known — the
        # bytes column next to the milliseconds)
        mem_extra = {}
        try:
            mem_bytes = self._mem_peaks[entry[2]]
        except KeyError:
            mem_bytes = _memory.ledger_peak(entry[2])
            self._mem_peaks[entry[2]] = mem_bytes
        if mem_bytes:
            mem_extra["bytes"] = mem_bytes
        # the flops column rides the same lookup discipline (one ledger
        # read per program, memoized); mfu is derived from the elapsed
        # wall just before the spans close — see ph.set() below
        from .. import costs as _costs
        try:
            prog_flops = self._prog_flops[entry[2]]
        except KeyError:
            prog_flops = _costs.ledger_flops(entry[2])
            self._prog_flops[entry[2]] = prog_flops
        if prog_flops:
            mem_extra["flops"] = int(prog_flops)
        with _telemetry.request_span("execute", bucket=bucket,
                                     occupancy=n_valid, program=entry[2],
                                     **mem_extra) as rspan, \
                _telemetry.phase("execute", bucket=bucket,
                                 occupancy=n_valid, **mem_extra) as ph:
            if not entry[1]:
                # first call of a block-backed bucket traces pure_fn, and
                # tracing swaps Parameter buffers for tracers via
                # _run_with_params — serialize it so a concurrent engine
                # call cannot observe the block mid-swap (warmup() avoids
                # even this wait; external forwards of the SAME live block
                # during serving remain the caller's responsibility)
                with self._trace_lock:
                    raw_out = prog(*padded)
                    entry[1] = True
            else:
                raw_out = prog(*padded)
            if not isinstance(raw_out, (tuple, list)):
                raw_out = (raw_out,)
            # host readback is the sync point (asnumpy discipline)
            outs = tuple(onp.asarray(o)[:n_valid] for o in raw_out)
            if prog_flops:
                # per-execution MFU against the cost ledger: set on both
                # the step-phase span and the per-request trace span
                # before they close (docs/OBSERVABILITY.md costs section)
                ca = _costs.execution_attrs(
                    entry[2], (time.perf_counter() - t0) * 1e6)
                if ca:
                    ph.set(**ca)
                    rspan.set(**ca)
        exec_ms = (time.perf_counter() - t0) * 1000.0
        self._metrics.record_batch(n_valid, bucket, exec_ms, t0)
        if self._int8_resident:
            # the quantized serving mode's traffic share, next to the
            # plain batch counters (serving/int8_* — docs/SERVING.md)
            self._metrics.inc("int8_batches")
            self._metrics.inc("int8_requests", n_valid)
        return outs

    def predict(self, inputs):
        """Single-request convenience: per-example arrays (no batch dim)
        in, per-example outputs out."""
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        stacked = [onp.asarray(a)[None, ...] for a in inputs]
        outs = self.run_batch(stacked, n_valid=1)
        outs = tuple(o[0] for o in outs)
        return outs if len(outs) > 1 else outs[0]

    # -- ahead-of-time compilation -----------------------------------------
    @staticmethod
    def _specs_of(example_inputs):
        # one normalizer for "arrays or (shape, dtype) pairs" in the repo
        from ..gluon.block import HybridBlock
        return HybridBlock._input_specs(example_inputs)

    def precompile(self, example_inputs=None, buckets=None,
                   max_workers=None, cache="default"):
        """AOT-compile bucket programs WITHOUT executing them
        (``jit(...).lower(...).compile()``), buckets in parallel.

        Tracing/lowering runs serially under the trace lock (it is Python
        and, for block models, swaps Parameter buffers); the XLA compiles
        — the expensive part — run on a thread pool (XLA releases the
        GIL), so a multi-bucket warmup overlaps instead of paying the
        ladder serially.  Executables go through the
        ``mxnet_tpu.compile`` program index: a restarted server
        deserializes yesterday's programs instead of recompiling
        (``aot_cache_hits`` metric).

        ``example_inputs``: per-example arrays or ``(shape, dtype)`` specs
        (no batch dim).  A :class:`~mxnet_tpu.stablehlo.ServedModel`
        engine defaults to the artifact's warmup manifest, so a bare
        ``engine.precompile()`` warms every exported bucket at load.
        Returns ``{"wall_s", "buckets": {bucket: info}}``.
        """
        import time as _time
        import jax
        from .. import compile as _compile

        if self._kind == "callable":
            return {"wall_s": 0.0, "buckets": {}}
        if example_inputs is None:
            if self._kind != "served":
                raise MXNetError(
                    "precompile() on a block-backed engine needs "
                    "example_inputs (per-example arrays or (shape, dtype) "
                    "specs)")
            specs = self._model.input_signature()
        else:
            if not isinstance(example_inputs, (tuple, list)):
                example_inputs = (example_inputs,)
            specs = self._specs_of(example_inputs)
        buckets = tuple(buckets) if buckets else self.batch_buckets
        for b in buckets:
            if b not in self.batch_buckets:
                raise MXNetError(f"precompile bucket {b} not in ladder "
                                 f"{self.batch_buckets}")
        sig = tuple((s, onp.dtype(d).name) for s, d in specs)

        t0 = _time.perf_counter()
        jobs = []
        for b in buckets:
            key = (b, sig)
            with self._lock:
                entry = self._programs.get(key)
                if entry is not None and entry[1]:
                    continue          # already compiled (or non-block base)
            sds = [jax.ShapeDtypeStruct((b,) + s, onp.dtype(d))
                   for s, d in specs]

            def job(b=b, sds=sds, key=key):
                # lowering is Python (and, for blocks, swaps Parameter
                # buffers) — serialize it under the trace lock; the XLA
                # compile below then overlaps with the NEXT bucket's
                # lowering and with other compiles.  The rewrite
                # pipeline (validation included) runs inside the same
                # window — also Python, also parameter-swapping.
                tl = _time.perf_counter()
                extra = None
                if self._kind == "block":
                    pure_fn, read_params = self._base
                    fn = pure_fn
                    if self._pipeline is not None:
                        fn = self._rewritten_callable(key)
                        # rewritten or not, the ACTIVE pipeline brands
                        # the cache key: a validation-discarded rewrite
                        # must not alias the no-pipeline twin either
                        extra = self._pipeline.fingerprint()
                    with self._trace_lock:
                        lowered = jax.jit(fn).lower(read_params(), *sds)
                else:
                    with self._trace_lock:
                        lowered = jax.jit(self._model.program(b)).lower(
                            *sds)
                lower_s = _time.perf_counter() - tl
                compiled, info = _compile.aot_compile_lowered(
                    lowered, cache=cache, label=f"serving:bucket{b}",
                    extra_key=extra)
                return compiled, dict(info, lower_s=lower_s)

            def safe_job(job=job):
                # a failing bucket must not discard the others' paid
                # compiles: capture, install what succeeded, re-raise last
                try:
                    return "ok", job()
                except Exception as e:      # noqa: BLE001
                    return "err", e

            jobs.append((key, safe_job))

        results = _compile.parallel_compile([j for _, j in jobs],
                                            max_workers=max_workers)

        infos = {}
        first_err = None
        for (key, _job), (status, payload) in zip(jobs, results):
            if status == "err":
                first_err = first_err or payload
                continue
            compiled, info = payload
            if self._kind == "block":
                _pure_fn, read_params = self._base
                trace_lock = self._trace_lock

                def prog(*inputs, _c=compiled, _rp=read_params,
                         _tl=trace_lock):
                    with _tl:
                        raws = _rp()
                    return _c(raws, *inputs)
            else:
                prog = compiled
            # precompiled entries correlate by their ProgramCache key, so
            # a trace's execute span names the exact persisted artifact
            pc_key = info.get("key")
            self._install_program(
                key, prog, traced=True, replace=True,
                label=f"pc:{str(pc_key)[:12]}" if pc_key else None)
            self._metrics.inc("aot_cache_hits" if info["cache_hit"]
                              else "aot_compiles")
            if not info["cache_hit"]:
                self._metrics.inc("compiles")
            infos[key[0]] = info
        if first_err is not None:
            raise first_err
        return {"wall_s": _time.perf_counter() - t0, "buckets": infos}

    # -- warmup ------------------------------------------------------------
    def warmup(self, example_inputs, buckets=None):
        """Pre-compile bucket programs with zeros shaped like
        ``example_inputs`` (per-example arrays, no batch dim) so the first
        real request doesn't pay an XLA compile.  Returns the bucket list
        warmed."""
        if not isinstance(example_inputs, (tuple, list)):
            example_inputs = (example_inputs,)
        specs = [(onp.asarray(a).shape, onp.asarray(a).dtype)
                 for a in example_inputs]
        buckets = tuple(buckets) if buckets else self.batch_buckets
        for b in buckets:
            if b not in self.batch_buckets:
                raise MXNetError(f"warmup bucket {b} not in ladder "
                                 f"{self.batch_buckets}")
            zeros = [onp.zeros((b,) + s, dtype=d) for s, d in specs]
            self.run_batch(zeros)
        return list(buckets)
