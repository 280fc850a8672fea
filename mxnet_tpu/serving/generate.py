"""Generative serving: KV-cached incremental decode + continuous batching.

The predict path (``InferenceEngine``/``DynamicBatcher``) amortizes ONE
forward per request; generation runs *hundreds* of data-dependent forwards
per request, so batching at request granularity would serialize every
long completion behind the batch.  This module batches at **token**
granularity instead (continuous batching / "iteration-level scheduling",
the Orca idea — PAPERS.md): requests join and leave the in-flight decode
batch at token boundaries, so a short completion never waits for a long
co-rider and a fresh prompt starts decoding one step after it arrives.

Two compiled programs serve everything (docs/SERVING.md):

* **prefill** — one pass over the prompt, shape-bucketed by prompt length
  at batch 1 (the InferenceEngine bucket discipline applied to sequence
  length).  Emits the first token (TTFT ends here) and scatters the
  prompt's per-layer K/V into the slot's ring-buffer row.
* **decode** — ONE fixed-shape step over the whole slot table: every call
  advances every active slot by one token against the device-resident
  ``(slots, heads, max_len, head_dim)`` ring caches.  Freed slots ride
  along masked (``active`` write gate), so the shape never changes and
  the program NEVER recompiles as requests churn.

Both compile through ``mxnet_tpu.compile`` (labels ``generate:prefill:L*``
/ ``generate:decode``) so a restarted server warm-loads yesterday's
programs, and both carry the param-swap discipline of
``HybridBlock.inference_fn``: weights ride as jit *arguments*, so a
hot-swap is a jit cache hit, never a recompile.

The loop keeps **one decode step in flight** (docs/SERVING.md): every
slot's last token lives on the device, in an ``int32[slots]`` vector both
programs consume and return beside the rings, so step n+1 is dispatched
before step n's tokens are read, and step n is read, emitted and completed
while the device runs step n+1.  An admission dispatches its prefill and
does not wait for it: the first token is read with whatever else is
unread.  Greedy tokens do not depend on it; a request that ends on
``eos_id`` is found one step late and that step's token is thrown away.

A request may name a ``sink`` (:meth:`GenerationEngine.submit`): its tokens
then leave the loop a step at a time, one ``sink.take(batch)`` for every
such stream of the step, and not through the stream's queue one by one.
``ModelServer`` submits every streamed HTTP request so, with its one
writer thread as the sink (``stream_writer.py``).

Ring-buffer semantics: a slot's position ``p`` writes cache index
``p % max_len`` and attends over ``min(p+1, max_len)`` entries — past
``max_len`` the cache is a sliding window over the last ``max_len``
tokens (softmax is order-invariant, so ring order never matters).
Prefill pads its K/V scatter to the bucket length; the padded rows are
provably dead — decode overwrites index ``j`` at position ``j`` before
the attention mask ever reaches it.

What a layer keeps need not be such a ring.  ``cache_spec(max_len)`` names,
for each layer, any number of ``(kind, trailing shape, dtype)``; the engine
allocates each as ``(slots,) + shape``, donates them all and writes what
``prefill`` returns at ``(slot, 0, ...)``.  A **state** — a shape with no
position axis, a short convolution's last rows say — is written whole by
every step, so nothing of it is dead: ``prefill`` owes it *as of the valid
length*, not of the padded bucket's end, and ``decode_step`` leaves the
state and the rings of a slot with ``active == 0`` as they were
(docs/SERVING.md, ``models/lfm2.py``).
"""
from __future__ import annotations

import queue
import threading
import time
import weakref

import numpy as onp

from .. import telemetry as _telemetry
from ..util import getenv
from .errors import ServingError, QueueFullError, EngineClosedError
from .metrics import LatencyHistogram, _hist_acc, _hist_add, _hist_expo

__all__ = ["GenerationEngine", "GenerationStream", "GenerationMetrics"]

_DEFAULT_PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256)

# sentinel closing a GenerationStream's token queue
_EOS_SENTINEL = object()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
_live_gen_metrics: "weakref.WeakSet" = weakref.WeakSet()


class GenerationMetrics:
    """Counters/gauges/histograms for one generation engine — the
    ``ServingMetrics`` shape (per-instance lock, retired accumulators so
    process-wide counters stay monotonic across engine lifetimes,
    summed by the module-level ``generate`` telemetry collector)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.ttft = LatencyHistogram()         # submit -> first token
        self.decode_step = LatencyHistogram()  # one whole-batch decode step
        self._counters = {
            "requests": 0,          # accepted submits
            "completed": 0,
            "errors": 0,
            "tokens_generated": 0,
            "prefills": 0,
            "decode_steps": 0,      # whole-batch steps dispatched
            # ... of them, with the step before still unread on the host
            "decode_steps_overlapped": 0,
            "slot_steps_discarded": 0,  # tokens thrown away (EOS a step late)
            "slot_allocs": 0,
            "slot_frees": 0,
            "cache_wraps": 0,       # requests whose ring wrapped (window slid)
            "dispatch_retries": 0,  # transient prefill/decode failures retried
            "kv_inplace_dispatches": 0,  # dispatches that consumed the rings
            "kv_ring_rebuilds": 0,  # rings lost to a failure, zeroed anew
            "rejected_queue_full": 0,
            "prefill_compiles": 0,
            "prefill_cache_hits": 0,
            "decode_compiles": 0,
            "decode_cache_hits": 0,
            # where spans would cost too much (a token, a request, a
            # thread): microsecond sums, read as ratios over the counts
            "loop_offcpu_us": 0,    # stage + emit wall less loop-thread CPU
            "queue_wait_us": 0,     # submit -> slot taken, over prefills
            "emit_to_wire_us": 0,   # _emit -> send returned, per token
            "stream_write_us": 0,   # formatting + send, per token
            "stream_tokens_written": 0,
            "stream_writer_wakes": 0,   # batches of them the writer took
        }
        self._gauges = {
            "free_kv_slots": 0,
            "active_streams": 0,
            "queue_depth": 0,
            "kv_cache_bytes": 0,
            "batch_occupancy": 0,   # active slots in the latest decode step
        }
        _live_gen_metrics.add(self)
        weakref.finalize(self, _retire_gen_metrics, self._counters,
                         self.ttft, self.decode_step)

    def declare(self, counters=(), gauges=()):
        """What a served model brings, as ``(name, help)``: the counters
        of its decode step (they start at 0 like the engine's own) and the
        gauges of its kinds of ring, declared to the ``generate``
        collector under the names the engine's own metrics have."""
        with self._lock:
            for name, _help in counters:
                self._counters.setdefault(name, 0)
        _telemetry.extend_collector("generate", {
            "generate/" + name: (kind, text)
            for kind, entries in (("counter", counters), ("gauge", gauges))
            for name, text in entries})

    def inc(self, counter, n=1):
        with self._lock:
            self._counters[counter] += n

    def add(self, **deltas):
        """Several counters under one lock acquisition."""
        with self._lock:
            for counter, n in deltas.items():
                self._counters[counter] += n

    def set_gauge(self, gauge, value):
        with self._lock:
            self._gauges[gauge] = value

    def observe_ttft(self, ms):
        with self._lock:
            self.ttft.observe(ms)

    def record_decode_step(self, riders, emitted, step_ms, offcpu_us,
                           overlapped):
        """Everything one turn of the loop that dispatched a decode step
        counts, under one lock: the step's ``riders``, the decode tokens
        ``emitted`` beside it (the step before's), whether that step was
        still unread at the dispatch, and ``step_ms`` from the start of
        ``stage`` to the end of ``emit``."""
        with self._lock:
            c = self._counters
            c["decode_steps"] += 1
            c["decode_steps_overlapped"] += bool(overlapped)
            c["tokens_generated"] += emitted
            c["loop_offcpu_us"] += offcpu_us
            self._gauges["batch_occupancy"] = riders
            self.decode_step.observe(step_ms)

    def stats(self):
        with self._lock:
            out = {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "ttft": self.ttft.snapshot(),
                "decode_step": self.decode_step.snapshot(),
            }
        c = out["counters"]
        out["tokens_per_request_mean"] = round(
            c["tokens_generated"] / c["completed"], 3) if c["completed"] \
            else 0.0
        return out


_gen_retired_lock = threading.Lock()
_gen_retired_counters: dict = {}
_gen_retired_hists = {"generate/ttft_ms": _hist_acc(),
                      "generate/decode_step_ms": _hist_acc()}


def _retire_gen_metrics(counters, ttft, decode_step):
    with _gen_retired_lock:
        for k, v in counters.items():
            _gen_retired_counters[k] = _gen_retired_counters.get(k, 0) + v
        _hist_add(_gen_retired_hists["generate/ttft_ms"], ttft)
        _hist_add(_gen_retired_hists["generate/decode_step_ms"], decode_step)


def _gen_telemetry_collect():
    insts = list(_live_gen_metrics)
    out = {}
    with _gen_retired_lock:
        counters: dict = dict(_gen_retired_counters)
        hists = {k: {"counts": list(a["counts"]), "count": a["count"],
                     "sum": a["sum"]}
                 for k, a in _gen_retired_hists.items()}
    gauges: dict = {}
    for m in insts:
        with m._lock:
            for k, v in m._counters.items():
                counters[k] = counters.get(k, 0) + v
            for k, v in m._gauges.items():
                gauges[k] = gauges.get(k, 0) + v
            _hist_add(hists["generate/ttft_ms"], m.ttft)
            _hist_add(hists["generate/decode_step_ms"], m.decode_step)
    for k, v in counters.items():
        out["generate/" + k] = v
    for k, v in gauges.items():
        out["generate/" + k] = v
    for k, acc in hists.items():
        out[k] = _hist_expo(acc)
    return out


_telemetry.register_collector("generate", _gen_telemetry_collect, {
    "generate/requests": ("counter", "accepted generation submits"),
    "generate/completed": ("counter", "generations finished (eos/length)"),
    "generate/errors": ("counter", "generations failed with an exception"),
    "generate/tokens_generated": ("counter", "total tokens emitted"),
    "generate/prefills": ("counter", "prompt prefill dispatches"),
    "generate/decode_steps": ("counter", "whole-batch decode steps"),
    "generate/decode_steps_overlapped": ("counter",
                                         "decode steps dispatched while the "
                                         "step before was still unread on "
                                         "the host"),
    "generate/slot_steps_discarded": ("counter",
                                      "tokens of a decode step thrown away: "
                                      "their request had ended on eos_id one "
                                      "step before"),
    "generate/slot_allocs": ("counter", "KV slots allocated"),
    "generate/slot_frees": ("counter", "KV slots freed"),
    "generate/cache_wraps": ("counter",
                             "requests whose KV ring wrapped (sliding "
                             "window engaged)"),
    "generate/dispatch_retries": ("counter",
                                  "transient prefill/decode failures "
                                  "retried"),
    "generate/kv_inplace_dispatches": ("counter",
                                       "prefill/decode dispatches that "
                                       "consumed the donated KV rings "
                                       "(updated in place)"),
    "generate/kv_ring_rebuilds": ("counter",
                                  "KV rings reallocated after a failure "
                                  "that consumed them"),
    "generate/rejected_queue_full": ("counter",
                                     "admission-control fast-rejects"),
    "generate/prefill_compiles": ("counter",
                                  "prefill bucket XLA compiles (cache "
                                  "miss)"),
    "generate/prefill_cache_hits": ("counter",
                                    "prefill program-index warm loads"),
    "generate/decode_compiles": ("counter",
                                 "decode program XLA compiles (cache "
                                 "miss)"),
    "generate/decode_cache_hits": ("counter",
                                   "decode program-index warm loads"),
    "generate/loop_offcpu_us": ("counter",
                                "us the loop thread was runnable and not "
                                "running in stage + emit (wall less "
                                "thread CPU time)"),
    "generate/queue_wait_us": ("counter",
                               "us from submit to a slot taken, summed "
                               "over prefills"),
    "generate/emit_to_wire_us": ("counter",
                                 "us from a token's emit to its send "
                                 "returning, summed over streamed tokens"),
    "generate/stream_write_us": ("counter",
                                 "us inside formatting a token line and its "
                                 "send, summed over streamed tokens"),
    "generate/stream_tokens_written": ("counter",
                                       "token lines the server's stream "
                                       "writer put on the sockets"),
    "generate/stream_writer_wakes": ("counter",
                                     "times the stream writer woke to a "
                                     "batch of the loop's tokens"),
    "generate/free_kv_slots": ("gauge", "unallocated KV-cache slots"),
    "generate/active_streams": ("gauge", "requests in the decode batch"),
    "generate/queue_depth": ("gauge", "admitted requests awaiting a slot"),
    "generate/kv_cache_bytes": ("gauge",
                                "device-resident KV ring-buffer bytes"),
    "generate/batch_occupancy": ("gauge",
                                 "active slots in the latest decode step"),
    "generate/ttft_ms": ("histogram", "submit -> first-token ms"),
    "generate/decode_step_ms": ("histogram",
                                "wall ms of one turn of the loop: stage of "
                                "a decode step to the end of the emit of "
                                "the step before"),
})


# ---------------------------------------------------------------------------
# per-request stream handle
# ---------------------------------------------------------------------------
class GenerationStream:
    """One request's handle: a token stream plus the final result.

    Tokens arrive on an internal queue as the engine emits them —
    iterate (:meth:`tokens`) for streaming, or call :meth:`result` to
    block for the completed dict ``{"tokens", "finish_reason",
    "ttft_ms", "tokens_per_s"}``.  A failed generation raises its error
    from both paths.

    A stream submitted with a ``sink`` (see :meth:`GenerationEngine.submit`)
    has no reader in the process: its tokens and its end go to the sink
    instead, and :meth:`tokens` yields nothing.  ``wire`` is the sink's own,
    for what it keeps of this stream; the engine never reads it."""

    def __init__(self, trace=None, sink=None):
        self.trace = trace if trace is not None else _telemetry.NULL_TRACE
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._result = None
        self._exc = None
        self._sink = sink
        # the loop's way into the batch of its step: GenerationEngine._post,
        # given at admission (a request that fails in the queue has none)
        self._post = None
        self.wire = None

    # engine-side ----------------------------------------------------------
    def _put(self, token):
        """A token on its way out, or None: the stream has ended.  Both
        carry the stamp of this call: what a token waits between here and
        the socket is summed by whoever writes it out.  To a sink the end
        travels behind the stream's last token, in the same batch or a
        later one, so it cannot overtake it."""
        stamp = time.perf_counter_ns()
        if self._sink is None:
            self._q.put(_EOS_SENTINEL if token is None else (token, stamp))
        elif self._post is None:
            # never admitted, so no step's batch holds a token of it
            self._sink.take([(self, token, stamp)])
        else:
            self._post(self, token, stamp)

    def _emit(self, token):
        self._put(int(token))

    def _complete(self, result):
        self._result = result
        self._done.set()
        self._put(None)

    def _fail(self, exc):
        self._exc = exc
        self._done.set()
        self._put(None)

    # client-side ----------------------------------------------------------
    @property
    def done(self):
        return self._done.is_set()

    def stamped_tokens(self, timeout=None):
        """Yield ``(token id, emit stamp)`` as tokens are generated, the
        stamp in ``time.perf_counter_ns()``; raises the generation's error
        (if any) after the stream closes.  ``timeout`` bounds the wait for
        EACH token (``TimeoutError`` past it)."""
        while True:
            try:
                t = self._q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError("no token within timeout") from None
            if t is _EOS_SENTINEL:
                if self._exc is not None:
                    raise self._exc
                return
            yield t

    def tokens(self, timeout=None):
        """:meth:`stamped_tokens` without the stamps."""
        for t, _emit_ns in self.stamped_tokens(timeout):
            yield t

    def __iter__(self):
        return self.tokens()

    def result(self, timeout=None):
        """Block for the final result dict (or raise the error)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in flight")
        if self._exc is not None:
            raise self._exc
        return self._result


class _GenRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "stream", "trace",
                 "t_submit", "t_first", "t_decode0", "slot", "generated",
                 "sent", "wrapped", "steps", "probe")

    def __init__(self, prompt, max_new, eos_id, stream, probe=False):
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.stream = stream
        self.trace = stream.trace
        self.t_submit = time.perf_counter()
        self.t_first = None
        self.t_decode0 = None
        self.slot = None
        self.generated = []
        # tokens whose program has been dispatched: the prefill's, then one
        # a decode step ridden; ahead of len(generated) by what is unread
        self.sent = 0
        self.wrapped = False
        self.steps = 0
        # a probed request keeps, a token, what the program computed it from
        self.probe = [] if probe else None


class _Unread:
    """A dispatched program whose tokens the host has not read yet: a
    prefill (``req`` and its stamps) or a decode step (``riders``, each a
    request and the slot it rode in).  ``step_id`` is the loop step that
    dispatched it."""

    __slots__ = ("tokens", "probe", "step_id", "req", "riders", "span")

    def __init__(self, tokens, probe, step_id, req=None, riders=None,
                 span=None):
        self.tokens = tokens
        self.probe = probe
        self.step_id = step_id
        self.req = req
        self.riders = riders
        self.span = span


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class GenerationEngine:
    """Continuous-batching generation over a KV-cached causal model.

    Parameters
    ----------
    model : HybridBlock
        An initialized model exposing the incremental-decode protocol:
        ``prefill(tokens, valid_length) -> (logits, [(k, v), ...])`` and
        ``decode_step(tokens, caches, position, active) ->
        (logits, caches')`` with per-layer ``(B, H, M, D)`` ring caches
        (:class:`~mxnet_tpu.models.lm.TransformerLM` is the reference
        implementation).
    slots : int
        KV-cache slots = the max in-flight decode batch (default
        ``MXNET_KV_SLOTS``).
    max_len : int
        Ring-buffer length per slot: the attention window (default
        ``MXNET_KV_MAX_LEN``).  Prompts longer than the top prefill
        bucket (or ``max_len``) are rejected.
    prefill_buckets : sequence of int
        Prompt-length ladder; a prompt pads to the smallest bucket >= its
        length.  Defaults to powers of two capped at ``max_len``.
    max_queue : int
        Admission bound on requests waiting for a slot
        (:class:`QueueFullError` beyond it).
    precompile : bool
        Compile the decode program and every prefill bucket at
        construction (default).  Tracing swaps tracers onto the model's
        SHARED Parameters (``gluon.block.PARAM_TRACE_LOCK`` serializes
        traced execution, but an eager forward of the same model on
        another thread can still observe the swap mid-trace) — so the
        engine front-loads every trace onto the constructing thread,
        like ``InferenceEngine.warmup()``.  ``precompile=False`` defers
        compiles to the loop thread at first use: only safe when nothing
        else touches this model while requests are in flight.
    decode_retries : int
        Transient-failure retries per prefill/decode dispatch.  A retry
        runs on the same rings, so it happens only while they are alive:
        the programs consume them (see :meth:`_dispatch`).
    """

    def __init__(self, model, slots=None, max_len=None, prefill_buckets=None,
                 max_queue=256, metrics=None, precompile=True,
                 cache="default", decode_retries=3, compile_passes=None):
        for attr in ("prefill", "decode_step", "cache_spec"):
            if not hasattr(model, attr):
                raise ServingError(
                    f"{type(model).__name__} does not speak the "
                    f"incremental-decode protocol (missing .{attr} — see "
                    "models.TransformerLM and docs/SERVING.md)")
        self._model = model
        self._slots = int(slots) if slots is not None \
            else int(getenv("MXNET_KV_SLOTS"))
        self._max_len = int(max_len) if max_len is not None \
            else int(getenv("MXNET_KV_MAX_LEN"))
        if self._slots < 1 or self._max_len < 2:
            raise ServingError(
                f"bad KV geometry: slots={self._slots} "
                f"max_len={self._max_len}")
        if prefill_buckets is None:
            prefill_buckets = [b for b in _DEFAULT_PREFILL_BUCKETS
                               if b <= self._max_len]
            if not prefill_buckets:
                prefill_buckets = [self._max_len]
        self._prefill_buckets = tuple(sorted(set(int(b)
                                                 for b in prefill_buckets)))
        if self._prefill_buckets[0] < 1 \
                or self._prefill_buckets[-1] > self._max_len:
            raise ServingError(
                f"prefill_buckets {self._prefill_buckets} must lie in "
                f"[1, max_len={self._max_len}] — prefill scatters the "
                "whole padded prompt into the ring")
        self._metrics = metrics if metrics is not None else \
            GenerationMetrics()
        self._decode_retries = max(0, int(decode_retries))
        self._cache_label = cache
        # rewrite pipeline for the PREFILL programs only (per-model
        # override of MXNET_COMPILE_PASSES — docs/COMPILE_PASSES.md).
        # Decode stays unrewritten: its per-token working set is the KV
        # ring, not activations, so int8 residency buys nothing there
        # and a rewrite would fork its cache key for no win.
        from ..compile import passes as _passes
        self._pipeline = _passes.resolve_pipeline(compile_passes)
        self._passes_reports: dict = {}

        # -- parameters ride as jit arguments (inference_fn discipline) --
        from ..base import MXNetError
        self._ps = model._tree_params()
        if any(p.is_deferred or p._nd is None for p in self._ps):
            raise MXNetError(
                "GenerationEngine: uninitialized or deferred parameters — "
                "initialize() and run one forward with real data first")

        # -- device-resident ring caches: what the model says it keeps, a
        # layer: [(kind, trailing shape, dtype), ...], each (S,) + shape --
        from ..base import np_dtype
        S, M = self._slots, self._max_len
        spec = model.cache_spec(M)
        self._rings_per_layer = [len(layer) for layer in spec]
        self._ring_specs = [(kind, (S,) + tuple(shape), np_dtype(dtype))
                            for layer in spec
                            for kind, shape, dtype in layer]
        by_kind: dict = {}
        for kind, shape, dtype in self._ring_specs:
            by_kind[kind] = by_kind.get(kind, 0) \
                + int(onp.prod(shape)) * onp.dtype(dtype).itemsize
        kv_bytes = sum(by_kind.values())
        budget = int(getenv("MXNET_KV_BUDGET_BYTES"))
        if budget > 0 and kv_bytes > budget:
            # layers need not be alike: every kind with its count and bytes
            kinds = [kind for kind, _shape, _dtype in self._ring_specs]
            held = ", ".join(f"{kinds.count(kind)} x {kind} = {n} bytes"
                             for kind, n in by_kind.items())
            raise ServingError(
                f"KV cache needs {kv_bytes} bytes ({S} slots x {len(spec)} "
                f"layers: {held}) > MXNET_KV_BUDGET_BYTES={budget} — "
                "shrink MXNET_KV_SLOTS / MXNET_KV_MAX_LEN or raise the "
                "budget")
        self._cache_flat, self._last_tok = self._zero_rings(), \
            self._zero_last()
        self.kv_cache_bytes = kv_bytes
        self.kv_cache_bytes_by_kind = by_kind
        self._metrics.set_gauge("kv_cache_bytes", kv_bytes)
        # what the model's decode step counts on the device, read back
        # with the step's tokens: (name, help) each
        counters = tuple(getattr(model, "step_counters", ()))
        self._step_counters = tuple(name for name, _help in counters)
        self._metrics.declare(counters, [
            (f"kv_cache_bytes_{kind}", f"bytes of the {kind} rings")
            for kind in by_kind])
        for kind, nbytes in by_kind.items():
            self._metrics.set_gauge(f"kv_cache_bytes_{kind}", nbytes)
        self._metrics.set_gauge("free_kv_slots", S)

        # -- scheduler state (single loop thread owns all of it) --
        self._positions = onp.zeros(S, dtype=onp.int32)
        self._by_slot: list = [None] * S            # slot -> _GenRequest
        self._free = list(range(S - 1, -1, -1))     # pop() -> lowest slot
        self._unread: list = []     # dispatched and not read, oldest first
        # what this step's streams have for their sinks, a list a sink
        self._outbox: dict = {}
        self._step_id = None        # the loop step open now (telemetry on)
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(max_queue)))
        self._closed = False
        self._aborted = False
        # param-swap serializer: the PROCESS-WIDE trace lock, not a private
        # one — the loop thread traces against the same Parameter objects a
        # caller-thread full forward swaps (gluon.block.PARAM_TRACE_LOCK)
        from ..gluon.block import PARAM_TRACE_LOCK
        self._trace_lock = PARAM_TRACE_LOCK
        self._prefill_progs: dict = {}              # bucket -> (prog, label)
        self._decode_prog = None                    # (prog, label)
        self._probe_progs: dict = {}    # bucket, None: decode -> the same
        if precompile:
            self.precompile()
        self._thread = threading.Thread(target=self._loop,
                                        name="generate-engine", daemon=True)
        self._thread.start()

    # -- introspection -----------------------------------------------------
    @property
    def metrics(self):
        return self._metrics

    @property
    def slots(self):
        return self._slots

    @property
    def max_len(self):
        return self._max_len

    @property
    def prefill_buckets(self):
        return self._prefill_buckets

    def program_labels(self):
        """Compiled-program labels by role — the ProgramCache correlation
        handles (``generate:prefill:L*`` vs ``generate:decode``): tests
        assert the two roles are DISTINCT cache entries and that churn
        never grows this dict."""
        out = {f"prefill:L{b}": lab
               for b, (_p, lab) in sorted(self._prefill_progs.items())}
        if self._decode_prog is not None:
            out["decode"] = self._decode_prog[1]
        return out

    def compile_passes_info(self):
        """Rewrite-pipeline surface (mirrors
        ``InferenceEngine.compile_passes_info``): which passes built the
        prefill programs, their cache-key fingerprint, and the per-label
        pass reports."""
        if self._pipeline is None:
            return {"spec": "", "fingerprint": None, "programs": {}}
        return {
            "spec": self._pipeline.spec,
            "fingerprint": self._pipeline.fingerprint(),
            "programs": {
                lab: [dict(r) for r in reps]
                for lab, reps in sorted(self._passes_reports.items())},
        }

    def _zero_rings(self):
        import jax.numpy as jnp
        from .. import memory as _memory
        rings = []
        for _kind, shape, dtype in self._ring_specs:
            buf = jnp.zeros(shape, dtype)
            if _memory._census_active:
                _memory.tag(buf, "kv_cache")
            rings.append(buf)
        return rings

    def _zero_last(self):
        """Every slot's last token, on the device: what the decode program
        reads its inputs from and a prefill writes its first token into."""
        import jax.numpy as jnp
        return jnp.zeros((self._slots,), jnp.int32)

    def _ring_sds(self):
        import jax
        return [jax.ShapeDtypeStruct(shape, dtype)
                for _kind, shape, dtype in self._ring_specs]

    def _by_layer(self, flat):
        """The flat list of rings as the model takes them: a tuple a
        layer."""
        out, i = [], 0
        for n in self._rings_per_layer:
            out.append(tuple(flat[i:i + n]))
            i += n
        return out

    def _bucket_for(self, n):
        for b in self._prefill_buckets:
            if b >= n:
                return b
        raise ServingError(
            f"prompt length {n} exceeds the top prefill bucket "
            f"{self._prefill_buckets[-1]} (max_len={self._max_len})")

    # -- pure functions (params + caches ride as jit arguments) ------------
    def _prefill_pure(self, bucket, probe=False):
        import jax
        import jax.numpy as jnp
        from ..gluon.block import _run_with_params
        from ..ndarray.ndarray import NDArray, unwrap
        from .. import autograd
        from .. import random as _random
        key = jax.random.PRNGKey(0)
        model, ps = self._model, self._ps
        kw = self._probe_kw(probe)

        def pure(raws, tok, vl, slot, last, *cache_flat):
            def call():
                with autograd._Scope(recording=False, training=False), \
                        _random.key_scope(key):
                    return model.prefill(NDArray(tok), NDArray(vl), **kw)

            res, _aux = _run_with_params(ps, raws, call)
            with _telemetry.part("head"):
                # (1, Lb, V), or (1, 1, V): the row at vl - 1 alone, from
                # a model whose vocabulary times the bucket is gigabytes
                lraw = unwrap(res[0])
                row = lraw[0, 0] if lraw.shape[1] == 1 \
                    else jnp.take(lraw[0], vl[0] - 1, axis=0)
                first = jnp.argmax(row).astype(jnp.int32)
                # the host reads `first`; the slot's next decode step
                # reads it from the vector, without waiting for the host
                out = [first, jax.lax.dynamic_update_slice(
                    last, first[None], (slot,))]
            rows = [unwrap(r) for layer in res[1] for r in layer]
            for (kind, _s, _d), ring, new in zip(self._ring_specs,
                                                 cache_flat, rows):
                # what the model keeps, written whole at the slot.  Of a
                # ring indexed by position the padded rows beyond vl are
                # dead: decode overwrites index j at position j before
                # the mask reaches it.  A state has no dead part: the
                # model owes it as of vl, not of the bucket's end.  In a
                # device trace the write is the part's whose name the
                # ring's kind is (conv, indexer), else attention's
                with _telemetry.part(kind if kind in _telemetry.PARTS
                                     else "attention"), \
                        _telemetry.part("ring_write"):
                    out.append(jax.lax.dynamic_update_slice(
                        ring, new.astype(ring.dtype),
                        (slot,) + (0,) * (ring.ndim - 1)))
            if probe:
                out.append({"logits": row, **(res[2] if kw else {})})
            return tuple(out)

        # the jitted module's name in a device trace: jit_pure_prefill_L32
        pure.__name__ = pure.__qualname__ = \
            f"{'probe' if probe else 'pure'}_prefill_L{bucket}"
        return pure

    def _probe_kw(self, probe):
        """A probed program asks a model that says it ``probes`` for what
        it chose; of any other it shows the logits alone."""
        return {"probe": True} if probe and getattr(
            self._model, "probes", False) else {}

    def _decode_pure(self, probe=False):
        import jax
        import jax.numpy as jnp
        from ..gluon.block import _run_with_params
        from ..ndarray.ndarray import NDArray, unwrap
        from .. import autograd
        from .. import random as _random
        key = jax.random.PRNGKey(0)
        model, ps = self._model, self._ps
        kw = self._probe_kw(probe)

        def pure_decode(raws, pos, act, last, *cache_flat):
            # a slot that does not ride reads as token 0, whatever it held
            with _telemetry.part("embed"):
                tok = jnp.where(act > 0, last, 0)
            caches = [tuple(NDArray(r) for r in layer)
                      for layer in self._by_layer(cache_flat)]

            def call():
                with autograd._Scope(recording=False, training=False), \
                        _random.key_scope(key):
                    return model.decode_step(NDArray(tok), caches,
                                             NDArray(pos),
                                             active=NDArray(act), **kw)

            res, _aux = _run_with_params(ps, raws, call)
            with _telemetry.part("head"):
                nxt = jnp.argmax(unwrap(res[0]), axis=-1).astype(jnp.int32)
                # the riders' new tokens stay on the device for the next
                # step
                keep = jnp.where(act > 0, nxt, last)
                if self._step_counters:
                    # one array back to the host: the tokens, then the
                    # counts
                    nxt = jnp.concatenate(
                        [nxt, unwrap(res[2]).astype(jnp.int32)])
            out = (nxt, keep) + tuple(unwrap(r) for layer in res[1]
                                      for r in layer)
            if probe:
                out += ({"logits": unwrap(res[0]),
                         **(res[-1] if kw else {})},)
            return out

        if probe:
            pure_decode.__name__ = pure_decode.__qualname__ = "probe_decode"
        return pure_decode

    def _read_params(self):
        # live read per dispatch (load_parameters hot-swap = jit cache hit)
        with self._trace_lock:
            return [p._nd._data for p in self._ps]

    # -- compilation -------------------------------------------------------
    def _lower(self, fn, sds):
        """Lower a serving program with what it carries from step to step
        donated: its last arguments, the slots' last tokens and the rings.
        The outputs are then the same buffers, so the program writes only
        the rows it changes.  Never the weights: they live on across steps
        and are shared with ``load_parameters``."""
        import jax
        # donation-recovery: tests/test_generate.py::test_failure_that_consumes_the_rings_fails_riders_and_rebuilds
        end = 1 + len(sds)
        carried = tuple(range(end - 1 - len(self._ring_specs), end))
        with self._trace_lock:
            return jax.jit(fn, donate_argnums=carried).lower(
                self._read_params(), *sds)

    def _input_sds(self, bucket=None):
        """The arguments after the weights, of a prefill bucket's program
        or (None) of the decode program: the host's inputs (positions and
        the riders' gate; the padded prompt, its length and the slot), then
        what the program carries: the slots' last tokens and the rings."""
        import jax
        S = self._slots
        shapes = [((S,), onp.int32), ((S,), onp.float32)] \
            if bucket is None else \
            [((1, bucket), onp.int32), ((1,), onp.int32), ((), onp.int32)]
        shapes.append(((S,), onp.int32))
        return [jax.ShapeDtypeStruct(*s) for s in shapes] + self._ring_sds()

    def _compile_probe(self, bucket=None):
        """The probed twin of a prefill bucket's program or (None) of the
        decode program: the same function on the same rings, donated the
        same, with one more output (see :meth:`submit`).  Compiled on the
        loop thread when the first probed request needs it."""
        entry = self._probe_progs.get(bucket)
        if entry is None:
            from .. import compile as _compile
            fn = self._decode_pure(True) if bucket is None \
                else self._prefill_pure(bucket, True)
            label = "generate:probe:" + (
                "decode" if bucket is None else f"prefill:L{bucket}")
            compiled, _info = _compile.aot_compile_lowered(
                self._lower(fn, self._input_sds(bucket)),
                cache=self._cache_label, label=label)
            entry = self._probe_progs[bucket] = (compiled, label)
        return entry

    def _compile_prefill(self, bucket):
        entry = self._prefill_progs.get(bucket)
        if entry is not None:
            return entry
        from .. import compile as _compile
        sds = self._input_sds(bucket)
        fn, extra = self._prefill_pure(bucket), None
        if self._pipeline is not None:
            from ..compile import passes as _passes
            label = f"passes:generate:prefill:L{bucket}"
            with self._trace_lock:
                raws = self._read_params()
                prog = _passes.CapturedProgram.capture(
                    fn, (raws, *sds), label=label)
            rewritten, reports = self._pipeline.run(
                prog, example_args=(raws, *sds), label=label)
            self._passes_reports[label] = reports
            fn = rewritten.as_callable()
            # brand the cache key even when every rewrite was discarded:
            # a pipeline-on engine must never alias the pipeline-off twin
            extra = self._pipeline.fingerprint()
        compiled, info = _compile.aot_compile_lowered(
            self._lower(fn, sds), cache=self._cache_label,
            label=f"generate:prefill:L{bucket}", extra_key=extra)
        self._metrics.inc("prefill_cache_hits" if info["cache_hit"]
                          else "prefill_compiles")
        entry = (compiled, f"generate:prefill:L{bucket}")
        self._prefill_progs[bucket] = entry
        return entry

    def _compile_decode(self):
        if self._decode_prog is not None:
            return self._decode_prog
        from .. import compile as _compile
        compiled, info = _compile.aot_compile_lowered(
            self._lower(self._decode_pure(), self._input_sds()),
            cache=self._cache_label, label="generate:decode")
        self._metrics.inc("decode_cache_hits" if info["cache_hit"]
                          else "decode_compiles")
        self._decode_prog = (compiled, "generate:decode")
        return self._decode_prog

    def precompile(self, buckets=None):
        """Warm the decode program and the given (default: all) prefill
        buckets before the first request pays an XLA compile."""
        for b in (tuple(buckets) if buckets else self._prefill_buckets):
            if b not in self._prefill_buckets:
                raise ServingError(f"precompile bucket {b} not in ladder "
                                   f"{self._prefill_buckets}")
            self._compile_prefill(b)
        self._compile_decode()

    # -- submission --------------------------------------------------------
    def submit(self, tokens, max_new_tokens=32, eos_id=None, trace=None,
               probe=False, sink=None):
        """Queue one prompt; returns a :class:`GenerationStream`
        immediately.  ``max_new_tokens`` counts every emitted token
        (including the prefill's first and any EOS).

        ``sink``: who puts this stream's tokens somewhere outside the
        process, a socket say, and wants them a step at a time and not one
        by one: anything with ``take(batch)``.  The loop calls it once a
        step with a list of ``(stream, token, emit stamp)`` over every
        stream of that sink, in the order emitted, the stamp in
        ``time.perf_counter_ns()``; a stream's end is one more entry with
        the token None, after which :meth:`GenerationStream.result` does not
        wait.  ``take`` must neither block nor raise: it runs on the loop
        thread (and, for a request that :meth:`stop` fails in the queue, on
        the stopping one).  Without a sink the stream keeps its queue for
        :meth:`GenerationStream.tokens`.

        ``probe``: show what the serving programs computed for this
        request, to hold a deployment against a reference.  Its prefill
        and every decode step it rides in run the probed twins of the
        programs (:meth:`_compile_probe`), in its slot of the live rings
        beside whatever else is in flight, and its result carries
        ``"probe"``: for each emitted token a dict of host arrays, the
        float32 ``"logits"`` the token is the largest of and, from a
        model that ``probes``, what its ``prefill`` / ``decode_step``
        return with ``probe=True`` (of a decode step this request's row).
        A probed step is slower: keep it out of what is timed."""
        if self._closed:
            raise EngineClosedError("GenerationEngine is stopped")
        prompt = onp.asarray(tokens, dtype=onp.int32).reshape(-1)
        if prompt.size == 0:
            raise ServingError("empty prompt")
        self._bucket_for(prompt.size)      # reject oversized prompts NOW
        stream = GenerationStream(
            trace if trace is not None else _telemetry.new_trace(), sink)
        req = _GenRequest(prompt, max(1, int(max_new_tokens)),
                          None if eos_id is None else int(eos_id), stream,
                          probe)
        try:
            self._q.put_nowait(req)
        except queue.Full:
            self._metrics.inc("rejected_queue_full")
            raise QueueFullError(
                f"generation queue at capacity ({self._q.maxsize})")
        self._metrics.inc("requests")
        self._metrics.set_gauge("queue_depth", self._q.qsize())
        if req.trace:
            _telemetry.inflight_add(req.trace.trace_id)
        return req.stream

    def generate(self, tokens, max_new_tokens=32, eos_id=None, trace=None,
                 timeout=None):
        """Synchronous convenience: submit and block for the result."""
        return self.submit(tokens, max_new_tokens, eos_id,
                           trace=trace).result(timeout)

    # -- engine loop (single thread owns slots/positions/caches) -----------
    def _loop(self):
        while True:
            if self._aborted:
                self._fail_riders(EngineClosedError("engine aborted"))
                self._hand_over()
                return
            first = None
            if not self._unread and len(self._free) == self._slots \
                    and self._q.empty():
                # nothing in flight, nothing queued: wait outside any step
                if self._closed:
                    return
                try:
                    first = self._q.get(timeout=0.05)
                except queue.Empty:
                    continue
            # one iteration with work = one step; the memory sampler runs
            # when its envelope closes, never once a phase
            with _telemetry.step_span("generate", sample_phases=False) as env:
                self._step_id = getattr(env, "step_id", None)
                if first is not None:
                    self._metrics.set_gauge("queue_depth", self._q.qsize())
                    self._admit(first)
                self._admit_ready()
                self._decode_once()
                # what a failure left for the sinks, here or at an admission
                self._hand_over()

    def _admit_ready(self):
        while self._free:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            self._metrics.set_gauge("queue_depth", self._q.qsize())
            self._admit(req)

    def _dispatch(self, prog, raws, inputs, what):
        """Run one compiled program on what the engine carries from step
        to step, the slots' last tokens and the rings, and make what it
        returns in their place the engine's at once: the next program is
        dispatched on them before this one's tokens are read.  Returns the
        output the host reads (the tokens, unread) and a probed program's
        further output, or None.

        The program consumes what it is given (donated, see
        :meth:`_lower`).  A transient failure is retried in place only
        while all of it is alive, as it is after the injected fault, which
        fires before the call.  Whatever else fails, here or at a read,
        goes to :meth:`_rings_lost`."""
        from .. import faults as _faults
        attempt = 0
        carried = [self._last_tok, *self._cache_flat]
        while True:
            try:
                if what == "decode":
                    # THE chaos lever for generative serving: a plan entry
                    # `generate.decode@N:...` fails / delays / kills this
                    # replica mid-generation (docs/RESILIENCE.md)
                    _faults.point("generate.decode")
                out = prog(raws, *inputs, *carried)
            except (_faults.TransientFault, ConnectionResetError,
                    TimeoutError):
                if attempt >= self._decode_retries \
                        or any(a.is_deleted() for a in carried):
                    raise
                attempt += 1
                self._metrics.inc("dispatch_retries")
                continue
            if all(a.is_deleted() for a in carried):
                self._metrics.inc("kv_inplace_dispatches")
            end = 1 + len(carried)
            self._last_tok, self._cache_flat = out[1], list(out[2:end])
            return out[0], (out[end] if len(out) > end else None)

    def _rings_lost(self, at_read=False):
        """After a failed dispatch or read: are the rings gone?  A failure
        at a dispatch while everything carried is alive came before a
        program took it, and nothing is lost.  A program that died after
        taking its arguments, or one that failed on the device and was
        found at the read of its tokens (what the engine holds by then is
        its output, or the output of the step dispatched after it), leaves
        no row to trust: the engine serves on from fresh zero rings, and
        the caller fails every request that holds a slot."""
        carried = [self._last_tok, *self._cache_flat]
        if at_read:
            for a in carried:       # freed before the new ones are made
                if not a.is_deleted():
                    a.delete()
        elif not any(a.is_deleted() for a in carried):
            return False
        del carried
        self._cache_flat, self._last_tok = self._zero_rings(), \
            self._zero_last()
        self._metrics.inc("kv_ring_rebuilds")
        return True

    def _fail_riders(self, exc):
        """Every request that holds a slot fails, and what was dispatched
        for them and not read is dropped with them."""
        self._unread.clear()
        for r in self._by_slot:
            if r is not None:
                self._release(r)
                self._fail(r, exc)

    def _post(self, stream, token, stamp):
        """A stream's token (or None, its end) into the batch its sink
        takes when the step has been read: the loop thread's own list."""
        batch = self._outbox.get(stream._sink)
        if batch is None:
            batch = self._outbox[stream._sink] = []
        batch.append((stream, token, stamp))

    def _hand_over(self):
        """Every sink takes its batch of this step: one call, one wake."""
        if self._outbox:
            batches, self._outbox = self._outbox, {}
            for sink, batch in batches.items():
                sink.take(batch)

    def _admit(self, req):
        req.stream._post = self._post
        slot = self._free.pop()
        wait_us = (time.perf_counter() - req.t_submit) * 1e6
        self._metrics.add(slot_allocs=1, prefills=1,
                          queue_wait_us=int(wait_us))
        P = int(req.prompt.size)
        bucket = self._bucket_for(P)
        if req.trace:
            req.trace.add_span("generate_queue",
                               _telemetry._wall_us() - int(wait_us), wait_us,
                               slot=slot)
        with _telemetry.phase("admit", bucket=bucket, slot=slot,
                              prompt_len=P):
            self._admit_into(req, slot, P, bucket)

    def _admit_into(self, req, slot, P, bucket):
        """Dispatch the prefill and go on: the device orders it after the
        step in flight (it takes that step's rings), the slot's first token
        lands in the vector the next decode step reads, and the host reads
        it at the next readback (:meth:`_emit_first`)."""
        tok = onp.zeros((1, bucket), dtype=onp.int32)
        tok[0, :P] = req.prompt
        vl = onp.asarray([P], dtype=onp.int32)
        try:
            prog, label = self._compile_prefill(bucket) \
                if req.probe is None else self._compile_probe(bucket)
            # live read per dispatch (a hot-swap is a jit cache hit)
            first, probe = self._dispatch(
                prog, self._read_params(),
                (tok, vl, onp.int32(slot)), "prefill")
        except Exception as e:      # noqa: BLE001 — fail one request only,
            # unless the rings went with it
            self._free.append(slot)
            self._metrics.inc("slot_frees")
            if self._rings_lost():
                self._fail_riders(e)
            self._fail(req, e)
            return
        req.slot = slot
        req.sent = 1
        self._positions[slot] = P
        self._by_slot[slot] = req
        self._metrics.set_gauge("free_kv_slots", len(self._free))
        self._metrics.set_gauge("active_streams",
                                self._slots - len(self._free))
        self._unread.append(_Unread(
            first, probe, self._step_id, req=req,
            span=(_telemetry._wall_us(), dict(
                bucket=bucket, program=label, slot=slot, prompt_len=P))
            if req.trace else None))

    def _emit_first(self, unread, first, seen):
        """A prefill's token, read: the request's first."""
        req = unread.req
        req.t_first = time.perf_counter()
        req.generated.append(first)
        if seen is not None:
            req.probe.append(seen)
        if unread.span is not None:
            # dispatch to the token read: queued behind the step in flight,
            # run, and the wait for the loop's next readback
            us0, attrs = unread.span
            req.trace.add_span("generate_prefill", us0,
                               _telemetry._wall_us() - us0, **attrs)
        self._metrics.observe_ttft((req.t_first - req.t_submit) * 1000.0)
        req.stream._emit(first)
        self._finish_if_done(req, first)

    def _finish_if_done(self, req, token):
        if req.eos_id is not None and token == req.eos_id:
            self._complete(req, "eos")
        elif len(req.generated) >= req.max_new:
            self._complete(req, "length")

    def _decode_once(self):
        """One turn of the pipeline.  Dispatch the next decode step for
        every request that still has a token to come, then read, emit and
        complete what was dispatched before it, which the device finished
        or is finishing while the new step waits its turn there.  With no
        step to dispatch, everything unread is drained, so that no token
        waits for an arrival.

        A request at ``max_new`` is known without its last token and rides
        no further.  One that ends on ``eos_id`` is found when that token
        is read, a step late: the row it wrote meanwhile is dead (a prefill
        writes a slot from row 0) and its token is thrown away, counted in
        ``slot_steps_discarded``."""
        import jax
        riders = [r for r in self._by_slot
                  if r is not None and r.sent < r.max_new]
        older = list(self._unread)
        if not riders and not older:
            return          # an admission that failed, alone in its step
        # the decode step before, if its tokens are still unread
        before = next((u for u in older if u.riders is not None), None)
        reading = False
        t0, offcpu_ns = time.perf_counter_ns(), 0
        try:
            if riders:
                t0, offcpu_ns = self._dispatch_step(riders)
            reading = True
            with _telemetry.phase(
                    "readback", of_step=before and before.step_id):
                # waits for the device, which has the new step queued
                read = [(onp.asarray(u.tokens),
                         jax.tree_util.tree_map(onp.asarray, u.probe))
                        for u in older]
        except Exception as e:      # noqa: BLE001
            # a non-transient failure has no healthy path forward for the
            # riders, with or without their rings, and the step still in
            # flight goes with it: fail them honestly, keep serving
            older = None    # e's traceback keeps this frame, not the arrays
            self._rings_lost(at_read=reading)
            self._fail_riders(e)
            return
        del self._unread[:len(older)]
        t2, c2 = time.perf_counter_ns(), time.thread_time_ns()
        with _telemetry.phase("emit") as span:
            firsts, emitted, discarded = self._emit_read(older, read)
            span.set(riders=firsts + emitted)
        t3 = time.perf_counter_ns()
        if riders:
            offcpu_ns += (t3 - t2) - (time.thread_time_ns() - c2)
            self._metrics.record_decode_step(
                len(riders), emitted, (t3 - t0) / 1e6,
                max(0, offcpu_ns) // 1000, before is not None)
        elif emitted:
            self._metrics.inc("tokens_generated", emitted)
        if discarded:
            self._metrics.inc("slot_steps_discarded", discarded)
        with _telemetry.phase("release"):
            # what was read goes back here and not at this function's
            # return, so that the time freeing it takes has a name
            # (docs/OBSERVABILITY.md, `release`)
            del older, read, before

    def _dispatch_step(self, riders):
        """Stage and dispatch one decode step for ``riders`` and advance
        them: positions move without looking at a token.  Returns the
        start of ``stage`` and the ns of it the loop thread was off the
        CPU."""
        # lazy on the first step (ModelServer does not precompile): a
        # failed compile fails the riders like a failed dispatch
        probed = any(r.probe is not None for r in riders)
        prog, _label = self._compile_probe() if probed \
            else self._compile_decode()
        # loop_offcpu_us: wall less this thread's CPU time over the two
        # phases that never wait for the device (four thread-clock reads)
        t0, c0 = time.perf_counter_ns(), time.thread_time_ns()
        with _telemetry.phase("stage"):
            act = onp.zeros(self._slots, dtype=onp.float32)
            now = time.perf_counter()
            for r in riders:
                act[r.slot] = 1.0
                if r.t_decode0 is None:
                    r.t_decode0 = now
            pos = self._positions.copy()
            raws = self._read_params()
        offcpu_ns = (time.perf_counter_ns() - t0) - (time.thread_time_ns()
                                                     - c0)
        with _telemetry.phase("dispatch"):
            tokens, probe = self._dispatch(prog, raws, (pos, act), "decode")
        self._unread.append(_Unread(tokens, probe, self._step_id,
                                    riders=[(r, r.slot) for r in riders]))
        for r in riders:
            r.sent += 1
            self._positions[r.slot] += 1
            if not r.wrapped and int(self._positions[r.slot]) >= \
                    self._max_len:
                r.wrapped = True
                self._metrics.inc("cache_wraps")
        return t0, offcpu_ns

    def _emit_read(self, older, read):
        """Hand what was read to its streams, oldest first: a prefill's
        first token, a decode step's token a rider; then the step's batch
        to each sink.  Returns the counts of first tokens, of decode tokens
        emitted and of decode tokens thrown away (their request had ended
        on ``eos_id`` a step before)."""
        import jax
        S = self._slots
        firsts = emitted = discarded = 0
        for unread, (toks, seen) in zip(older, read):
            if unread.riders is None:
                self._emit_first(unread, int(toks), seen)
                firsts += 1
                continue
            if self._step_counters:
                self._metrics.add(**dict(zip(
                    self._step_counters, (int(n) for n in toks[S:]))))
            for r, slot in unread.riders:
                if r.slot != slot:
                    discarded += 1
                    continue
                t = int(toks[slot])
                r.steps += 1
                r.generated.append(t)
                if r.probe is not None:
                    r.probe.append(jax.tree_util.tree_map(
                        lambda a, slot=slot: a[slot], seen))
                r.stream._emit(t)
                self._finish_if_done(r, t)
                emitted += 1
        self._hand_over()
        return firsts, emitted, discarded

    # -- completion --------------------------------------------------------
    def _release(self, req):
        if req.slot is not None:
            self._by_slot[req.slot] = None
            self._positions[req.slot] = 0
            self._free.append(req.slot)
            req.slot = None
            self._metrics.inc("slot_frees")
            self._metrics.set_gauge("free_kv_slots", len(self._free))
            self._metrics.set_gauge("active_streams",
                                    self._slots - len(self._free))

    def _complete(self, req, reason):
        self._release(req)
        now = time.perf_counter()
        wall_s = now - req.t_submit
        ttft_ms = (req.t_first - req.t_submit) * 1000.0
        tokens_per_s = len(req.generated) / max(wall_s, 1e-9)
        if req.trace:
            if req.t_decode0 is not None:
                # ONE aggregate span for the decode hops (a span per
                # token would drown the waterfall): steps tells the story
                us0 = _telemetry._wall_us() - int((now - req.t_decode0)
                                                  * 1e6)
                req.trace.add_span("generate_decode", us0,
                                   (now - req.t_decode0) * 1e6,
                                   steps=req.steps,
                                   program="generate:decode")
            req.trace.add_span(
                "generate", _telemetry._wall_us() - int(wall_s * 1e6),
                wall_s * 1e6, tokens=len(req.generated),
                ttft_ms=round(ttft_ms, 3),
                tokens_per_s=round(tokens_per_s, 3), finish=reason)
            _telemetry.inflight_remove(req.trace.trace_id)
            _telemetry.maybe_spool(req.trace, wall_s * 1000.0, "generate")
        self._metrics.inc("completed")
        result = {
            "tokens": [int(t) for t in req.generated],
            "finish_reason": reason,
            "ttft_ms": round(ttft_ms, 3),
            "tokens_per_s": round(tokens_per_s, 3),
        }
        if req.probe is not None:
            result["probe"] = req.probe
        req.stream._complete(result)

    def _fail(self, req, exc):
        self._metrics.inc("errors")
        if req.trace:
            req.trace.mark("error")
            _telemetry.inflight_remove(req.trace.trace_id)
        req.stream._fail(exc)

    # -- shutdown ----------------------------------------------------------
    def stop(self, timeout=30.0):
        """Stop admission and drain: queued and in-flight generations
        finish; anything still pending after ``timeout`` fails with
        :class:`EngineClosedError`."""
        self._closed = True
        self._thread.join(timeout)
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            self._fail(req, EngineClosedError("engine stopped"))

    close = stop

    def abort(self, timeout=30.0):
        """Stop without draining: what is in flight fails with
        :class:`EngineClosedError` at the next step's boundary, as does
        what is queued, and the rings are given back to the device.  For a
        host that must have the memory now (a long generation is minutes
        of decode steps)."""
        self._aborted = True
        self.stop(timeout)
        if not self._thread.is_alive():
            self._cache_flat, self._last_tok = [], None
            self._unread.clear()
