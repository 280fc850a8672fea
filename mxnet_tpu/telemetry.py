"""mxnet_tpu.telemetry — one metrics registry, step-phase tracing,
Prometheus/JSON exposition, and a flight recorder for crash reports.

The stack grew five disjoint observability surfaces (serving metrics,
engine flush hooks, io gauges, fault counters, ProgramCache stats); this
module is the single pane of glass over all of them:

* :class:`MetricsRegistry` — process-wide counters / gauges / histograms
  under a ``subsystem/name`` grammar.  Subsystems either own first-class
  metric objects (:func:`counter` / :func:`gauge` / :func:`histogram`) or
  register a **collector** — a zero-hot-path-cost callback read only at
  snapshot time (:func:`register_collector`; this is how the serving,
  engine, io, faults and compile surfaces plug in without adding a single
  lock acquisition to their hot paths).  :func:`snapshot` merges both into
  one dict; :func:`prometheus_text` renders the same set in Prometheus
  text exposition format (``subsystem/name`` -> ``mxnet_subsystem_name``).
* **Step-phase spans** — :func:`step_boundary` tags each training step
  with a monotonic id (never reused, so retries stay distinguishable) and
  :func:`phase` records named sub-spans (``data_wait``, ``forward``,
  ``backward``, ``optimizer_update``, ``step_flush``, ``compile``,
  ``checkpoint``, ``collective``, ...) against it.  Spans land in a
  bounded ring and, when the profiler is running, mirror into the
  chrome-trace dump (``phase/<name>`` events carrying the step id) —
  ``tools/trace_report.py`` folds either source into a per-step phase
  breakdown table.  The same call site also enters a
  ``jax.profiler.TraceAnnotation`` named ``mx:<kind>.<phase>``
  (``mx:<kind>.step`` for the envelope): inside a ``jax.profiler`` session
  the span lands in the host plane of the ``.xplane.pb`` beside the device
  ops, on the profiler's clock; outside one it is a check of one flag.
* **Flight recorder** — the span ring is capped
  (``MXNET_TELEMETRY_RING``) and :func:`flight_recorder_payload` groups
  its tail into a last-K-steps timeline: the ``telemetry`` section of
  ``faults.crash_report_payload``, so a crash report carries *where the
  time went*, not just latencies.
* **Exposition** — :func:`serve_metrics` starts a loopback HTTP server
  (``/metrics`` Prometheus text, ``/statusz`` JSON snapshot,
  ``/healthz``) for training jobs; the serving front-end exposes the same
  routes on its own port.

Always-on by design: with ``MXNET_TELEMETRY=0`` every span call is a
no-op context-manager constant (no clock read), and with it on the cost
is a few dict appends per *step* — never per op.  Grammar, metric tables,
span phases and the flight-recorder schema: docs/OBSERVABILITY.md; the
lint ``tools/check_metric_names.py`` keeps registrations and docs in
sync.
"""
from __future__ import annotations

import itertools
import json
import re
import threading
import time
from collections import deque

from .base import MXNetError

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "counter", "gauge", "histogram", "register_collector", "snapshot",
    "prometheus_text", "enabled", "enable", "phase", "step_boundary",
    "end_step", "step_span", "add_span", "flight_recorder",
    "flight_recorder_payload", "serve_metrics", "MetricsServer", "reset",
    "RequestTrace", "NULL_TRACE", "new_trace", "continue_trace",
    "tracing_enabled", "set_trace_sample", "request_scope", "request_span",
    "maybe_spool", "flush_trace_spool", "inflight_trace_ids",
    "format_request_waterfall", "set_memory_sampler",
    "part", "PARTS", "SUB_PARTS", "PARTS_VERSION",
]

_NAME_RE = re.compile(r"^[a-z0-9_]+/[a-z0-9_]+$")
_PROM_CHARS_RE = re.compile(r"[^a-zA-Z0-9_:]")
_METRIC_TYPES = ("counter", "gauge", "histogram")


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------
class Counter:
    """Monotonic count.  ``inc`` is one lock + one add."""

    __slots__ = ("name", "help", "_v", "_lock")

    def __init__(self, name, help=""):      # noqa: A002 — prom terminology
        self.name = name
        self.help = help
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self._v += n

    @property
    def value(self):
        return self._v

    def _zero(self):
        with self._lock:
            self._v = 0


class Gauge:
    """Last-written value."""

    __slots__ = ("name", "help", "_v", "_lock")

    def __init__(self, name, help=""):      # noqa: A002
        self.name = name
        self.help = help
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v):
        with self._lock:
            self._v = v

    @property
    def value(self):
        return self._v

    def _zero(self):
        with self._lock:
            self._v = 0.0


def _geom_bounds(lo=0.1, hi=120000.0, factor=2.0):
    bounds, b = [], lo
    while b < hi:
        bounds.append(b)
        b *= factor
    bounds.append(float("inf"))
    return bounds


class Histogram:
    """Log-bucketed histogram (geometric bounds, ms-oriented default).

    ``expo()`` returns the Prometheus-shaped snapshot: *cumulative* bucket
    counts keyed by upper bound, plus sum and count — the same structure
    collectors hand back for foreign histograms (e.g. the serving latency
    histograms), so the registry treats both identically.
    """

    __slots__ = ("name", "help", "_bounds", "_counts", "_sum", "_count",
                 "_lock")

    def __init__(self, name, help="", bounds=None):     # noqa: A002
        self.name = name
        self.help = help
        self._bounds = list(bounds) if bounds else _geom_bounds()
        self._counts = [0] * len(self._bounds)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v):
        import bisect
        i = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self._counts[min(i, len(self._counts) - 1)] += 1
            self._sum += v
            self._count += 1

    def expo(self):
        with self._lock:
            cum, out = 0, []
            for b, c in zip(self._bounds, self._counts):
                cum += c
                out.append([b, cum])
            return {"count": self._count, "sum": round(self._sum, 6),
                    "buckets": out}

    def _zero(self):
        with self._lock:
            self._counts = [0] * len(self._bounds)
            self._sum = 0.0
            self._count = 0


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------
class MetricsRegistry:
    """Process-wide metric namespace under the ``subsystem/name`` grammar.

    Two registration styles:

    * **owned metrics** — :meth:`counter` / :meth:`gauge` /
      :meth:`histogram` create (or return the existing) metric object;
      callers mutate it directly.
    * **collectors** — :meth:`register_collector` attaches a callback per
      subsystem, invoked only at snapshot time.  ``spec`` declares every
      metric the collector may emit (a *literal* dict at the call site —
      ``tools/check_metric_names.py`` lints the declarations against the
      grammar and docs/OBSERVABILITY.md).  Undeclared names a collector
      returns at runtime are surfaced as counters (the faults subsystem
      grows counter names dynamically) but cannot shadow declared ones.

    A name registered as one type can never be re-registered as another,
    and a collector-declared name can never also be owned.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict = {}        # name -> metric object
        self._collectors: dict = {}     # subsystem -> (fn, spec)

    # -- registration ------------------------------------------------------
    def _check_name(self, name):
        if not _NAME_RE.match(name):
            raise MXNetError(
                f"metric name {name!r} does not match the subsystem/name "
                "grammar (lowercase [a-z0-9_]+/[a-z0-9_]+ — "
                "docs/OBSERVABILITY.md)")

    def _make(self, name, cls, help, **kw):             # noqa: A002
        self._check_name(name)
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise MXNetError(
                        f"metric {name!r} already registered as "
                        f"{type(m).__name__}, not {cls.__name__}")
                return m
            for sub, (_fn, spec) in self._collectors.items():
                if name in spec:
                    raise MXNetError(
                        f"metric {name!r} is already declared by the "
                        f"{sub!r} collector")
            m = self._metrics[name] = cls(name, help, **kw)
            return m

    def counter(self, name, help=""):                   # noqa: A002
        return self._make(name, Counter, help)

    def gauge(self, name, help=""):                     # noqa: A002
        return self._make(name, Gauge, help)

    def histogram(self, name, help="", bounds=None):    # noqa: A002
        return self._make(name, Histogram, help, bounds=bounds)

    def register_collector(self, subsystem, fn, spec):
        """Attach ``fn`` (no args -> ``{name: value}``) for ``subsystem``.

        ``spec`` maps each declared metric name to ``(type, help)`` with
        type one of counter/gauge/histogram.  Histogram values must be
        :meth:`Histogram.expo`-shaped dicts.  Re-registering a subsystem
        replaces its previous collector (module reloads in tests)."""
        with self._lock:
            for name, decl in spec.items():
                if not _NAME_RE.match(name):
                    raise MXNetError(
                        f"collector metric {name!r} violates the "
                        "subsystem/name grammar")
                if not name.startswith(subsystem + "/"):
                    raise MXNetError(
                        f"collector metric {name!r} does not live under "
                        f"its subsystem {subsystem!r}")
                typ = decl[0] if isinstance(decl, (tuple, list)) else decl
                if typ not in _METRIC_TYPES:
                    raise MXNetError(
                        f"collector metric {name!r} has unknown type "
                        f"{typ!r} (one of {_METRIC_TYPES})")
                if name in self._metrics:
                    raise MXNetError(
                        f"collector metric {name!r} is already an owned "
                        "metric")
                for sub, (_fn, other) in self._collectors.items():
                    if sub != subsystem and name in other:
                        raise MXNetError(
                            f"metric {name!r} declared by two collectors "
                            f"({sub!r} and {subsystem!r})")
            self._collectors[subsystem] = (fn, dict(spec))

    def extend_collector(self, subsystem, spec):
        """Declare further metrics of a registered collector: names known
        only at run time (what a served model counts).  Under the rules
        of :meth:`register_collector`; a name declared before keeps its
        declaration."""
        with self._lock:
            fn, old = self._collectors[subsystem]
        self.register_collector(subsystem, fn, {**spec, **old})

    # -- snapshot ----------------------------------------------------------
    @staticmethod
    def _decl_type(decl):
        return decl[0] if isinstance(decl, (tuple, list)) else decl

    def snapshot(self):
        """One dict over every registered surface:
        ``{"counters": {...}, "gauges": {...}, "histograms": {...}}``.
        Collector failures are isolated — a broken subsystem drops out of
        the snapshot, it never breaks it."""
        out = {"counters": {}, "gauges": {}, "histograms": {},
               "ts": time.time()}
        with self._lock:
            owned = list(self._metrics.values())
            collectors = list(self._collectors.items())
        for m in owned:
            if isinstance(m, Counter):
                out["counters"][m.name] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][m.name] = m.value
            else:
                out["histograms"][m.name] = m.expo()
        for _sub, (fn, spec) in collectors:
            try:
                vals = fn()
            except Exception:   # noqa: BLE001 — snapshot must never fail
                vals = {}
            vals = dict(vals or {})
            # declared-but-unreturned metrics surface at zero: a subsystem
            # that has seen no traffic still shows up in every snapshot
            # (the completeness contract the registry exists for)
            for name in spec:
                if name not in vals:
                    # the zero histogram still carries the mandatory +Inf
                    # bucket — exposition of a bucketless histogram fails
                    # strict Prometheus parsers
                    vals[name] = {"count": 0, "sum": 0.0,
                                  "buckets": [[float("inf"), 0]]} \
                        if self._decl_type(spec[name]) == "histogram" else 0
            for name, val in vals.items():
                typ = self._decl_type(spec.get(name, "counter"))
                if typ == "histogram":
                    out["histograms"][name] = val
                elif typ == "gauge":
                    out["gauges"][name] = float(val)
                else:
                    out["counters"][name] = int(val)
        return out

    # -- prometheus exposition --------------------------------------------
    @staticmethod
    def _prom_name(name):
        # collector-surfaced dynamic names (e.g. a user's
        # ``faults.inc("trainer.step_retries")``) may carry characters
        # outside the Prometheus name charset; a single bad name must not
        # abort the whole scrape (Prometheus rejects the entire text body
        # on one malformed line), so sanitize here rather than trusting
        # the registration-time grammar check to have seen every name
        return "mxnet_" + _PROM_CHARS_RE.sub("_", name.replace("/", "_"))

    @staticmethod
    def _fmt(v):
        if isinstance(v, bool):
            return "1" if v else "0"
        if isinstance(v, int):
            return str(v)
        f = float(v)
        if f != f:
            return "NaN"
        if f in (float("inf"), float("-inf")):
            return "+Inf" if f > 0 else "-Inf"
        return repr(f)

    def _help_for(self, name):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None and m.help:
                return m.help
            for _sub, (_fn, spec) in self._collectors.items():
                decl = spec.get(name)
                if isinstance(decl, (tuple, list)) and len(decl) > 1 \
                        and decl[1]:
                    return decl[1]
        return None

    def prometheus_text(self, snap=None):
        """The snapshot in Prometheus text exposition format 0.0.4."""
        snap = snap if snap is not None else self.snapshot()
        lines = []

        def head(name, typ):
            h = self._help_for(name)
            if h:
                lines.append(f"# HELP {self._prom_name(name)} "
                             + h.replace("\\", "\\\\").replace("\n", " "))
            lines.append(f"# TYPE {self._prom_name(name)} {typ}")

        for name in sorted(snap["counters"]):
            head(name, "counter")
            lines.append(f"{self._prom_name(name)} "
                         f"{self._fmt(snap['counters'][name])}")
        for name in sorted(snap["gauges"]):
            head(name, "gauge")
            lines.append(f"{self._prom_name(name)} "
                         f"{self._fmt(snap['gauges'][name])}")
        for name in sorted(snap["histograms"]):
            h = snap["histograms"][name]
            head(name, "histogram")
            pn = self._prom_name(name)
            for le, cum in h.get("buckets", []):
                lines.append(f'{pn}_bucket{{le="{self._fmt(float(le))}"}} '
                             f"{int(cum)}")
            lines.append(f"{pn}_sum {self._fmt(float(h.get('sum', 0.0)))}")
            lines.append(f"{pn}_count {int(h.get('count', 0))}")
        return "\n".join(lines) + "\n"

    def _reset(self):
        with self._lock:
            for m in self._metrics.values():
                m._zero()


_registry = MetricsRegistry()


def registry():
    """The process-wide default :class:`MetricsRegistry`."""
    return _registry


def counter(name, help=""):             # noqa: A002
    return _registry.counter(name, help)


def gauge(name, help=""):               # noqa: A002
    return _registry.gauge(name, help)


def histogram(name, help="", bounds=None):      # noqa: A002
    return _registry.histogram(name, help, bounds=bounds)


def register_collector(subsystem, fn, spec):
    return _registry.register_collector(subsystem, fn, spec)


def extend_collector(subsystem, spec):
    return _registry.extend_collector(subsystem, spec)


def snapshot():
    """One call, every subsystem: the merged counters/gauges/histograms
    snapshot of the default registry."""
    return _registry.snapshot()


def prometheus_text():
    """``/metrics`` body: the default registry in Prometheus text
    exposition format."""
    return _registry.prometheus_text()


# ---------------------------------------------------------------------------
# on/off switch
# ---------------------------------------------------------------------------
_enabled = [None]       # None = read MXNET_TELEMETRY on first use


def enabled():
    """Span recording on?  (``MXNET_TELEMETRY``, default on; the metrics
    registry itself is not gated — only span/ring recording is.)"""
    v = _enabled[0]
    if v is None:
        from .util import getenv
        v = _enabled[0] = bool(getenv("MXNET_TELEMETRY"))
    return v


def enable(flag=True):
    """Override the env switch for this process (``enable(None)`` re-reads
    ``MXNET_TELEMETRY`` on next use)."""
    _enabled[0] = None if flag is None else bool(flag)


# ---------------------------------------------------------------------------
# step-phase spans + flight recorder
# ---------------------------------------------------------------------------
# trace's own registry entries (the step id allocator is process-global
# and monotonic: a retried step gets a FRESH id, ids are never reused)
_STEPS = counter("trace/steps", "step spans opened (training + serving)")
_SPANS = counter("trace/spans", "phase spans recorded into the ring")
_DROPPED = counter("trace/spans_dropped",
                   "spans evicted from the flight-recorder ring")
_STEP_MS = histogram("trace/step_ms", "wall ms per closed step span")

_step_seq = itertools.count(1)
_tls = threading.local()
_ring_lock = threading.Lock()
_ring = None            # deque created lazily (env-sized)

# span-boundary memory sampler (mxnet_tpu.memory installs it): called as
# fn(phase, step, ts_us) after each span lands, None = no sampling.  A
# hook rather than an import so telemetry stays leaf-level in the import
# graph (memory imports telemetry, never the reverse).
_mem_sampler = [None]


def set_memory_sampler(fn):
    """Install (or clear, fn=None) the span-boundary memory sampling
    callback — ``mxnet_tpu.memory`` owns the only production caller."""
    _mem_sampler[0] = fn


def _get_ring():
    global _ring
    if _ring is None:
        from .util import getenv
        with _ring_lock:
            if _ring is None:
                _ring = deque(maxlen=max(16, int(
                    getenv("MXNET_TELEMETRY_RING"))))
    return _ring


def add_span(phase_name, ts_us, dur_us, step=None, kind=None, **attrs):
    """Record one finished span into the flight-recorder ring (and mirror
    it to the chrome-trace recorder when the profiler is running).

    ``ts_us``/``dur_us`` are ``time.perf_counter_ns() // 1000`` values —
    the same clock every recorder in the repo uses.  ``step`` defaults to
    the calling thread's current step id (None outside any step)."""
    if not enabled():
        return
    sample = True
    if step is None:
        cur = getattr(_tls, "step", None)
        if cur is not None:
            step, kind, sample = cur[0], cur[1], cur[4]
    rec = {"step": step, "kind": kind, "phase": phase_name,
           "ts_us": int(ts_us), "dur_us": round(float(dur_us), 3),
           "tid": threading.get_ident() % 100000}
    if attrs:
        rec["args"] = attrs
    ring = _get_ring()
    with _ring_lock:
        if len(ring) == ring.maxlen:
            _DROPPED.inc()
        ring.append(rec)
    _SPANS.inc()
    sampler = _mem_sampler[0]
    if sampler is not None and sample:
        # phase-correlated memory sample (docs/OBSERVABILITY.md memory/*):
        # best-effort — observability must never fail the observed step
        try:
            sampler(phase_name, rec["step"], rec["ts_us"])
        except Exception:   # noqa: BLE001
            pass
    from . import profiler as _profiler
    if _profiler.is_running():
        args = {"step": step}
        if attrs:
            args.update(attrs)
        _profiler.record_event(f"phase/{phase_name}", "phase",
                               int(ts_us), float(dur_us), args=args)


_annotation_cls = [None]


def _annotate(kind, phase_name, step):
    """Enter the ``jax.profiler`` sink of a span: a ``TraceAnnotation``
    named ``mx:<kind>.<phase>`` (``mx:<phase>`` outside any step) that
    carries the step id.  Outside a profiler session the annotation is a
    check of one flag; inside one it is an event in the host plane of the
    same ``.xplane.pb`` as the device ops, on the profiler's clock, under
    the thread that ran it."""
    cls = _annotation_cls[0]
    if cls is None:
        from jax.profiler import TraceAnnotation as cls
        _annotation_cls[0] = cls
    ann = cls(f"mx:{kind}.{phase_name}" if kind else f"mx:{phase_name}",
              step=step if step is not None else -1)
    ann.__enter__()
    return ann


class _NullSpan:
    """Shared no-op context manager: the entire cost of a span call with
    telemetry off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        """No-op attr update (API parity with :class:`_Phase`)."""


_NULL = _NullSpan()


class _Phase:
    __slots__ = ("_name", "_attrs", "_t0", "_ann")

    def __init__(self, name, attrs):
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        cur = getattr(_tls, "step", None)
        sid, kind = cur[:2] if cur is not None else (None, None)
        self._ann = _annotate(kind, self._name, sid)
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs):
        """Add/override span attributes before the scope closes — for
        values only knowable mid-span (e.g. the serving execute span's
        ``mfu``, derived from the elapsed wall)."""
        self._attrs = dict(self._attrs, **attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        add_span(self._name, self._t0 // 1000, (t1 - self._t0) / 1000,
                 **self._attrs)
        return False


# ---------------------------------------------------------------------------
# the device's side: the parts of a model's step
# ---------------------------------------------------------------------------
# What a device trace may call a piece of a step program.  A part's
# pre-norm and its residual add belong to it; a second scope inside one
# names a sub-part ("attention/ring_write").  docs/OBSERVABILITY.md says
# what each holds and which metric or record reads it.
PARTS = ("embed", "attention", "indexer", "conv", "ffn", "experts", "head",
         "loss", "optimizer")
SUB_PARTS = ("project", "attend", "ring_write", "scores", "top_k", "mask",
             "router", "sort", "product", "combine", "shared", "health",
             "short_conv", "scan", "gate")

# Both compile caches key a program with its debug locations stripped,
# and a scope's name lives there: an executable compiled before a scope
# was added, renamed or moved would be a warm hit under the old names.
# Bump this with any such change: ``compile.version_stamp`` and
# ``compile.enable_persistent_cache`` fold it into both keys.
PARTS_VERSION = 2


def part(name):
    """``with telemetry.part("attention"):`` — files the work *traced*
    under it as that part of the model: ``mx.<name>`` on the name stack
    of every operation (``jax.named_scope``), which XLA carries as the
    instruction's ``op_name`` into the executable and into the profiler's
    device events.  It acts while a program is traced and costs nothing
    when it runs; it is not gated by ``MXNET_TELEMETRY``."""
    import jax
    return jax.named_scope("mx." + name)


def phase(name, **attrs):
    """``with telemetry.phase("compile", label=...):`` — one named span
    attributed to the calling thread's current step.  Free when telemetry
    is off."""
    if not enabled():
        return _NULL
    return _Phase(name, attrs)


def _open_step(kind, sample_phases=True):
    """A fresh monotonic step id becomes the calling thread's current
    step: ``(id, kind, start ns, profiler annotation, sample_phases)``."""
    sid = next(_step_seq)
    ann = _annotate(kind, "step", sid)
    _tls.step = (sid, kind, time.perf_counter_ns(), ann, sample_phases)
    _STEPS.inc()
    return sid


def step_boundary(kind="train"):
    """Close the open implicit step on this thread and open a new one
    with a fresh monotonic id.  This is how the training loops mark step
    starts: ``gluon`` at ``autograd.record()`` entry, ``SPMDTrainer`` at
    ``step()`` entry — phases recorded until the next boundary attribute
    to this step.  Returns the new step id (None when telemetry is off)."""
    if not enabled():
        # discard (don't record) any step left open from before telemetry
        # was disabled: recording it on re-enable would produce a bogus
        # "step" span covering the whole disabled window
        _tls.step = None
        return None
    end_step()
    return _open_step(kind)


def end_step():
    """Close the calling thread's open implicit step (records its
    ``step`` span and wall-ms histogram sample).  Safe no-op otherwise."""
    cur = getattr(_tls, "step", None)
    if cur is None:
        return
    _tls.step = None
    sid, kind, t0, ann, _sample = cur
    t1 = time.perf_counter_ns()
    ann.__exit__(None, None, None)
    _STEP_MS.observe((t1 - t0) / 1e6)
    add_span("step", t0 // 1000, (t1 - t0) / 1000, step=sid, kind=kind)


class _StepSpan:
    """Explicit bracketed step (serving batches): saves and restores any
    surrounding step so a serve step nested in a training thread cannot
    orphan the trainer's attribution."""

    __slots__ = ("_kind", "_sample_phases", "_prev", "step_id")

    def __init__(self, kind, sample_phases):
        self._kind = kind
        self._sample_phases = sample_phases

    def __enter__(self):
        self._prev = getattr(_tls, "step", None)
        self.step_id = _open_step(self._kind, self._sample_phases)
        return self

    def __exit__(self, *exc):
        cur = getattr(_tls, "step", None)
        if cur is not None and cur[0] == self.step_id:
            end_step()
        _tls.step = self._prev
        return False


def step_span(kind="serve", sample_phases=True):
    """Context manager for a fully-bracketed step (one serving batch, one
    iteration of the generation loop).  ``sample_phases=False`` keeps the
    span-boundary memory sampler off the step's phases: it then runs once
    a step, when the envelope closes — for loops whose steps are a few
    milliseconds and whose phases would each pay a ``memory_stats()``."""
    if not enabled():
        return _NULL
    return _StepSpan(kind, sample_phases)


def flight_recorder():
    """Raw snapshot of the span ring (oldest first)."""
    if _ring is None:
        return []
    with _ring_lock:
        return list(_ring)


def flight_recorder_payload(last_steps=16):
    """The crash-report ``telemetry`` section (schema v1,
    docs/OBSERVABILITY.md): the ring's spans grouped into the last
    ``last_steps`` step timelines, newest last, plus the count of spans
    recorded outside any step."""
    spans = flight_recorder()
    by_step: dict = {}
    unattributed = 0
    for s in spans:
        if s["step"] is None:
            unattributed += 1
            continue
        by_step.setdefault(s["step"], []).append(s)
    steps = []
    for sid in sorted(by_step)[-max(1, int(last_steps)):]:
        ss = sorted(by_step[sid], key=lambda s: s["ts_us"])
        steps.append({"step": sid, "kind": ss[0].get("kind"),
                      "spans": [{k: v for k, v in s.items()
                                 if k not in ("step", "kind")}
                                for s in ss]})
    return {"schema": 1, "steps": steps,
            "unattributed_spans": unattributed,
            "dropped_spans": _DROPPED.value,
            "total_spans_recorded": _SPANS.value}


def reset():
    """Zero owned metrics and clear the span ring (tests).  The step-id
    allocator is NOT reset — ids stay monotonic for the process life, so
    a span recorded before a reset can never alias one recorded after."""
    _registry._reset()
    if _ring is not None:
        with _ring_lock:
            _ring.clear()
    _tls.step = None


# ---------------------------------------------------------------------------
# request-scoped distributed tracing
# ---------------------------------------------------------------------------
# A request crossing client -> Router -> replica -> DynamicBatcher ->
# InferenceEngine carries ONE trace id end to end; each hop records
# wall-clock spans against it (wall clock, not perf_counter: spans from
# different processes must merge onto one timeline), the attempt counter
# increments on transparent retry / orphan re-route while the id stays
# stable, and the response carries the server-side breakdown back to the
# client.  Completed traces are tail-sampled into an on-disk spool that
# ``tools/trace_report.py --fleet`` merges across processes.  With
# ``MXNET_TRACE_SAMPLE=0`` (the default) every call here returns a shared
# no-op constant — same contract as ``MXNET_TELEMETRY=0`` for step spans.
_TRACE_REQUESTS = counter("trace/requests",
                          "request traces opened in this process")
_TRACE_SPOOLED = counter("trace/spooled",
                         "completed request traces written to the spool")
_TRACE_SPOOL_DROPPED = counter(
    "trace/spool_dropped",
    "spool records dropped past the in-memory cap")
_TRACE_SPOOL_ERRORS = counter("trace/spool_errors",
                              "trace spool writes that failed")
_TRACE_INFLIGHT = gauge("trace/inflight",
                        "traced requests currently held by this process")

_trace_rate = [None]            # None = read MXNET_TRACE_SAMPLE on first use


def _sample_rate():
    v = _trace_rate[0]
    if v is None:
        from .util import getenv
        v = _trace_rate[0] = max(0.0, float(getenv("MXNET_TRACE_SAMPLE")))
    return v


def tracing_enabled():
    """Request tracing on?  (``MXNET_TRACE_SAMPLE`` > 0.)"""
    return _sample_rate() > 0.0


def set_trace_sample(rate):
    """Override the head-sampling rate for this process
    (``set_trace_sample(None)`` re-reads ``MXNET_TRACE_SAMPLE`` on next
    use).  Rate 0 turns request tracing into the shared no-op constant."""
    _trace_rate[0] = None if rate is None else max(0.0, float(rate))


def _wall_us():
    return time.time_ns() // 1000


class _ReqSpan:
    """Times one hop-local span into a :class:`RequestTrace`."""

    __slots__ = ("_trace", "_name", "_attrs", "_t0")

    def __init__(self, trace, name, attrs):
        self._trace = trace
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        self._t0 = _wall_us()
        return self

    def __exit__(self, *exc):
        self._trace.add_span(self._name, self._t0, _wall_us() - self._t0,
                             **self._attrs)
        return False


class RequestTrace:
    """One request's trace context at one hop.

    ``trace_id`` is minted by the client (or the first hop that sees an
    untraced request) and rides the wire unchanged; ``attempt`` is the
    router's dispatch-attempt counter (0 for the first dispatch — a
    retried/re-routed request keeps its id and bumps the attempt);
    ``sampled`` is the head-sample verdict that guarantees spooling.
    Spans recorded here use the wall clock so traces merge across
    processes (``tools/trace_report.py --fleet``).
    """

    __slots__ = ("trace_id", "attempt", "sampled", "sent_us", "_spans",
                 "_marks", "_lock")

    def __init__(self, trace_id, attempt=0, sampled=False, sent_us=None):
        self.trace_id = str(trace_id)
        self.attempt = int(attempt)
        self.sampled = bool(sampled)
        # when this hop continued an incoming context: the wall-clock µs
        # the upstream hop SENT the request (rides the wire), so the
        # receiver can span the wire + accept-queue gap it can't observe
        # any other way (same-host wall-clock alignment, like all spans)
        self.sent_us = int(sent_us) if sent_us else None
        self._spans = []
        self._marks = set()
        self._lock = threading.Lock()

    def __bool__(self):
        return True

    def span(self, name, **attrs):
        """``with trace.span("router_dispatch", replica=1):`` — one
        wall-clock span recorded against this trace."""
        return _ReqSpan(self, name, attrs)

    def add_span(self, name, ts_us, dur_us, proc=None, **attrs):
        """Record one finished span (wall-clock µs)."""
        rec = {"phase": name, "ts_us": int(ts_us),
               "dur_us": round(float(dur_us), 3), "attempt": self.attempt}
        if proc is not None:
            rec["proc"] = proc
        if attrs:
            rec["args"] = attrs
        with self._lock:
            self._spans.append(rec)

    def merge(self, spans, proc=None):
        """Fold another hop's spans in (e.g. the replica breakdown a
        dispatch response carried), tagging them with ``proc`` unless
        they already name their process."""
        if not spans:
            return
        with self._lock:
            for s in spans:
                s = dict(s)
                if proc is not None and "proc" not in s:
                    s["proc"] = proc
                self._spans.append(s)

    def mark(self, reason):
        """Flag an always-keep spool reason (``retried`` / ``rerouted``
        / ``shed`` — ``slow`` is computed at spool time)."""
        with self._lock:
            self._marks.add(str(reason))

    @property
    def marks(self):
        with self._lock:
            return sorted(self._marks)

    def spans(self):
        with self._lock:
            return [dict(s) for s in self._spans]

    def wire(self):
        """The request-body ``trace`` field forwarded to the next hop.
        ``sent_us`` is stamped at call time — build the wire dict right
        before sending so the receiver's accept span measures transport
        + accept queue, not payload construction."""
        return {"id": self.trace_id, "attempt": self.attempt,
                "sampled": self.sampled, "sent_us": _wall_us()}

    def accept_span(self, name, now_us, **attrs):
        """Record the wire + accept-queue gap: upstream ``sent_us`` →
        this hop picking the request up (no-op when the incoming context
        carried no send timestamp)."""
        if self.sent_us is not None and now_us > self.sent_us:
            self.add_span(name, self.sent_us, now_us - self.sent_us,
                          **attrs)

    def response_payload(self, proc=None):
        """The response-body ``trace`` field: id + the full server-side
        breakdown (own spans plus any merged downstream ones), so the
        client renders a waterfall with zero scraping.  ``proc`` tags
        this hop's own spans with its process label; merged spans keep
        theirs.  ``sent_us`` is stamped at call time — build this right
        before writing the response so the caller's receive span covers
        the reply transport."""
        spans = self.spans()
        if proc is not None:
            for s in spans:
                s.setdefault("proc", proc)
        return {"id": self.trace_id, "attempt": self.attempt,
                "sampled": self.sampled, "keep": self.marks,
                "sent_us": _wall_us(), "spans": spans}


class _NullTrace:
    """The entire cost of request tracing when it is off: one shared
    constant whose every method is a no-op (``MXNET_TRACE_SAMPLE=0``)."""

    __slots__ = ()
    trace_id = None
    attempt = 0
    sampled = False
    sent_us = None
    marks = ()

    def __bool__(self):
        return False

    def span(self, name, **attrs):
        return _NULL

    def add_span(self, *a, **k):
        pass

    def accept_span(self, *a, **k):
        pass

    def merge(self, spans, proc=None):
        pass

    def mark(self, reason):
        pass

    def spans(self):
        return []

    def wire(self):
        return None

    def response_payload(self):
        return None


NULL_TRACE = _NullTrace()


def new_trace():
    """Mint a fresh trace for an outgoing request (the client side).

    The head-sample coin decides at mint time: a sampled-out request
    gets :data:`NULL_TRACE` — the same shared no-op constant as
    ``MXNET_TRACE_SAMPLE=0``, so the requests you are *not* looking at
    pay nothing (``tests/test_tracing.py`` holds the identity).  A
    head-sample hit is
    traced at every hop and guaranteed a spool record."""
    rate = _sample_rate()
    if rate <= 0.0:
        return NULL_TRACE
    if rate < 1.0:
        import random as _pyrandom
        if _pyrandom.random() >= rate:
            return NULL_TRACE
    import os as _os
    _TRACE_REQUESTS.inc()
    return RequestTrace(_os.urandom(8).hex(), 0, True)


def continue_trace(wire):
    """Adopt an incoming request's ``trace`` wire field at a server hop.
    Returns :data:`NULL_TRACE` when the request carries no trace or
    tracing is off locally — so ``continue_trace(w) or new_trace()`` is
    the front-end idiom for "continue it, else mint one"."""
    if not wire or not tracing_enabled():
        return NULL_TRACE
    try:
        _TRACE_REQUESTS.inc()
        return RequestTrace(wire["id"], wire.get("attempt", 0),
                            wire.get("sampled", False),
                            sent_us=wire.get("sent_us"))
    except (KeyError, TypeError, ValueError):
        return NULL_TRACE


# -- thread-local trace scope (how the engine finds the batch's traces) -----
def request_scope(traces):
    """Bind the given live traces to the calling thread for the duration
    of the ``with`` block: :func:`request_span` inside (e.g. the
    engine's ``execute`` hop) records into every one of them.  The
    batcher wraps each engine dispatch in this with the batch's traced
    co-riders."""
    traces = [t for t in (traces or ()) if t]
    if not traces:
        return _NULL
    return _RequestScope(traces)


class _RequestScope:
    __slots__ = ("_traces", "_prev")

    def __init__(self, traces):
        self._traces = traces

    def __enter__(self):
        self._prev = getattr(_tls, "req_traces", None)
        _tls.req_traces = self._traces
        return self

    def __exit__(self, *exc):
        _tls.req_traces = self._prev
        return False


class _MultiSpan:
    __slots__ = ("_traces", "_name", "_attrs", "_t0")

    def __init__(self, traces, name, attrs):
        self._traces = traces
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        self._t0 = _wall_us()
        return self

    def set(self, **attrs):
        """Add/override span attributes before the scope closes (same
        contract as :meth:`_Phase.set`)."""
        self._attrs = dict(self._attrs, **attrs)

    def __exit__(self, *exc):
        dur = _wall_us() - self._t0
        for t in self._traces:
            t.add_span(self._name, self._t0, dur, **self._attrs)
        return False


def request_span(name, **attrs):
    """One span recorded into every trace bound by the nearest enclosing
    :func:`request_scope` — the shared no-op constant when none is."""
    traces = getattr(_tls, "req_traces", None)
    if not traces:
        return _NULL
    return _MultiSpan(traces, name, attrs)


# -- in-flight registry (crash reports name the requests a process held) ----
_inflight_lock = threading.Lock()
_inflight: dict = {}            # trace_id -> count


def inflight_add(trace_id):
    if not trace_id:
        return
    with _inflight_lock:
        _inflight[trace_id] = _inflight.get(trace_id, 0) + 1
        _TRACE_INFLIGHT.set(len(_inflight))


def inflight_remove(trace_id):
    if not trace_id:
        return
    with _inflight_lock:
        n = _inflight.get(trace_id, 0) - 1
        if n > 0:
            _inflight[trace_id] = n
        else:
            _inflight.pop(trace_id, None)
        _TRACE_INFLIGHT.set(len(_inflight))


def inflight_trace_ids():
    """Trace ids of requests this process is currently holding — the
    ``in_flight_trace_ids`` field of crash reports (schema v2,
    docs/RESILIENCE.md): a wedged replica's report names exactly the
    requests it died holding."""
    with _inflight_lock:
        return sorted(_inflight)


# -- the spool --------------------------------------------------------------
_SPOOL_CAP = 10000              # per-process record bound (disk + memory)
_SPOOL_FLUSH_EVERY = 8
_spool_lock = threading.Lock()
_spool_records: list = []       # buffered, not yet on disk
_spool_accepted = [0]           # records accepted (buffered or on disk)
_spool_unflushed = [0]
_spool_atexit = [False]


def _spool_dir():
    import os as _os
    return _os.environ.get("MXNET_TRACE_SPOOL_DIR") or None


def _spool_path():
    import os as _os
    d = _spool_dir()
    if not d:
        return None
    return _os.path.join(d, f"trace_spool_{_os.getpid()}.jsonl")


def flush_trace_spool():
    """Append the buffered records to this process's spool file — one
    JSON record per line, so a flush costs O(new records), never a
    whole-file rewrite on the request path.  Each record is written in
    one ``write`` call; a crash mid-append can tear at most the final
    line, which the ``--fleet`` reader skips.  Called automatically
    every few records, at interpreter exit, and on server shutdown."""
    import os as _os
    path = _spool_path()
    if path is None:
        return None
    with _spool_lock:
        records = _spool_records[:]
        _spool_records.clear()
        _spool_unflushed[0] = 0
    if not records:
        return path
    try:
        _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "a") as f:
            for rec in records:
                f.write(json.dumps(rec, separators=(",", ":")) + "\n")
        return path
    except (OSError, TypeError, ValueError):
        _TRACE_SPOOL_ERRORS.inc()
        return None


def _slow_ms():
    from .util import getenv
    return float(getenv("MXNET_TRACE_SLOW_MS"))


def maybe_spool(trace, wall_ms, role):
    """Tail-sampling decision at request completion: spool when the
    head-sample coin said yes OR an always-keep rule fires — the request
    was slow (``MXNET_TRACE_SLOW_MS``), retried, re-routed, or shed.
    Returns the keep reasons (empty tuple = sampled out, not spooled)."""
    if not trace:
        return ()
    keep = list(trace.marks)
    if wall_ms is not None and wall_ms >= _slow_ms():
        keep.append("slow")
    if trace.sampled:
        keep.append("sampled")
    if not keep:
        return ()
    if _spool_dir() is None:
        return tuple(keep)
    import os as _os
    # spool only this hop's OWN spans (the ones without a `proc` tag):
    # spans merged from downstream hops are already in that process's
    # spool, and double-spooling them would double-count at --fleet merge
    rec = {"trace_id": trace.trace_id, "role": role, "pid": _os.getpid(),
           "ts": time.time(), "attempt": trace.attempt,
           "sampled": trace.sampled, "keep": sorted(set(keep)),
           "wall_ms": round(float(wall_ms), 3) if wall_ms is not None
           else None,
           "spans": [s for s in trace.spans() if "proc" not in s]}
    flush_now = False
    with _spool_lock:
        if _spool_accepted[0] >= _SPOOL_CAP:
            # bound the per-process spool: past the cap new records are
            # dropped (and counted), never silently rotated — forensics
            # prefers the front of a storm over its tail
            _TRACE_SPOOL_DROPPED.inc()
            return tuple(sorted(set(keep)))
        _spool_records.append(rec)
        _spool_accepted[0] += 1
        _spool_unflushed[0] += 1
        if _spool_unflushed[0] >= _SPOOL_FLUSH_EVERY:
            flush_now = True
        if not _spool_atexit[0]:
            _spool_atexit[0] = True
            import atexit
            atexit.register(flush_trace_spool)
    _TRACE_SPOOLED.inc()
    if flush_now:
        flush_trace_spool()
    return tuple(sorted(set(keep)))


# The span-union / waterfall rendering logic is deliberately duplicated
# in the stdlib-only ``tools/trace_report.py`` (it must fold spools
# without importing jax).  The shared bodies live inside structured
# KEEP-IN-SYNC blocks that ``tools/check_keep_in_sync.py`` (a fast
# tier-1 lint) verifies are textually identical on both sides.

# >>> KEEP-IN-SYNC(span-union) mxnet_tpu/telemetry.py <-> tools/trace_report.py
_ENVELOPE_PHASES = ("client_request",)


def _span_intervals_us(spans, include_envelope=False):
    """Sorted (lo, hi) µs intervals of the coverage-countable spans.  The
    ``client_request`` envelope is excluded by default: it IS the wall
    being covered, and counting it would make every coverage figure a
    tautological 100%."""
    return sorted((s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
                  if s.get("dur_us", 0) > 0
                  and (include_envelope
                       or s.get("phase") not in _ENVELOPE_PHASES))


def _interval_union_us(iv):
    """Union length of sorted (lo, hi) intervals (overlap counted once)."""
    total = 0.0
    cur_lo = cur_hi = None
    for lo, hi in iv:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


_COLLECTIVE_PHASE = "collective"
_OVERLAP_COMPUTE_PHASES = ("backward", "execute")


def _merge_intervals_us(iv):
    """Union-normalize sorted (lo, hi) intervals: merged, overlap-free."""
    out = []
    for lo, hi in iv:
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _interval_intersection_us(a, b):
    """Total overlap length between two union-normalized interval lists."""
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _collective_overlap_us(spans):
    """(hidden_us, total_us) for a step's ``collective`` spans: how much
    of the collective time was hidden under backward/execute compute.  A
    span carrying a measured ``args.hidden_us`` (the paired-program
    dryrun referee writes one) is authoritative; otherwise the hidden
    time is the wall-clock intersection with the compute spans."""
    coll = [s for s in spans if s.get("phase") == _COLLECTIVE_PHASE
            and s.get("dur_us", 0) > 0]
    if not coll:
        return 0.0, 0.0
    total = float(sum(s["dur_us"] for s in coll))
    measured = [float((s.get("args") or {}).get("hidden_us", 0) or 0)
                for s in coll]
    if any(measured):
        return min(total, sum(measured)), total
    cv = _merge_intervals_us(
        sorted((s["ts_us"], s["ts_us"] + s["dur_us"]) for s in coll))
    comp = _merge_intervals_us(
        sorted((s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
               if s.get("phase") in _OVERLAP_COMPUTE_PHASES
               and s.get("dur_us", 0) > 0))
    return _interval_intersection_us(cv, comp), total
# <<< KEEP-IN-SYNC(span-union)


def span_union_ms(spans, include_envelope=False):
    """Wall-clock union of a span list's intervals in ms — the coverage
    numerator: how much of a request's life the trace accounts for
    (overlapping hops counted once)."""
    return _interval_union_us(
        _span_intervals_us(spans, include_envelope)) / 1000.0


# >>> KEEP-IN-SYNC(waterfall-span-line) mxnet_tpu/telemetry.py <-> tools/trace_report.py
def _format_span_line(s, t0_us):
    """One waterfall row: +offset, duration, process, phase, args."""
    args = dict(s.get("args") or {})
    if s.get("attempt") is not None:
        args["attempt"] = s["attempt"]
    arg_s = " ".join(f"{k}={v}" for k, v in sorted(args.items()))
    return (f"  +{(s['ts_us'] - t0_us) / 1000.0:8.2f} "
            f"{s['dur_us'] / 1000.0:8.2f}ms  "
            f"{str(s.get('proc', '?')):<16} {s['phase']:<18} {arg_s}")
# <<< KEEP-IN-SYNC(waterfall-span-line)


def format_request_waterfall(payload, wall_ms=None):
    """Render one request's trace breakdown (a ``response_payload()`` /
    spool record / ``trace_report --fleet`` merged dict) as an aligned
    waterfall, offsets relative to the earliest span."""
    spans = sorted(payload.get("spans") or [],
                   key=lambda s: (s.get("ts_us", 0), -s.get("dur_us", 0)))
    tid = payload.get("trace_id") or payload.get("id") or "?"
    wall = wall_ms if wall_ms is not None else payload.get("wall_ms")
    if wall is None and spans:
        wall = (max(s["ts_us"] + s["dur_us"] for s in spans)
                - min(s["ts_us"] for s in spans)) / 1000.0
    keep = ",".join(payload.get("keep") or ()) or "-"
    attempts = 1 + max((s.get("attempt", 0) for s in spans), default=0)
    head = (f"trace {tid}  wall {wall:.2f} ms  attempts {attempts}  "
            f"keep={keep}")
    if not spans:
        return head + "\n  (no spans)"
    cov = span_union_ms(spans) / wall if wall else 0.0
    t0 = min(s["ts_us"] for s in spans)
    lines = [head]
    for s in spans:
        lines.append(_format_span_line(s, t0))
    lines.append(f"  span union {span_union_ms(spans):.2f} ms = "
                 f"{100.0 * cov:.1f}% of wall")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# exposition for training jobs
# ---------------------------------------------------------------------------
class MetricsServer:
    """Loopback HTTP exposition server: ``/metrics`` (Prometheus text),
    ``/statusz`` (full JSON snapshot + flight-recorder tail),
    ``/healthz``.  ``port=0`` picks an ephemeral port."""

    def __init__(self, port=0, host="127.0.0.1"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):      # noqa: A003
                pass

            def _reply(self, code, body, ctype):
                if isinstance(body, str):
                    body = body.encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):                        # noqa: N802
                if self.path == "/metrics":
                    self._reply(200, prometheus_text(),
                                "text/plain; version=0.0.4; charset=utf-8")
                elif self.path == "/statusz":
                    self._reply(200, json.dumps(statusz_payload(),
                                                default=str),
                                "application/json")
                elif self.path == "/healthz":
                    self._reply(200, '{"status": "ok"}', "application/json")
                else:
                    self._reply(404, '{"error": "not_found"}',
                                "application/json")

        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="mxnet-tpu-metrics", daemon=True)
        self._thread.start()

    @property
    def host(self):
        return self._httpd.server_address[0]

    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def stop(self):
        self._httpd.shutdown()
        self._thread.join(5.0)
        self._httpd.server_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def _json_safe(obj):
    """Replace non-finite floats (histogram +Inf bucket bounds) with their
    Prometheus string spellings: ``json.dumps`` would emit the bare token
    ``Infinity``, which is not RFC 8259 JSON and breaks strict clients."""
    if isinstance(obj, float):
        if obj != obj:
            return "NaN"
        if obj in (float("inf"), float("-inf")):
            return "+Inf" if obj > 0 else "-Inf"
        return obj
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


def statusz_payload():
    """The ``/statusz`` JSON body: full snapshot + the flight recorder's
    recent-step timeline (shared by :class:`MetricsServer` and the
    serving front-end).  Strictly JSON-serializable: non-finite bucket
    bounds are spelled ``"+Inf"``."""
    return _json_safe({"telemetry": snapshot(),
                       "flight_recorder": flight_recorder_payload(
                           last_steps=8)})


def serve_metrics(port=0, host="127.0.0.1"):
    """Start the metrics exposition server for a training job; returns a
    :class:`MetricsServer` (``.port``, ``.url``, ``.stop()``)."""
    return MetricsServer(port=port, host=host)
