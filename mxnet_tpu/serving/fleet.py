"""Replica fleet serving: supervised worker processes behind a router.

One process serves one chip; a *service* is N of them that survive a
replica being killed or hung mid-storm.  This module turns the serving
stack into that service:

* :class:`ReplicaSpec` — a picklable description of what a worker
  serves (model factory, bucket ladder, batcher knobs, env).  Workers
  are real processes (``multiprocessing`` spawn), each running the full
  ``InferenceEngine`` → ``DynamicBatcher`` → ``ModelServer`` stack on an
  ephemeral loopback port, warm-starting bucket programs from the
  *shared* on-disk ProgramCache index (point ``spec.env`` at one
  ``JAX_COMPILATION_CACHE_DIR`` — docs/COMPILE.md) so replica N+1 pays a
  deserialize, not an XLA compile.
* :class:`ReplicaSupervisor` — spawns the workers, health-checks them
  (heartbeat + progress + ``/healthz`` probe) and restarts crashed or
  hung replicas with :func:`faults.classify_exit`-driven exponential
  backoff; a replica that fails permanently (bad model factory) is
  marked failed instead of burning the restart budget.
* :class:`Router` — least-loaded dispatch over the live replicas with
  per-request deadline propagation, transparent re-dispatch of
  *idempotent* requests orphaned by a dying replica (a connection that
  broke after the request was sent may have executed — non-idempotent
  requests fail instead of double-executing), and fleet-level shedding
  (``QueueFullError``) when aggregate queue depth breaches the
  ``max_outstanding`` SLO.  :meth:`Router.rolling_swap` is the zero-drop
  rollout: drain one replica at a time (stop dispatching, finish
  in-flight), hot-swap weights, re-admit.
* :class:`RouterServer` — the loopback HTTP front: ``/predict`` with an
  ``idempotent`` flag, plus ``/metrics`` / ``/statusz`` / ``/healthz``
  carrying per-replica status and the fleet-aggregate ``fleet/*``
  telemetry (docs/OBSERVABILITY.md).

Self-healing rides two request-granular mechanisms on the router
(docs/SERVING.md): per-replica **circuit breakers** (consecutive-
failure or latency-EWMA trip → open → one half-open probe → close)
route a failing or slow-but-alive replica around within milliseconds
of the signal instead of heartbeat granularity, and **hedged dispatch**
races one extra attempt of an idempotent request after a p95-derived
delay — first response wins — under a hard hedge-rate token budget so
hedging can never amplify an overload.  The fleet-granular leg is
``serving.autoscaler.Autoscaler``, a policy loop over the federated
gauges that grows/shrinks the replica set strictly through the
zero-drop drain machinery (``add_replica`` / ``remove_replica`` here).

Chaos is a first-class test input: the worker-side ``serving.replica``
fault point (in ``InferenceEngine``) and the router-side
``router.dispatch`` point (here) let ``MXNET_FAULT_PLAN`` kill or wedge
a replica mid-request-storm, and the wire-level ``net.connect`` (here)
/ ``net.request`` / ``net.response`` (``http.py``) points express the
degraded-network kinds ``delay``/``reset``/``torn``/``blackhole``;
``tests/test_fleet.py`` holds the acceptance proofs (zero lost idempotent
requests across a crash / slow, torn and partitioned wires, breaker
trip+recover, autoscaler convergence, zero-drop rollout).
Architecture, drain protocol and SLO knobs: docs/SERVING.md.
"""
from __future__ import annotations

import json as _json
import logging
import os
import queue as _queue
import threading
import time
import urllib.error
import urllib.request
import weakref
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as _FutTimeout

import numpy as onp

from ..base import MXNetError
from .. import telemetry as _telemetry
from .errors import (DeadlineExceededError, EngineClosedError,
                     GenerationStreamBroken, QueueFullError,
                     ServiceUnavailableError, ServingError)
from .http import encode_array, decode_array
from .metrics import LatencyHistogram, histogram_expo

__all__ = ["ReplicaSpec", "ReplicaSupervisor", "Router", "RouterServer",
           "federation_prometheus_text"]

_log = logging.getLogger("mxnet_tpu.serving.fleet")


def _tr(trace):
    """``[trace <id> attempt <n>]`` suffix for error messages and
    retry/re-route log lines — how a fleet-level failure names the
    request it belongs to (empty for untraced requests)."""
    return f" [trace {trace.trace_id} attempt {trace.attempt}]" \
        if trace else ""


# ---------------------------------------------------------------------------
# fleet-aggregate metrics (module-level: counters stay monotonic across
# supervisor/router lifetimes; gauges read the live instances at scrape)
# ---------------------------------------------------------------------------
_fleet_lock = threading.Lock()
_fleet_counters = {
    "dispatches": 0, "completed": 0, "errors": 0, "retries": 0,
    "orphans": 0, "shed": 0, "restarts": 0, "hangs": 0, "drains": 0,
    "swaps": 0, "rollouts": 0, "federation_pulls": 0,
    "federation_errors": 0,
    "breaker_trips": 0, "breaker_probes": 0, "breaker_closes": 0,
    "hedges": 0, "hedge_wins": 0, "hedge_denied": 0,
    "scale_ups": 0, "scale_downs": 0, "scale_denied": 0,
    "gen_requests": 0, "gen_reroutes": 0, "gen_broken": 0,
    "gen_restarts": 0,
    "lease_grants": 0, "lease_epoch_bumps": 0,
}
_fleet_latency = LatencyHistogram()
_live_supervisors: "weakref.WeakSet" = weakref.WeakSet()
_live_routers: "weakref.WeakSet" = weakref.WeakSet()
_live_autoscalers: "weakref.WeakSet" = weakref.WeakSet()


def _inc(name, n=1):
    with _fleet_lock:
        _fleet_counters[name] += n


def _observe_latency(ms):
    with _fleet_lock:
        _fleet_latency.observe(ms)


def _telemetry_collect():
    with _fleet_lock:
        out = {"fleet/" + k: v for k, v in _fleet_counters.items()}
        out["fleet/latency_ms"] = histogram_expo(_fleet_latency)
    replicas = up = stale = 0
    for sup in list(_live_supervisors):
        st = sup.status()
        replicas += len(st)
        up += sum(1 for r in st.values() if r["state"] == "up")
        stale += sup.federation_stale_count()
    out["fleet/replicas"] = replicas
    out["fleet/replicas_up"] = up
    out["fleet/federation_stale"] = stale
    routers = list(_live_routers)
    out["fleet/outstanding"] = sum(r.outstanding for r in routers)
    breaker_open = 0
    hedge_delay = 0.0
    for r in routers:
        breaker_open += sum(1 for b in r.breaker_status().values()
                            if b["state"] != "closed")
        hedge_delay = max(hedge_delay, r.hedge_delay_ms() or 0.0)
    out["fleet/breaker_open"] = breaker_open
    out["fleet/hedge_delay_ms"] = round(hedge_delay, 3)
    out["fleet/lease_epoch"] = max(
        (r._lease_epoch for r in routers), default=0)
    out["fleet/scale_target"] = sum(
        a.target for a in list(_live_autoscalers))
    return out


_telemetry.register_collector("fleet", _telemetry_collect, {
    "fleet/dispatches": ("counter", "router dispatch attempts"),
    "fleet/completed": ("counter", "fleet requests resolved with a result"),
    "fleet/errors": ("counter", "fleet requests failed with an exception"),
    "fleet/retries": ("counter",
                      "requests re-dispatched to another replica"),
    "fleet/orphans": ("counter",
                      "in-flight requests orphaned by a dying replica"),
    "fleet/shed": ("counter",
                   "fleet-level admission-control rejects + deadline sheds"),
    "fleet/restarts": ("counter", "supervisor replica restarts"),
    "fleet/hangs": ("counter", "replicas declared hung and killed"),
    "fleet/drains": ("counter", "per-replica drain cycles"),
    "fleet/swaps": ("counter", "per-replica weight swaps applied"),
    "fleet/rollouts": ("counter", "completed rolling weight swaps"),
    "fleet/federation_pulls": ("counter",
                               "worker /statusz snapshots pulled by "
                               "supervisors"),
    "fleet/federation_errors": ("counter",
                                "worker /statusz pulls that failed"),
    "fleet/federation_stale": ("gauge",
                               "replicas whose federated snapshot is "
                               "frozen (dead or past the staleness "
                               "window)"),
    "fleet/breaker_trips": ("counter",
                            "per-replica circuit breakers tripped open "
                            "(consecutive failures or latency EWMA)"),
    "fleet/breaker_probes": ("counter",
                             "half-open probe requests admitted through "
                             "an open breaker"),
    "fleet/breaker_closes": ("counter",
                             "breakers closed after a successful "
                             "half-open probe"),
    "fleet/breaker_open": ("gauge",
                           "replicas currently behind an open or "
                           "half-open breaker"),
    "fleet/hedges": ("counter",
                     "hedged attempts dispatched (idempotent requests "
                     "past the p95-derived hedge delay)"),
    "fleet/hedge_wins": ("counter",
                         "requests whose hedged attempt answered first"),
    "fleet/hedge_denied": ("counter",
                           "hedges blocked by the hedge-rate budget"),
    "fleet/hedge_delay_ms": ("gauge",
                             "current p95-derived hedge delay (0 until "
                             "enough latency samples)"),
    "fleet/gen_requests": ("counter",
                           "generation requests routed (streaming + "
                           "non-streaming)"),
    "fleet/gen_reroutes": ("counter",
                           "generations re-routed to another replica "
                           "before the first token (prefill-only retry)"),
    "fleet/gen_broken": ("counter",
                         "generation streams broken after the first "
                         "token (typed, never silently re-routed)"),
    "fleet/gen_restarts": ("counter",
                           "whole-generation restarts after a mid-stream "
                           "break (Router.generate midstream='restart')"),
    "fleet/lease_grants": ("counter",
                           "replica lease tables served to zero-hop "
                           "clients (RouterServer /leases)"),
    "fleet/lease_epoch_bumps": ("counter",
                                "lease revocations: fleet-shape changes "
                                "(drain/forget/breaker trip/endpoint "
                                "churn) that moved the lease epoch"),
    "fleet/lease_epoch": ("gauge",
                          "current lease epoch (max over live routers)"),
    "fleet/scale_ups": ("counter", "autoscaler replicas added"),
    "fleet/scale_downs": ("counter",
                          "autoscaler replicas removed (zero-drop "
                          "drain-then-stop)"),
    "fleet/scale_denied": ("counter",
                           "autoscaler decisions blocked by bounds, "
                           "cooldown or a failed drain"),
    "fleet/scale_target": ("gauge",
                           "autoscaler target replica count (summed "
                           "over live autoscalers)"),
    "fleet/replicas": ("gauge", "configured replicas across live fleets"),
    "fleet/replicas_up": ("gauge", "replicas currently serving"),
    "fleet/outstanding": ("gauge",
                          "accepted requests queued + in flight at routers"),
    "fleet/latency_ms": ("histogram",
                         "fleet end-to-end submit->result ms"),
})


# ---------------------------------------------------------------------------
# fleet metric federation: worker /statusz snapshots -> one front-end view
# ---------------------------------------------------------------------------
def _hist_zero():
    return {"count": 0, "sum": 0.0, "buckets": []}


def _hist_sum(a, b):
    """Merge two expo-shaped histograms (same bucket layout — every
    process shares the LatencyHistogram/telemetry geometric bounds).  On
    a layout mismatch the longer operand wins outright rather than
    producing a lying merge."""
    ba, bb = a.get("buckets") or [], b.get("buckets") or []
    if len(ba) != len(bb):
        return a if len(ba) >= len(bb) else b
    return {"count": a.get("count", 0) + b.get("count", 0),
            "sum": round(a.get("sum", 0.0) + b.get("sum", 0.0), 6),
            "buckets": [[la, ca + cb]
                        for (la, ca), (_lb, cb) in zip(ba, bb)]}


class _ReplicaFederation:
    """One replica's federated metric state at the supervisor.

    The PR-7 retired-accumulator contract at fleet scope: worker
    counters/histograms reset to zero when the process restarts, so the
    last snapshot of each dead incarnation folds into a ``base`` and the
    *effective* value is ``base + current`` — the federated series
    freezes while the replica is down and never decreases.  Gauges are
    instantaneous and simply go stale with the incarnation that reported
    them."""

    def __init__(self):
        self._lock = threading.Lock()
        self._base_counters: dict = {}
        self._base_hists: dict = {}
        self._counters: dict = {}
        self._gauges: dict = {}
        self._hists: dict = {}
        self.ts = None              # monotonic time of last good pull
        self.incarnation = 0

    def absorb(self, snap, now, incarnation):
        """Fold one pulled worker telemetry snapshot in."""
        counters = dict(snap.get("counters") or {})
        hists = dict(snap.get("histograms") or {})
        with self._lock:
            if incarnation != self.incarnation or any(
                    counters.get(k, 0) < v
                    for k, v in self._counters.items()):
                # new incarnation (or a reset we did not see spawn):
                # freeze the dead life's totals into the base
                self._fold_locked()
                self.incarnation = incarnation
            self._counters = counters
            self._gauges = dict(snap.get("gauges") or {})
            self._hists = hists
            self.ts = now

    def fold(self):
        """Called at respawn: the previous incarnation's totals move
        into the base so the restarted worker's zeros cannot read as a
        counter reset."""
        with self._lock:
            self._fold_locked()

    def _fold_locked(self):
        for k, v in self._counters.items():
            self._base_counters[k] = self._base_counters.get(k, 0) + v
        for k, h in self._hists.items():
            self._base_hists[k] = _hist_sum(
                self._base_hists.get(k, _hist_zero()), h)
        self._counters = {}
        self._hists = {}

    def effective(self):
        """``(counters, gauges, histograms)`` with the freeze/never-
        decrease guarantee applied."""
        with self._lock:
            counters = dict(self._base_counters)
            for k, v in self._counters.items():
                counters[k] = counters.get(k, 0) + v
            hists = dict(self._base_hists)
            for k, h in self._hists.items():
                hists[k] = _hist_sum(hists.get(k, _hist_zero()), h)
            return counters, dict(self._gauges), hists


# ---------------------------------------------------------------------------
# replica spec + worker process entry
# ---------------------------------------------------------------------------
class ReplicaSpec:
    """Picklable description of one replica's serving stack.

    ``model_factory`` must be a module-level (picklable) callable
    returning the model to serve — a ``HybridBlock``, a ``ServedModel``
    or a plain callable.  ``warmup_example`` (per-example arrays, no
    batch dim) warms every bucket at startup; with ``precompile=True``
    the warmup goes through ``InferenceEngine.precompile`` so a fleet
    sharing one ``JAX_COMPILATION_CACHE_DIR`` (via ``env``) deserializes
    yesterday's — or replica 0's — programs instead of recompiling.
    ``apply_weights(model, payload)`` applies a rolling-swap payload; the
    default handles ``HybridBlock`` (a ``{param_name: ndarray}`` dict via
    ``set_data``) and any model exposing its own ``apply_weights``.
    """

    def __init__(self, model_factory, batch_buckets=(1, 2, 4, 8, 16),
                 max_batch_size=8, max_delay_ms=2.0, max_queue=64,
                 warmup_example=None, precompile=False, env=None,
                 per_replica_env=None, restart_env=None, apply_weights=None,
                 heartbeat_s=None, generate_factory=None,
                 compile_passes=None):
        self.model_factory = model_factory
        # per-model rewrite-pipeline override (MXNET_COMPILE_PASSES
        # default; docs/COMPILE_PASSES.md) — rides the pickle to every
        # worker, and its fingerprint joins the shared ProgramCache key
        # so a fleet toggling passes across restarts can never warm-load
        # the other mode's programs
        self.compile_passes = compile_passes
        # picklable zero-arg callable returning a ready GenerationEngine
        # (it builds its own model in-worker); when set, the replica's
        # ModelServer also serves /generate and the worker's generate/*
        # metrics federate through the /statusz pull like everything else
        self.generate_factory = generate_factory
        self.batch_buckets = tuple(batch_buckets)
        self.max_batch_size = int(max_batch_size)
        self.max_delay_ms = float(max_delay_ms)
        self.max_queue = int(max_queue)
        self.warmup_example = warmup_example
        self.precompile = bool(precompile)
        self.env = dict(env or {})
        # per-replica overrides (``{idx: {var: value}}``) — how a chaos
        # plan targets ONE replica of an otherwise-uniform fleet
        self.per_replica_env = {int(k): dict(v)
                                for k, v in (per_replica_env or {}).items()}
        # applied on top for restart incarnations only (spawn count >= 1):
        # e.g. ``restart_env={"MXNET_FAULT_PLAN": ""}`` makes the
        # replacement worker of a chaos-killed replica come back clean
        # instead of re-arming the same fault schedule
        self.restart_env = dict(restart_env or {})
        self.apply_weights = apply_weights
        from ..util import getenv
        self.heartbeat_s = float(heartbeat_s if heartbeat_s is not None
                                 else getenv("MXNET_FLEET_HEARTBEAT_S"))


def _default_apply_weights(model, payload):
    if hasattr(model, "apply_weights"):
        model.apply_weights(payload)
        return
    from ..gluon.block import Block
    if isinstance(model, Block):
        params = model.collect_params()
        from .. import ndarray as nd
        for name, value in payload.items():
            params[name].set_data(nd.array(onp.asarray(value)))
        return
    raise MXNetError(
        f"cannot apply weights to {type(model).__name__}: give the model "
        "an apply_weights(payload) method or pass ReplicaSpec("
        "apply_weights=...)")


def _replica_main(spec, conn, idx, incarnation=0):
    """Worker process entry: build the serving stack, report readiness,
    heartbeat, and execute supervisor commands until ``stop``."""
    env = dict(spec.env)
    env.update(spec.per_replica_env.get(idx, {}))
    if incarnation > 0:
        env.update(spec.restart_env)
    os.environ.update({k: str(v) for k, v in env.items()})
    from .. import faults as _faults
    _faults.clear()                  # re-read MXNET_FAULT_PLAN from env
    from .batcher import DynamicBatcher
    from .engine import InferenceEngine
    from .http import ModelServer
    try:
        model = spec.model_factory()
        # getattr: pickled ReplicaSpecs from before the pass layer have
        # no compile_passes attribute — warm-start them unrewritten
        engine = InferenceEngine(
            model, batch_buckets=spec.batch_buckets,
            compile_passes=getattr(spec, "compile_passes", None))
        if spec.warmup_example is not None:
            if spec.precompile:
                # the fleet-scale ProgramCache payoff: lower once, then
                # deserialize what a sibling replica already compiled
                engine.precompile(spec.warmup_example)
            else:
                engine.warmup(spec.warmup_example)
        batcher = DynamicBatcher(engine, max_batch_size=spec.max_batch_size,
                                 max_delay_ms=spec.max_delay_ms,
                                 max_queue=spec.max_queue)
        generator = (spec.generate_factory()
                     if spec.generate_factory is not None else None)
        server = ModelServer(batcher, port=0, generator=generator).start()
    except Exception as e:           # noqa: BLE001 — reported + classified
        try:
            conn.send(("init_error", repr(e), _faults.classify(e)))
        except (OSError, BrokenPipeError):
            pass
        return
    try:
        conn.send(("ready", {"port": server.port, "pid": os.getpid()}))
    except (OSError, BrokenPipeError):
        server.stop()
        return
    apply_fn = spec.apply_weights or _default_apply_weights
    last_hb = 0.0
    running = True
    while running:
        try:
            if conn.poll(spec.heartbeat_s):
                msg = conn.recv()
                cmd = msg[0]
                if cmd == "swap":
                    try:
                        apply_fn(model, msg[1])
                        conn.send(("swapped", None))
                    except Exception as e:   # noqa: BLE001 — reply, don't die
                        conn.send(("swap_error", repr(e)))
                elif cmd == "ping":
                    conn.send(("pong", None))
                elif cmd == "stop":
                    server.stop()            # graceful drain (http.py)
                    conn.send(("stopped", None))
                    running = False
            now = time.monotonic()
            if running and now - last_hb >= spec.heartbeat_s:
                s = batcher.metrics.stats()
                conn.send(("hb", {
                    "ts": time.time(),
                    "completed": s["counters"]["completed"]
                    + s["counters"]["errors"],
                    "queue_depth": s["gauges"]["queue_depth"],
                    "inflight": s["gauges"]["inflight"],
                }))
                last_hb = now
        except (EOFError, OSError, BrokenPipeError):
            # supervisor is gone: nothing to serve for
            server.stop(drain_s=1.0)
            running = False


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------
class _Replica:
    """Supervisor-side handle for one worker process (internal)."""

    def __init__(self, idx, spec):
        self.idx = idx
        self.spec = spec
        self.proc = None
        self.conn = None
        self.port = None
        self.state = "starting"      # starting|up|down|failed|stopped
        self.restarts = 0
        self.spawn_count = 0
        self.consecutive_failures = 0
        self.respawn_at = None
        self.last_exit = None
        self.last_error = None
        self.init_classification = None
        self.suspect = False
        self.last_hb = {}
        self.last_hb_ts = None
        self.last_progress_ts = None
        self.last_completed = -1
        self.ready_event = threading.Event()
        self.replies: _queue.Queue = _queue.Queue()
        self.fed = _ReplicaFederation()
        self.fed_next = 0.0

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}" if self.port else None


class ReplicaSupervisor:
    """Spawn, health-check and restart N serving worker processes.

    The supervisor owns process lifecycle only — request traffic goes
    through a :class:`Router` pointed at it.  Health has three legs, all
    driven from one monitor thread:

    * **liveness** — a dead process (crash, OOM, injected
      ``serving.replica@N:crash``) restarts after classified exponential
      backoff (:func:`faults.classify_exit`; permanent init failures
      mark the replica ``failed`` instead);
    * **progress** — heartbeats carry the replica's completed count and
      queue depth; a replica that is *busy but frozen* (a hung engine
      dispatch: ``serving.replica@N:hang``) past ``hang_grace_s`` is
      killed and restarted (``fleet/hangs``);
    * **probe** — a router-reported suspect replica gets an immediate
      ``/healthz`` probe; probe failure is treated as a hang.
    """

    def __init__(self, spec, n_replicas=2, hang_grace_s=None,
                 max_restarts=None, backoff_s=0.2, max_backoff_s=10.0,
                 start_timeout_s=120.0, federate_s=None):
        from ..util import getenv
        if not isinstance(spec, ReplicaSpec):
            spec = ReplicaSpec(spec)
        self.spec = spec
        # metric-federation pull cadence (worker /statusz snapshots);
        # rides the heartbeat clock by default so one knob tunes both
        self.federate_s = float(federate_s) if federate_s is not None \
            else max(0.25, spec.heartbeat_s)
        self.hang_grace_s = float(
            hang_grace_s if hang_grace_s is not None
            else getenv("MXNET_FLEET_HANG_GRACE_S"))
        self.max_restarts = int(
            max_restarts if max_restarts is not None
            else getenv("MXNET_FLEET_MAX_RESTARTS"))
        self.backoff_s = float(backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self.start_timeout_s = float(start_timeout_s)
        self._replicas = [_Replica(i, spec) for i in range(int(n_replicas))]
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._monitor = None
        self._federator = None
        _live_supervisors.add(self)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        import multiprocessing as mp
        self._ctx = mp.get_context("spawn")
        for r in self._replicas:
            self._spawn(r)
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="mxnet-tpu-fleet-monitor",
                                         daemon=True)
        self._monitor.start()
        # federation pulls run on their OWN thread: a wedged worker's
        # stalled /statusz (the very case the supervisor exists to
        # catch) must never delay heartbeat pumping or hang detection
        self._federator = threading.Thread(target=self._federate_loop,
                                           name="mxnet-tpu-fleet-federate",
                                           daemon=True)
        self._federator.start()
        deadline = time.monotonic() + self.start_timeout_s
        for r in self._replicas:
            if not r.ready_event.wait(max(0.0,
                                          deadline - time.monotonic())):
                self.stop()
                raise MXNetError(
                    f"replica {r.idx} did not come up within "
                    f"{self.start_timeout_s:.0f}s "
                    f"(state={r.state}, last_error={r.last_error})")
            if r.state == "failed":
                self.stop()
                raise MXNetError(
                    f"replica {r.idx} failed permanently at start: "
                    f"{r.last_error}")
        return self

    def stop(self, timeout=10.0):
        self._stop.set()
        # join the monitor BEFORE tearing workers down: once it has
        # exited nothing can respawn a replica under us (a respawn
        # racing stop() would leak a live worker process)
        if self._monitor is not None:
            self._monitor.join(5.0)
            self._monitor = None
        if self._federator is not None:
            self._federator.join(5.0)
            self._federator = None
        replicas = self._list()
        for r in replicas:
            if r.proc is not None and r.proc.is_alive() and \
                    r.conn is not None:
                try:
                    r.conn.send(("stop",))
                except (OSError, BrokenPipeError):
                    pass
        deadline = time.monotonic() + timeout
        for r in replicas:
            if r.proc is not None:
                r.proc.join(max(0.1, deadline - time.monotonic()))
                if r.proc.is_alive():
                    r.proc.terminate()
                    r.proc.join(2.0)
            r.state = "stopped"

    def _list(self):
        """Snapshot of the replica handles (the list mutates under the
        autoscaler's add/remove)."""
        with self._lock:
            return list(self._replicas)

    # -- elastic fleet size (the autoscaler's scale path) ------------------
    def add_replica(self, timeout_s=None):
        """Grow the fleet by one replica on a fresh (never reused) index;
        blocks until the worker reports ready.  A worker that fails to
        come up is rolled back out of the fleet and raises."""
        timeout_s = self.start_timeout_s if timeout_s is None \
            else float(timeout_s)
        with self._lock:
            if self._stop.is_set() or self._monitor is None:
                raise MXNetError("supervisor not running")
            idx = max((r.idx for r in self._replicas), default=-1) + 1
            r = _Replica(idx, self.spec)
            self._replicas.append(r)
        self._spawn(r)
        if not r.ready_event.wait(timeout_s) or r.state != "up":
            with self._lock:
                if r in self._replicas:
                    self._replicas.remove(r)
            if r.proc is not None and r.proc.is_alive():
                r.proc.terminate()
                r.proc.join(2.0)
            raise MXNetError(
                f"replica {idx} failed to come up within {timeout_s:.0f}s "
                f"(state={r.state}, last_error={r.last_error})")
        return idx

    def remove_replica(self, idx, timeout=15.0):
        """Shrink the fleet by one replica.  The caller owns the
        zero-drop half of the contract: drain the replica at the Router
        FIRST (``router.drain(idx)``) so nothing is in flight, then
        remove, then ``router.forget(idx)`` — the worker itself still
        stops through the graceful ``ModelServer.stop`` drain as a
        second line of defense."""
        with self._lock:
            r = next((x for x in self._replicas if x.idx == idx), None)
            if r is None:
                raise MXNetError(f"no replica {idx} in the fleet")
            self._replicas.remove(r)
            r.state = "stopping"     # the monitor snapshot may still
            r.respawn_at = None      # hold it: never respawn/restart it
            r.ready_event.set()
        if r.proc is not None and r.proc.is_alive() and r.conn is not None:
            try:
                r.conn.send(("stop",))
            except (OSError, BrokenPipeError):
                pass
        if r.proc is not None:
            r.proc.join(timeout)
            if r.proc.is_alive():
                r.proc.terminate()
                r.proc.join(2.0)
        r.state = "stopped"
        return idx

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- views -------------------------------------------------------------
    def endpoints(self):
        """``{idx: url}`` of replicas currently serving."""
        with self._lock:
            return {r.idx: r.url for r in self._replicas
                    if r.state == "up" and r.port}

    def status(self):
        """Per-replica status (``/statusz`` fleet section, tests)."""
        now = time.monotonic()
        with self._lock:
            return {r.idx: {
                "state": r.state,
                "port": r.port,
                "pid": r.proc.pid if r.proc is not None else None,
                "restarts": r.restarts,
                "last_exit": r.last_exit,
                "last_error": r.last_error,
                "hb_age_s": round(now - r.last_hb_ts, 3)
                if r.last_hb_ts else None,
                "queue_depth": r.last_hb.get("queue_depth"),
                "completed": r.last_hb.get("completed"),
            } for r in self._replicas}

    def mark_suspect(self, idx):
        """Router-side hint: this replica just failed a connection; the
        monitor probes it on the next tick instead of waiting for the
        heartbeat clock."""
        for r in self._list():
            if r.idx == idx:
                r.suspect = True

    # -- metric federation -------------------------------------------------
    def _replica_stale(self, r, now=None):
        now = time.monotonic() if now is None else now
        return r.state != "up" or r.fed.ts is None or \
            now - r.fed.ts > 3.0 * self.federate_s

    def federation_stale_count(self):
        now = time.monotonic()
        return sum(1 for r in self._list()
                   if r.fed.ts is not None and self._replica_stale(r, now))

    def federated(self):
        """The fleet-federated view of worker-internal metrics.

        ``replicas`` carries each replica's effective
        counters/gauges/histograms (base + current incarnation — a dead
        replica's counters freeze and never decrease, the PR-7
        retired-accumulator contract at fleet scope) plus snapshot age
        and a ``stale`` flag; ``summed`` is the fleet total (stale
        replicas' *gauges* drop out of the sum — a dead worker has no
        queue depth — while their counters stay in)."""
        now = time.monotonic()
        out: dict = {"replicas": {}, "summed": {
            "counters": {}, "gauges": {}, "histograms": {}}}
        summed = out["summed"]
        for r in self._list():
            counters, gauges, hists = r.fed.effective()
            if r.fed.ts is None and not counters and not gauges:
                continue            # never pulled: nothing to report yet
            stale = self._replica_stale(r, now)
            out["replicas"][r.idx] = {
                "counters": counters, "gauges": gauges,
                "histograms": hists,
                "age_s": round(now - r.fed.ts, 3)
                if r.fed.ts is not None else None,
                "stale": stale,
                "incarnation": r.fed.incarnation,
            }
            for k, v in counters.items():
                summed["counters"][k] = summed["counters"].get(k, 0) + v
            if not stale:
                for k, v in gauges.items():
                    summed["gauges"][k] = summed["gauges"].get(k, 0) + v
            for k, h in hists.items():
                summed["histograms"][k] = _hist_sum(
                    summed["histograms"].get(k, _hist_zero()), h)
        return out

    def _federate(self, r):
        """Pull one worker's /statusz telemetry snapshot (monitor
        thread, budgeted by ``federate_s``)."""
        now = time.monotonic()
        if r.state != "up" or not r.port or now < r.fed_next:
            return
        r.fed_next = now + self.federate_s   # even on failure: no hot loop
        try:
            # pooled keep-alive pull: a fleet's monitor threads used to
            # pay a fresh TCP connect per replica per heartbeat
            from .transport import shared_pool
            t = min(2.0, max(0.5, self.federate_s))
            payload = shared_pool().get_json(
                r.url + "/statusz", connect_timeout_s=t, read_timeout_s=t)
            snap = payload.get("telemetry") or {}
            r.fed.absorb(snap, time.monotonic(), r.spawn_count)
            _inc("federation_pulls")
        except Exception:           # noqa: BLE001 — monitor must survive
            _inc("federation_errors")

    # -- commands ----------------------------------------------------------
    def swap(self, idx, payload, timeout=60.0):
        """Apply a weight payload on one (drained) replica and wait for
        its ack.  The engine re-reads params per dispatch, so the swap
        serves immediately — no recompile, no restart."""
        r = next((x for x in self._list() if x.idx == idx), None)
        if r is None:
            raise ServiceUnavailableError(
                f"replica {idx} is no longer in the fleet")
        if r.state != "up" or r.conn is None:
            raise ServiceUnavailableError(
                f"replica {idx} not up (state={r.state})")
        while not r.replies.empty():     # drop stale replies
            try:
                r.replies.get_nowait()
            except _queue.Empty:
                break
        try:
            r.conn.send(("swap", payload))
        except (OSError, BrokenPipeError) as e:
            raise ServiceUnavailableError(
                f"replica {idx} pipe dead: {e!r}") from None
        try:
            kind, detail = r.replies.get(timeout=timeout)
        except _queue.Empty:
            raise ServiceUnavailableError(
                f"replica {idx} swap timed out after {timeout:.0f}s") \
                from None
        if kind != "swapped":
            raise MXNetError(f"replica {idx} swap failed: {detail}")
        _inc("swaps")

    # -- internals ---------------------------------------------------------
    def _spawn(self, r):
        # the outgoing incarnation's federated totals freeze into the
        # base BEFORE the replacement's zeros can arrive — the scraped
        # fleet counters never decrease across a restart
        r.fed.fold()
        parent, child = self._ctx.Pipe(duplex=True)
        proc = self._ctx.Process(
            target=_replica_main,
            args=(self.spec, child, r.idx, r.spawn_count),
            name=f"mxnet-tpu-replica-{r.idx}", daemon=True)
        proc.start()
        child.close()
        now = time.monotonic()
        with self._lock:
            r.proc, r.conn = proc, parent
            r.spawn_count += 1
            r.state = "starting"
            r.port = None
            r.init_classification = None
            r.suspect = False
            r.respawn_at = None
            r.last_hb_ts = now
            r.last_progress_ts = now
            r.last_completed = -1

    def _monitor_loop(self):
        while not self._stop.is_set():
            for r in self._list():
                try:
                    self._pump(r)
                    self._check(r)
                except Exception:   # noqa: BLE001 — monitor must survive
                    pass
            self._stop.wait(0.05)

    def _federate_loop(self):
        while not self._stop.is_set():
            for r in self._list():
                try:
                    self._federate(r)
                except Exception:   # noqa: BLE001 — federator must survive
                    pass
            self._stop.wait(0.05)

    def _pump(self, r):
        """Drain the replica's pipe (the monitor is the only reader)."""
        if r.conn is None:
            return
        while True:
            try:
                if not r.conn.poll(0):
                    return
                msg = r.conn.recv()
            except (EOFError, OSError, BrokenPipeError):
                return               # liveness check handles the corpse
            kind = msg[0]
            now = time.monotonic()
            if kind == "ready":
                with self._lock:
                    r.port = msg[1]["port"]
                    r.state = "up"
                    r.consecutive_failures = 0
                    r.last_hb_ts = now
                    r.last_progress_ts = now
                r.ready_event.set()
            elif kind == "hb":
                hb = msg[1]
                with self._lock:
                    r.last_hb = hb
                    r.last_hb_ts = now
                    busy = hb["queue_depth"] > 0 or hb["inflight"] > 0
                    if hb["completed"] > r.last_completed or not busy:
                        r.last_progress_ts = now
                        r.last_completed = hb["completed"]
            elif kind == "init_error":
                with self._lock:
                    r.last_error = msg[1]
                    r.init_classification = msg[2]
            else:                    # swapped/swap_error/stopped/pong
                r.replies.put((kind, msg[1] if len(msg) > 1 else None))

    def _check(self, r):
        if r.state in ("failed", "stopped", "stopping"):
            return
        now = time.monotonic()
        if r.state == "down":
            # the dead process was already accounted by _handle_exit —
            # only the respawn clock matters now
            if r.respawn_at is not None and now >= r.respawn_at \
                    and not self._stop.is_set():
                with self._lock:
                    # the monitor iterates a snapshot: a replica the
                    # autoscaler removed since must never be respawned
                    # (that would leak an unsupervised worker)
                    if r not in self._replicas or r.state != "down":
                        return
                    r.restarts += 1
                _inc("restarts")
                self._spawn(r)
            return
        if r.proc is not None and not r.proc.is_alive():
            self._handle_exit(r, now)
            return
        if r.state != "up":
            return
        stale_hb = r.last_hb_ts is not None and \
            now - r.last_hb_ts > max(self.hang_grace_s,
                                     3 * self.spec.heartbeat_s)
        stalled = r.last_progress_ts is not None and \
            now - r.last_progress_ts > self.hang_grace_s
        probe_failed = False
        if r.suspect:
            r.suspect = False
            probe_failed = not self._probe(r)
        if stale_hb or stalled or probe_failed:
            _inc("hangs")
            with self._lock:
                r.last_error = ("hung: stale_hb" if stale_hb else
                                "hung: no progress" if stalled else
                                "hung: healthz probe failed")
            try:
                r.proc.kill()
            except Exception:       # noqa: BLE001
                pass
            r.proc.join(2.0)
            self._handle_exit(r, now)

    @staticmethod
    def _probe(r, timeout=1.0):
        if not r.port:
            return False
        try:
            from .transport import shared_pool
            resp = shared_pool().request(r.url + "/healthz",
                                         connect_timeout_s=timeout,
                                         read_timeout_s=timeout)
            return resp.status == 200
        except Exception:           # noqa: BLE001
            return False

    def _handle_exit(self, r, now):
        from .. import faults as _faults
        rc = r.proc.exitcode if r.proc is not None else None
        with self._lock:
            r.last_exit = rc
            r.port = None
            classification = r.init_classification or \
                _faults.classify_exit(rc)
            r.consecutive_failures += 1
            if classification == _faults.PERMANENT or \
                    r.consecutive_failures > self.max_restarts:
                r.state = "failed"
                r.ready_event.set()   # unblock a start() waiting on it
                return
            r.state = "down"
            delay = min(self.max_backoff_s,
                        self.backoff_s * (2 ** (r.consecutive_failures - 1)))
            import random as _pyrandom
            r.respawn_at = now + delay * (0.5 + _pyrandom.random())


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------
class _CircuitBreaker:
    """One replica's circuit-breaker state (internal to :class:`Router`;
    every transition happens under the router lock).

    closed → open on ``failures`` consecutive dispatch failures OR a
    success-latency EWMA above ``max(latency_floor_ms, ratio × fleet-
    median EWMA)`` (a *uniformly* slow fleet never latency-trips — there
    is nowhere better to route); open → half-open after ``open_s``,
    admitting exactly ONE probe request; probe success closes (EWMA and
    counters reset so the breaker re-learns), failure or a
    still-over-threshold probe latency re-opens.  The point: a
    slow-but-alive replica is routed around within milliseconds of the
    EWMA crossing, instead of waiting out heartbeat/hang-grace clocks.
    """

    __slots__ = ("state", "consecutive_failures", "ewma_ms", "samples",
                 "opened_at", "probe_inflight", "trips", "trip_reason")

    #: EWMA smoothing for per-replica success latency (~last 6 requests)
    ALPHA = 0.3

    def __init__(self):
        self.state = "closed"            # closed|open|half_open
        self.consecutive_failures = 0
        self.ewma_ms = None
        self.samples = 0
        self.opened_at = None
        self.probe_inflight = False
        self.trips = 0
        self.trip_reason = None

    def observe(self, ms):
        self.samples += 1
        self.ewma_ms = ms if self.ewma_ms is None else \
            self.ALPHA * ms + (1.0 - self.ALPHA) * self.ewma_ms

    def trip(self, now, reason):
        self.state = "open"
        self.opened_at = now
        self.probe_inflight = False
        self.trips += 1
        self.trip_reason = reason

    def close(self):
        self.state = "closed"
        self.consecutive_failures = 0
        self.ewma_ms = None              # re-learn the healthy latency
        self.samples = 0
        self.probe_inflight = False
        self.trip_reason = None

    def status(self, now):
        return {"state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "ewma_ms": round(self.ewma_ms, 3)
                if self.ewma_ms is not None else None,
                "trips": self.trips,
                "trip_reason": self.trip_reason,
                "open_age_s": round(now - self.opened_at, 3)
                if self.opened_at is not None and self.state != "closed"
                else None}


class _HedgeTask:
    """A hedge marker on the dispatch queue: run ONE extra attempt of
    ``req`` against a replica it is not already trying (first response
    wins via the future's settle guard)."""

    __slots__ = ("req",)

    def __init__(self, req):
        self.req = req


class _FleetRequest:
    __slots__ = ("payload", "future", "t_submit", "deadline", "idempotent",
                 "tried", "attempts", "trace", "t_submit_wall_us",
                 "queue_span_done", "retry_t0_us", "defer_spool",
                 "finished", "hedge_armed", "hedged", "current_key")

    def __init__(self, payload, deadline_ms, idempotent, trace=None):
        self.payload = payload
        self.future = Future()
        self.t_submit = time.monotonic()
        self.deadline = (self.t_submit + deadline_ms / 1000.0
                         if deadline_ms is not None else None)
        self.idempotent = bool(idempotent)
        self.tried = set()
        self.attempts = 0
        self.trace = trace if trace is not None else _telemetry.NULL_TRACE
        self.t_submit_wall_us = _telemetry._wall_us() if self.trace else 0
        self.queue_span_done = False
        self.retry_t0_us = None
        self.defer_spool = False
        self.finished = False        # _finish() ran (outstanding released)
        self.hedge_armed = False     # registered with the hedge scheduler
        self.hedged = False          # a hedge attempt was dispatched
        self.current_key = None      # replica the primary is trying now


def _settle(fut, result=None, exc=None):
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
        return True
    except InvalidStateError:
        return False


class Router:
    """Least-loaded request router over a replica fleet.

    ``backends`` is a :class:`ReplicaSupervisor` (live endpoints follow
    restarts automatically) or a static list of base URLs (tests,
    externally-managed replicas).  ``submit()`` mirrors the batcher's
    contract — a ``Future`` per request — with three fleet-level
    behaviors on top:

    * **shedding**: more than ``max_outstanding`` accepted-but-unresolved
      requests fast-rejects with ``QueueFullError`` (the aggregate
      queue-depth SLO; env ``MXNET_FLEET_MAX_OUTSTANDING``);
    * **deadline propagation**: the *remaining* budget rides to the
      chosen replica as its ``deadline_ms`` and bounds the HTTP timeout,
      so a re-dispatched request never gets a fresh clock;
    * **transparent retry**: failures that provably did not execute
      (connection refused, 429, 503, an injected ``router.dispatch``
      transient) re-dispatch to the next least-loaded replica for any
      request; a connection that died *after* the request was sent
      (reset/timeout — the replica may have executed it) re-dispatches
      only when the request was submitted ``idempotent`` (the default),
      else fails — never double-execute non-idempotent work.
    """

    def __init__(self, backends, max_outstanding=None, max_redispatch=8,
                 request_timeout_s=30.0, dispatch_threads=None,
                 cooldown_s=0.5, no_replica_timeout_s=30.0,
                 breakers=None, breaker_failures=None,
                 breaker_latency_ms=None, breaker_latency_ratio=3.0,
                 breaker_open_s=None, hedging=None, hedge_rate=None,
                 hedge_min_samples=32):
        from ..util import getenv
        if isinstance(backends, ReplicaSupervisor):
            self._sup = backends
            self._static = None
            n_hint = len(backends._replicas)
        else:
            self._sup = None
            self._static = {i: str(u).rstrip("/")
                            for i, u in enumerate(backends)}
            if not self._static:
                raise MXNetError("Router needs at least one backend")
            n_hint = len(self._static)
        self.max_outstanding = int(
            max_outstanding if max_outstanding is not None
            else getenv("MXNET_FLEET_MAX_OUTSTANDING"))
        self.max_redispatch = int(max_redispatch)
        self.request_timeout_s = float(request_timeout_s)
        self.cooldown_s = float(cooldown_s)
        self.no_replica_timeout_s = float(no_replica_timeout_s)
        # -- circuit breakers (docs/SERVING.md "Circuit breakers") ---------
        self.breakers_enabled = bool(
            breakers if breakers is not None
            else getenv("MXNET_FLEET_BREAKER"))
        self.breaker_failures = int(
            breaker_failures if breaker_failures is not None
            else getenv("MXNET_FLEET_BREAKER_FAILURES"))
        self.breaker_latency_ms = float(
            breaker_latency_ms if breaker_latency_ms is not None
            else getenv("MXNET_FLEET_BREAKER_LATENCY_MS"))
        self.breaker_latency_ratio = float(breaker_latency_ratio)
        self.breaker_open_s = float(
            breaker_open_s if breaker_open_s is not None
            else getenv("MXNET_FLEET_BREAKER_OPEN_S"))
        self._breakers: dict = {}
        # -- hedged dispatch (docs/SERVING.md "Hedged dispatch") -----------
        self.hedging_enabled = bool(
            hedging if hedging is not None else getenv("MXNET_FLEET_HEDGE"))
        self.hedge_rate = float(
            hedge_rate if hedge_rate is not None
            else getenv("MXNET_FLEET_HEDGE_RATE"))
        self.hedge_min_samples = int(hedge_min_samples)
        import collections as _collections
        self._lat_ring = _collections.deque(maxlen=256)
        self._lat_since_p95 = 0
        self._hedge_delay_cached = None
        # token bucket enforcing hedges <= hedge_rate x accepted requests:
        # each accepted submit deposits `hedge_rate` tokens, each hedge
        # spends one — the budget can never amplify an overload
        self._hedge_tokens = 0.0
        self._hedge_token_cap = max(2.0, 32.0 * self.hedge_rate)
        self._hedge_heap: list = []
        self._hedge_seq = 0
        self._hedge_cv = threading.Condition()
        self._hedge_thread = None
        self._n_threads = int(dispatch_threads if dispatch_threads
                              else max(4, 2 * n_hint))
        self._q: _queue.Queue = _queue.Queue()
        self._lock = threading.Lock()
        self._inflight: dict = {}
        self._inflight_cv = threading.Condition(self._lock)
        self._cooldown: dict = {}
        # key -> drain count: re-entrant so a rolling swap and an
        # autoscaler scale-down draining the same replica compose
        # instead of re-admitting each other's drains
        self._draining: dict = {}
        self._outstanding = 0
        self._threads = []
        self._stopped = threading.Event()
        # -- replica leases (docs/SERVING.md "Zero-hop data path") ---------
        # the control-plane side of direct dispatch: a monotonic epoch
        # that revokes every outstanding lease table when the fleet
        # changes shape (drain, forget, breaker trip, endpoint churn)
        self.lease_ttl_s = float(getenv("MXNET_LEASE_TTL_S"))
        self._lease_epoch = 1
        self._lease_seen = None         # endpoint set at last grant
        _live_routers.add(self)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._threads:
            return self
        self._stopped.clear()
        for i in range(self._n_threads):
            t = threading.Thread(target=self._loop,
                                 name=f"mxnet-tpu-router-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        self._hedge_thread = threading.Thread(
            target=self._hedge_loop, name="mxnet-tpu-router-hedge",
            daemon=True)
        self._hedge_thread.start()
        return self

    def stop(self, timeout=10.0):
        with self._lock:     # pairs with submit(): no put after drain
            self._stopped.set()
        with self._hedge_cv:
            self._hedge_cv.notify_all()
        self._q.put(None)
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.1, deadline - time.monotonic()))
        self._threads = []
        if self._hedge_thread is not None:
            self._hedge_thread.join(2.0)
            self._hedge_thread = None
        while True:                      # fail whatever never dispatched
            try:
                req = self._q.get_nowait()
            except _queue.Empty:
                break
            if isinstance(req, _FleetRequest):
                self._fail(req, EngineClosedError(
                    f"router stopped{_tr(req.trace)}"))
        _telemetry.flush_trace_spool()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    @property
    def outstanding(self):
        return self._outstanding

    # -- client side -------------------------------------------------------
    def submit(self, inputs, deadline_ms=None, idempotent=True, trace=None,
               defer_spool=False):
        """Enqueue one single-example request; returns a ``Future``.

        ``idempotent=False`` opts the request out of orphan re-dispatch:
        if the connection to a replica dies after the request was sent,
        the future fails instead of risking double execution.

        ``trace`` continues an incoming request's
        :class:`~mxnet_tpu.telemetry.RequestTrace` (the RouterServer
        passes the wire's ``trace`` field through); when tracing is on
        and no context is given, the router mints one — so in-process
        ``submit()`` callers get traced too.  The trace id is stable for
        the request's life; only the attempt counter moves on
        retry/re-route.  ``defer_spool=True`` suppresses the router-role
        spool at completion — the caller owns it (the RouterServer
        spools after serializing the reply so the ``router_reply`` span
        makes the record).
        """
        if self._stopped.is_set() or not self._threads:
            raise EngineClosedError("router not running (call start())")
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        payload = {"inputs": [encode_array(onp.asarray(a)) for a in inputs]}
        if trace is None:
            trace = _telemetry.new_trace()
        req = _FleetRequest(payload, deadline_ms, idempotent, trace=trace)
        req.defer_spool = bool(defer_spool)
        if req.trace:
            tid = req.trace.trace_id
            _telemetry.inflight_add(tid)
            req.future.add_done_callback(
                lambda _f, _tid=tid: _telemetry.inflight_remove(_tid))
        with self._lock:
            # re-check + enqueue under the lock: stop() flips _stopped
            # under the same lock before draining, so a request can
            # never slip into the queue after the drain (its future
            # would otherwise hang forever)
            if self._stopped.is_set():
                exc = EngineClosedError(f"router stopped{_tr(req.trace)}")
                _settle(req.future, exc=exc)   # fires inflight_remove
                raise exc
            if self._outstanding >= self.max_outstanding:
                _inc("shed")
                exc = QueueFullError(
                    f"fleet at capacity ({self.max_outstanding} "
                    f"outstanding){_tr(req.trace)}")
                # settle before raising so the rejected request leaves
                # the in-flight trace registry; an admission reject is
                # an always-keep spool rule (`shed`)
                _settle(req.future, exc=exc)
                if req.trace:
                    req.trace.mark("shed")
                    if not req.defer_spool:
                        _telemetry.maybe_spool(req.trace, 0.0,
                                               role="router")
                raise exc
            self._outstanding += 1
            # hedge-budget deposit: the budget is denominated in
            # accepted requests, so the hedge rate is bounded by
            # construction (docs/SERVING.md "Hedged dispatch")
            self._hedge_tokens = min(self._hedge_token_cap,
                                     self._hedge_tokens + self.hedge_rate)
            self._q.put(req)
        return req.future

    def predict(self, inputs, deadline_ms=None, idempotent=True,
                timeout=None, trace=None):
        return self.submit(inputs, deadline_ms=deadline_ms,
                           idempotent=idempotent,
                           trace=trace).result(timeout=timeout)

    # -- replica leases (docs/SERVING.md "Zero-hop data path") -------------
    def lease_bump(self, reason=""):
        """Revoke every outstanding lease table: direct-dispatch clients
        see the epoch move on their next refresh and rebuild their
        credit state.  Called on drain, forget, breaker trips, endpoint
        churn, and autoscaler decisions."""
        with self._lock:
            self._lease_epoch += 1
        _inc("lease_epoch_bumps")
        if reason:
            _log.debug("lease epoch bumped (%s)", reason)

    def lease_table(self):
        """The zero-hop control-plane grant: live, breaker-closed,
        non-draining replicas with per-replica admission credits carved
        from the router's remaining ``max_outstanding`` headroom.  An
        empty grant (no credits anywhere) IS the backpressure signal —
        clients must use the routed path until the router re-grants."""
        eps = self._live_endpoints()
        now = time.monotonic()
        with self._lock:
            avail = {}
            for key, url in eps.items():
                b = self._breakers.get(key)
                if b is not None and b.state != "closed" and \
                        self.breakers_enabled:
                    continue
                avail[key] = url
            seen = frozenset(avail.items())
            if self._lease_seen is not None and seen != self._lease_seen:
                # endpoint churn (scale-up, restart on a new port):
                # revoke so clients re-read the fresh table promptly
                self._lease_epoch += 1
                _inc("lease_epoch_bumps")
            self._lease_seen = seen
            headroom = max(0, self.max_outstanding - self._outstanding)
            per = min(32, headroom // max(1, len(avail))) if avail else 0
            table = {str(key): {"url": url, "credits": per,
                                "inflight": self._inflight.get(key, 0)}
                     for key, url in avail.items()}
            epoch = self._lease_epoch
        _inc("lease_grants")
        return {"epoch": epoch, "ttl_s": self.lease_ttl_s,
                "replicas": table}

    # -- rollout -----------------------------------------------------------
    def drain(self, key, timeout=60.0):
        """Stop dispatching to one replica and wait for its router-side
        in-flight count to reach zero (in-flight work *finishes* — the
        zero-drop half of the rollout contract).  Drains are counted, so
        two concurrent drainers of the same replica (a rolling swap
        racing an autoscaler scale-down) compose: the replica re-admits
        only after BOTH call :meth:`admit`."""
        _inc("drains")
        self.lease_bump("drain")
        with self._inflight_cv:
            self._draining[key] = self._draining.get(key, 0) + 1
            deadline = time.monotonic() + timeout
            while self._inflight.get(key, 0) > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._admit_locked(key)
                    raise ServingError(
                        f"drain of replica {key} timed out with "
                        f"{self._inflight.get(key, 0)} in flight")
                self._inflight_cv.wait(remaining)

    def _admit_locked(self, key):
        n = self._draining.get(key, 0) - 1
        if n > 0:
            self._draining[key] = n
        else:
            self._draining.pop(key, None)

    def admit(self, key):
        with self._lock:
            self._admit_locked(key)

    def forget(self, key):
        """Drop a removed replica's router-side state (breaker, cooldown,
        drain count) — called after an autoscaler scale-down so a
        departed replica cannot linger in breaker/drain views."""
        with self._lock:
            self._breakers.pop(key, None)
            self._cooldown.pop(key, None)
            self._draining.pop(key, None)
            if not self._inflight.get(key):
                self._inflight.pop(key, None)
        self.lease_bump("forget")

    def rolling_swap(self, payload, drain_timeout=60.0, swap_timeout=60.0):
        """Zero-drop rolling weight swap across the whole fleet.

        One replica at a time: drain (stop dispatching, finish
        in-flight), hot-swap weights in the worker, re-admit.  The rest
        of the fleet keeps absorbing traffic, so no accepted request is
        ever dropped.  Returns a per-replica report.

        Composes with a concurrent autoscaler: a replica the scale-down
        path removes mid-rollout is *skipped* (there is nothing left to
        swap and its in-flight work was already drained zero-drop),
        replicas the autoscaler adds after the rollout snapshot start
        with the new weights only if the spec's model factory serves
        them — swap again or roll by spec for mixed fleets.  Drains are
        counted, so the two paths draining the same replica never
        re-admit each other's drain."""
        if self._sup is None:
            raise MXNetError(
                "rolling_swap needs a supervisor-backed Router")
        report = []
        for key in sorted(self._sup.endpoints()):
            t0 = time.monotonic()
            if key not in self._sup.endpoints():
                report.append({"replica": key, "skipped": "removed"})
                continue
            self.drain(key, timeout=drain_timeout)
            try:
                try:
                    self._sup.swap(key, payload, timeout=swap_timeout)
                except ServiceUnavailableError:
                    # skip ONLY a replica the autoscaler actually REMOVED
                    # from the fleet (gone from supervisor status, not
                    # merely down/restarting — a crashed replica would
                    # respawn with the OLD weights, so that failure must
                    # surface, exactly as before this round)
                    if key in self._sup.status():
                        raise
                    report.append({"replica": key, "skipped": "removed"})
                    continue
            finally:
                self.admit(key)
            report.append({"replica": key,
                           "wall_s": round(time.monotonic() - t0, 3)})
        _inc("rollouts")
        return report

    # -- observability -----------------------------------------------------
    def status(self):
        now = time.monotonic()
        with self._lock:
            st = {
                "outstanding": self._outstanding,
                "draining": sorted(self._draining),
                "inflight": {k: v for k, v in self._inflight.items() if v},
                "breakers": {k: b.status(now)
                             for k, b in self._breakers.items()},
                "hedge": {
                    "enabled": self.hedging_enabled,
                    "delay_ms": self._hedge_delay_cached
                    if len(self._lat_ring) >= self.hedge_min_samples
                    else None,
                    "rate_cap": self.hedge_rate,
                    "tokens": round(self._hedge_tokens, 3),
                },
            }
        st["supervisor"] = self._sup.status() if self._sup else None
        st["endpoints"] = self._endpoints()
        auto = getattr(self, "_autoscaler", None)
        auto = auto() if auto is not None else None
        st["autoscaler"] = auto.status() if auto is not None else None
        return st

    # -- dispatcher --------------------------------------------------------
    def _endpoints(self):
        if self._sup is not None:
            return self._sup.endpoints()
        return dict(self._static)

    def _live_endpoints(self):
        now = time.monotonic()
        eps = self._endpoints()
        with self._lock:
            return {k: u for k, u in eps.items()
                    if k not in self._draining
                    and self._cooldown.get(k, 0.0) <= now}

    def _finish(self, req):
        # idempotent: with hedging, the primary path and a winning hedge
        # can both reach a terminal call — outstanding releases once
        with self._inflight_cv:
            if req.finished:
                return
            req.finished = True
            self._outstanding -= 1
            self._inflight_cv.notify_all()

    def _spool(self, req, shed=False):
        if not req.trace:
            return
        if shed:
            req.trace.mark("shed")
        if req.defer_spool:
            # the RouterServer spools this trace itself AFTER the reply
            # is serialized, so the router_reply span makes the record
            return
        _telemetry.maybe_spool(
            req.trace, (time.monotonic() - req.t_submit) * 1000.0,
            role="router")

    def _fail(self, req, exc, shed=False):
        if _settle(req.future, exc=exc):
            _inc("shed" if shed else "errors")
            self._spool(req, shed=shed)
        self._finish(req)

    def _complete(self, req, outs):
        won = _settle(req.future, outs if len(outs) > 1 else outs[0])
        if won:
            _inc("completed")
            _observe_latency((time.monotonic() - req.t_submit) * 1000.0)
            self._spool(req)
        self._finish(req)
        return won

    def _loop(self):
        while True:
            req = self._q.get()
            if req is None:
                self._q.put(None)    # propagate shutdown to siblings
                return
            if isinstance(req, _HedgeTask):
                try:
                    self._process_hedge(req.req)
                except Exception:    # noqa: BLE001 — hedge is best-effort
                    pass
                continue
            try:
                self._process(req)
            except Exception as e:   # noqa: BLE001 — never kill the loop
                self._fail(req, e)

    def _process(self, req):
        if req.trace and not req.queue_span_done:
            # router_queue: submit -> a dispatcher thread picked it up
            req.queue_span_done = True
            t = _telemetry._wall_us()
            req.trace.add_span("router_queue", req.t_submit_wall_us,
                               max(0.0, t - req.t_submit_wall_us))
        while True:
            if req.future.done():
                # cancelled, or a hedged attempt already answered —
                # first response wins, this path just releases
                self._finish(req)
                return
            now = time.monotonic()
            if req.deadline is not None and now >= req.deadline:
                self._fail(req, DeadlineExceededError(
                    "deadline expired in fleet routing "
                    f"({(now - req.t_submit) * 1000:.1f} ms since "
                    f"submit){_tr(req.trace)}"), shed=True)
                return
            cands = self._live_endpoints()
            allowed = self._breaker_filter(cands)
            untried = {k: u for k, u in allowed.items()
                       if k not in req.tried}
            if not untried:
                if allowed:
                    # every dispatchable replica failed this cycle:
                    # start a new one (with a small pause so a
                    # fleet-wide brownout doesn't hot-loop)
                    req.tried.clear()
                    untried = allowed
                    time.sleep(min(0.05 * max(1, req.attempts), 0.5))
                else:
                    # nothing dispatchable right now: replicas down
                    # (restart window), draining, or breaker-blocked
                    # until the next half-open window — wait, bounded
                    # by the deadline or the no-replica budget
                    if req.deadline is None and \
                            now - req.t_submit > self.no_replica_timeout_s:
                        self._fail(req, ServiceUnavailableError(
                            "no dispatchable replica within "
                            f"{self.no_replica_timeout_s:.0f}s"
                            f"{_tr(req.trace)}"))
                        return
                    if self._stopped.is_set():
                        self._fail(req, EngineClosedError(
                            f"router stopped{_tr(req.trace)}"))
                        return
                    time.sleep(0.02 if cands else 0.05)
                    continue
            with self._lock:
                # least-loaded pick + breaker admission (half-open probe
                # reservation) under ONE lock so two dispatchers can
                # never share a probe slot
                now2 = time.monotonic()
                key = None
                for k in sorted(untried, key=lambda k:
                                (self._inflight.get(k, 0), k)):
                    if self._breaker_admit_locked(k, now2):
                        key = k
                        break
                if key is not None:
                    self._inflight[key] = self._inflight.get(key, 0) + 1
            if key is None:
                time.sleep(0.02)     # lost the probe race: wait a beat
                continue
            req.current_key = key
            self._maybe_arm_hedge(req)
            if req.trace:
                # the trace's attempt counter IS the router's dispatch
                # counter: a re-dispatch bumps it, the id never changes
                req.trace.attempt = req.attempts
                if req.retry_t0_us is not None:
                    req.trace.add_span("router_retry", req.retry_t0_us,
                                       max(0.0, _telemetry._wall_us()
                                           - req.retry_t0_us))
                    req.retry_t0_us = None
            status, value = self._attempt(key, untried[key], req)
            if status == "ok":
                self._complete(req, value)
                return
            if status == "final":
                self._fail(req, value)
                return
            # retryable: "safe" (never executed) for any request;
            # "orphan" (may have executed) only for idempotent ones
            if status == "orphan":
                _inc("orphans")
                if not req.idempotent:
                    self._fail(req, ServiceUnavailableError(
                        "replica connection died mid-request and the "
                        f"request is not idempotent: {value!r}"
                        f"{_tr(req.trace)}"))
                    return
                req.trace.mark("rerouted")
            else:
                req.trace.mark("retried")
            req.attempts += 1
            req.tried.add(key)
            if req.attempts > self.max_redispatch:
                self._fail(req, value if isinstance(value, Exception)
                           else ServiceUnavailableError(
                               f"gave up after {req.attempts} dispatch "
                               f"attempts{_tr(req.trace)}"))
                return
            _inc("retries")
            if req.trace:
                req.retry_t0_us = _telemetry._wall_us()
            _log.info(
                "%s replica %s%s; re-dispatching (attempt %d): %r",
                "orphaned on" if status == "orphan" else "failed safe on",
                key, _tr(req.trace), req.attempts, value)

    def _attempt(self, key, url, req, hedged=False):
        """One dispatch attempt (the caller already incremented the
        replica's in-flight count under the router lock).  Releases
        in-flight accounting, feeds the breaker and the hedge-delay
        latency ring, records the ``router_dispatch`` trace span
        (``hedge=True`` on hedged attempts — same trace id, the span
        says which attempt raced), and returns ``_dispatch_once``'s
        ``(status, value)``."""
        t0 = time.monotonic()
        t_d0 = _telemetry._wall_us() if req.trace else 0
        try:
            status, value = self._dispatch_once(key, url, req)
        except Exception as e:       # noqa: BLE001 — must still release
            status, value = "final", e
        finally:
            with self._inflight_cv:
                n = self._inflight.get(key, 1) - 1
                if n > 0:
                    self._inflight[key] = n
                else:
                    # zero entries drop out: an autoscaled fleet's
                    # never-reused indices must not accumulate forever
                    self._inflight.pop(key, None)
                self._inflight_cv.notify_all()
        ms = (time.monotonic() - t0) * 1000.0
        if status == "ok":
            self._observe_attempt_latency(ms)
            self._breaker_success(key, ms)
        elif status in ("safe", "orphan"):
            self._breaker_failure(key)
        else:
            self._breaker_neutral(key)
        if req.trace:
            attrs = {"replica": key, "outcome": status}
            if hedged:
                attrs["hedge"] = True
            req.trace.add_span("router_dispatch", t_d0,
                               max(0.0, _telemetry._wall_us() - t_d0),
                               **attrs)
        return status, value

    # -- circuit breakers --------------------------------------------------
    def _breaker_filter(self, cands):
        """Subset of ``cands`` a new dispatch may consider right now
        (closed breakers, plus open/half-open ones whose probe window
        is available — admission itself happens at pick time)."""
        if not self.breakers_enabled or not self._breakers:
            return dict(cands)
        now = time.monotonic()
        with self._lock:
            return {k: u for k, u in cands.items()
                    if self._breaker_can_locked(k, now)}

    def _breaker_can_locked(self, key, now):
        if not self.breakers_enabled:
            return True
        b = self._breakers.get(key)
        if b is None or b.state == "closed":
            return True
        if b.state == "open":
            return b.opened_at is not None and \
                now - b.opened_at >= self.breaker_open_s
        return not b.probe_inflight          # half_open

    def _breaker_admit_locked(self, key, now):
        """Admission at pick time (router lock held): closed passes;
        an elapsed open breaker transitions to half-open and reserves
        THIS request as its single probe; a half-open breaker admits
        only while no probe is in flight."""
        if not self.breakers_enabled:
            return True
        b = self._breakers.get(key)
        if b is None or b.state == "closed":
            return True
        if b.state == "open":
            if b.opened_at is not None and \
                    now - b.opened_at >= self.breaker_open_s:
                b.state = "half_open"
                b.probe_inflight = True
                _inc("breaker_probes")
                return True
            return False
        if not b.probe_inflight:             # half_open
            b.probe_inflight = True
            _inc("breaker_probes")
            return True
        return False

    def _latency_threshold_locked(self, key):
        """EWMA trip threshold for ``key``: ``max(latency floor,
        ratio x median of the OTHER replicas' EWMAs)`` — None when no
        other replica has enough samples (a single replica, or a
        uniformly cold fleet, never latency-trips: there is nowhere
        better to route)."""
        others = [b.ewma_ms for k, b in self._breakers.items()
                  if k != key and b.ewma_ms is not None and b.samples >= 3]
        if not others:
            return None
        others.sort()
        med = others[len(others) // 2]
        return max(self.breaker_latency_ms,
                   self.breaker_latency_ratio * med)

    def _breaker_success(self, key, ms):
        if not self.breakers_enabled:
            # breakers toggled off mid-flight: still release any probe
            # reservation, or re-enabling would find the replica's
            # half-open slot stranded and never admit it again
            self._breaker_neutral(key)
            return
        closed = tripped = False
        with self._lock:
            b = self._breakers.setdefault(key, _CircuitBreaker())
            b.consecutive_failures = 0
            b.observe(ms)
            now = time.monotonic()
            thr = self._latency_threshold_locked(key)
            if b.state == "half_open":
                b.probe_inflight = False
                if thr is not None and ms > thr:
                    # alive but still slow: the probe answered, the
                    # replica stays routed around
                    b.trip(now, "latency")
                    tripped = True
                else:
                    b.close()
                    closed = True
            elif b.state == "closed" and thr is not None and \
                    b.samples >= 5 and b.ewma_ms > thr:
                b.trip(now, "latency")
                tripped = True
        if tripped:
            _inc("breaker_trips")
            self.lease_bump("breaker_trip")
            _log.warning("breaker OPEN for replica %s: latency ewma "
                         "%.1f ms (sample %.1f ms) over threshold", key,
                         self._breakers[key].ewma_ms or 0.0, ms)
        if closed:
            _inc("breaker_closes")
            _log.info("breaker closed for replica %s after successful "
                      "probe (%.1f ms)", key, ms)

    def _breaker_failure(self, key):
        if not self.breakers_enabled:
            self._breaker_neutral(key)   # release a mid-toggle probe
            return
        tripped = reason = None
        with self._lock:
            b = self._breakers.setdefault(key, _CircuitBreaker())
            b.consecutive_failures += 1
            now = time.monotonic()
            if b.state == "half_open":
                b.trip(now, "probe_failed")
                tripped, reason = True, "probe_failed"
            elif b.state == "closed" and \
                    b.consecutive_failures >= self.breaker_failures:
                b.trip(now, "failures")
                tripped, reason = True, \
                    f"{b.consecutive_failures} consecutive failures"
        if tripped:
            _inc("breaker_trips")
            self.lease_bump("breaker_trip")
            _log.warning("breaker OPEN for replica %s: %s", key, reason)

    def _breaker_neutral(self, key):
        """Release a probe without a verdict (the attempt failed for
        reasons that say nothing about the replica, e.g. the request's
        own deadline)."""
        with self._lock:
            b = self._breakers.get(key)
            if b is not None and b.state == "half_open":
                b.probe_inflight = False

    def breaker_status(self):
        """Per-replica breaker state (``/statusz`` fleet section, crash
        reports, tests)."""
        now = time.monotonic()
        with self._lock:
            return {k: b.status(now) for k, b in self._breakers.items()}

    def set_resilience(self, breakers=None, hedging=None):
        """Runtime toggle for the breaker/hedging machinery."""
        if breakers is not None:
            self.breakers_enabled = bool(breakers)
        if hedging is not None:
            self.hedging_enabled = bool(hedging)

    # -- hedged dispatch ---------------------------------------------------
    def _observe_attempt_latency(self, ms):
        with self._lock:
            self._lat_ring.append(ms)
            self._lat_since_p95 += 1
            # recompute on EVERY sample until the ring is big enough to
            # trust (a p95 cached off the first sample would otherwise
            # serve as the hedge delay for the next 16 — hedging after
            # one fast request's latency fires into replicas the p95
            # says to wait out), then amortize to every 16th
            if self._lat_since_p95 >= 16 or \
                    len(self._lat_ring) <= 2 * self.hedge_min_samples:
                self._lat_since_p95 = 0
                xs = sorted(self._lat_ring)
                p95 = xs[int(0.95 * (len(xs) - 1))]
                self._hedge_delay_cached = min(
                    max(p95, 1.0), self.request_timeout_s * 500.0)

    def hedge_delay_ms(self):
        """The current p95-derived hedge delay, or None while hedging is
        off / the latency ring has too few samples to trust."""
        if not self.hedging_enabled or \
                len(self._lat_ring) < self.hedge_min_samples:
            return None
        return self._hedge_delay_cached

    def _maybe_arm_hedge(self, req):
        """Register an idempotent request with the hedge scheduler just
        before its primary dispatch: if it is still unresolved after the
        p95-derived delay, one extra attempt races a different replica
        (budget permitting).  At most one hedge per request."""
        if req.hedge_armed or not req.idempotent or \
                not self.hedging_enabled:
            return
        d = self.hedge_delay_ms()
        if d is None:
            return
        import heapq
        req.hedge_armed = True
        # no cv notify here: the scheduler wakes on a short cadence
        # anyway, so arming costs one lock + heap push on the dispatch
        # hot path instead of a cross-thread wakeup per request (the
        # fleet_resilience_overhead record gates this bookkeeping)
        with self._hedge_cv:
            self._hedge_seq += 1
            heapq.heappush(self._hedge_heap,
                           (time.monotonic() + d / 1000.0,
                            self._hedge_seq, req))

    def _hedge_loop(self):
        """Single scheduler thread: pops due hedge registrations and —
        when the request is still unresolved and the hedge-rate budget
        allows — enqueues ONE extra dispatch for a dispatcher thread to
        run.  First response wins; the budget makes hedge amplification
        impossible under overload."""
        import heapq
        while not self._stopped.is_set():
            with self._hedge_cv:
                if not self._hedge_heap:
                    # short-cadence poll: arming never signals (hot-path
                    # cost), so a hedge registered into an empty heap
                    # fires at most one tick late
                    self._hedge_cv.wait(0.005)
                    continue
                fire_at = self._hedge_heap[0][0]
                now = time.monotonic()
                if fire_at > now:
                    self._hedge_cv.wait(min(fire_at - now, 0.05))
                    continue
                _fa, _seq, req = heapq.heappop(self._hedge_heap)
            if req.future.done() or req.finished or req.hedged:
                continue
            # budget + counters are settled in _process_hedge once a
            # replica is actually picked — a hedge that never dispatches
            # must neither count as one nor burn a token
            self._q.put(_HedgeTask(req))

    def _process_hedge(self, req):
        """Run the hedged attempt: one dispatch to a replica the request
        is not already trying.  A win settles the future (the primary
        path sees ``future.done()`` and just releases); a loss marks the
        replica tried and leaves the primary's retry loop in charge."""
        if req.future.done() or req.finished or req.hedged:
            return
        now = time.monotonic()
        if req.deadline is not None and now >= req.deadline:
            return
        cands = self._live_endpoints()
        exclude = set(req.tried)
        if req.current_key is not None:
            exclude.add(req.current_key)
        with self._lock:
            # budget gate BEFORE pick: fleet/hedges counts DISPATCHED
            # hedges only, an undispatched one must not burn a token,
            # and a denied one must not strand a half-open probe slot
            have_budget = self._hedge_tokens >= 1.0
            key = None
            if have_budget:
                now2 = time.monotonic()
                for k in sorted((k for k in cands if k not in exclude),
                                key=lambda k:
                                (self._inflight.get(k, 0), k)):
                    if self._breaker_admit_locked(k, now2):
                        key = k
                        break
                if key is not None:
                    self._hedge_tokens -= 1.0
                    self._inflight[key] = self._inflight.get(key, 0) + 1
        if not have_budget:
            _inc("hedge_denied")
            return
        if key is None:
            return                   # nowhere distinct to hedge to
        req.hedged = True
        _inc("hedges")
        status, value = self._attempt(key, cands[key], req, hedged=True)
        if status == "ok":
            if self._complete(req, value):
                _inc("hedge_wins")
        else:
            req.tried.add(key)       # the primary loop skips this one

    def _dispatch_once(self, key, url, req):
        """One HTTP attempt against one replica.  Returns
        ``("ok", outputs) | ("safe"|"orphan"|"final", exception)``."""
        from .. import faults as _faults
        try:
            _faults.point("router.dispatch")
        except Exception as e:       # noqa: BLE001 — injected
            if _faults.classify(e) == _faults.TRANSIENT:
                return "safe", e     # nothing was sent
            return "final", e
        # wire-level chaos on the router->replica connection
        # (docs/RESILIENCE.md net.* registry): a faulted connect never
        # sent anything, so it is always a "safe" re-route — blackhole
        # already slept its partition window inside the point
        act = _faults.wire_point("net.connect")
        if act is not None:
            self._suspect(key)
            return "safe", act.client_error()
        _inc("dispatches")
        body = dict(req.payload)
        if req.trace:
            # trace context rides the wire like deadline_ms: same id,
            # current attempt — the replica's spans land under both
            body["trace"] = req.trace.wire()
        timeout = self.request_timeout_s
        if req.deadline is not None:
            remaining_ms = (req.deadline - time.monotonic()) * 1000.0
            if remaining_ms <= 0:
                return "final", DeadlineExceededError(
                    "deadline expired before dispatch")
            body["deadline_ms"] = remaining_ms
            timeout = remaining_ms / 1000.0 + 1.0
        import json
        from .transport import shared_pool
        try:
            # pooled keep-alive dispatch: the per-dispatch TCP connect
            # used to dominate loopback latency.  The pool's raw
            # exception surface keeps the safe/orphan classification
            # below intact (refused connect = safe; a reused-idle race
            # with zero response bytes is replayed inside the pool —
            # nothing executed, so the replay cannot double-run work).
            resp = shared_pool().request(
                url + "/predict", "POST",
                json.dumps(body).encode("utf-8"),
                {"Content-Type": "application/json"},
                connect_timeout_s=min(timeout, 5.0),
                read_timeout_s=timeout)
            if resp.status != 200:
                detail = resp.data[:200].decode("utf-8", "replace")
                if resp.status == 429:   # replica queue full: not enqueued
                    return "safe", QueueFullError(detail)
                if resp.status == 503:   # draining/stopping: not executed
                    self._suspect(key)
                    return "safe", ServiceUnavailableError(detail)
                if resp.status == 504:
                    return "final", DeadlineExceededError(detail)
                return "final", ServingError(
                    f"HTTP {resp.status}: {detail}")
            out = json.loads(resp.data)
        except Exception as e:       # noqa: BLE001 — connection level
            self._suspect(key)
            root = e.reason if isinstance(e, urllib.error.URLError) \
                and e.reason is not None else e
            if isinstance(root, ConnectionRefusedError):
                return "safe", e     # never reached the replica
            return "orphan", e       # sent: the replica may have run it
        if req.trace and out.get("trace"):
            # fold the replica-side breakdown in (its spans arrive
            # already tagged replica:<pid>) — the response the client
            # gets carries the whole cross-process waterfall
            req.trace.merge(out["trace"].get("spans"))
        outs = tuple(decode_array(o) for o in out["outputs"])
        return "ok", outs

    def _suspect(self, key):
        with self._lock:
            self._cooldown[key] = time.monotonic() + self.cooldown_s
        if self._sup is not None:
            self._sup.mark_suspect(key)

    # -- generative serving ------------------------------------------------
    def _gen_pick(self, tried):
        """Breaker-aware least-loaded pick for one generation dispatch —
        the ``_process`` pick idiom without the queue (generation is
        synchronous: the caller's thread follows the stream).  Returns
        ``(key, url)`` with the replica's in-flight count already
        incremented (release with :meth:`_gen_release`), or ``None``
        when nothing is dispatchable right now."""
        cands = self._live_endpoints()
        allowed = self._breaker_filter(cands)
        untried = {k: u for k, u in allowed.items() if k not in tried}
        if not untried:
            if allowed:
                # every dispatchable replica was tried this generation:
                # start a fresh cycle (the _process idiom)
                tried.clear()
                untried = allowed
            else:
                return None
        with self._lock:
            now = time.monotonic()
            key = None
            for k in sorted(untried, key=lambda k:
                            (self._inflight.get(k, 0), k)):
                if self._breaker_admit_locked(k, now):
                    key = k
                    break
            if key is not None:
                self._inflight[key] = self._inflight.get(key, 0) + 1
        if key is None:
            return None
        return key, untried[key]

    def _gen_release(self, key):
        with self._inflight_cv:
            n = self._inflight.get(key, 1) - 1
            if n > 0:
                self._inflight[key] = n
            else:
                self._inflight.pop(key, None)
            self._inflight_cv.notify_all()

    def generate_stream(self, tokens, max_new_tokens=32, eos_id=None,
                        trace=None, timeout_s=None):
        """Route one generation to a replica and stream its tokens; the
        generator's ``return`` value is the final result dict.

        A generation stream is NOT idempotent mid-flight: the replica
        holds the KV cache, and tokens the caller already consumed
        cannot be unsent.  The router therefore re-routes ONLY failures
        before the first token (prefill never ran, or its cache died
        with the replica — nothing observable happened), bounded by
        ``max_redispatch``; a death after the first token raises
        :class:`GenerationStreamBroken` with the trace id and the tokens
        delivered so far.  Generations are never hedged — two replicas
        decoding the same prompt would burn fleet-wide KV slots for one
        answer.
        """
        from .client import ServingClient
        if self._stopped.is_set() or not self._threads:
            raise EngineClosedError("router not running (call start())")
        if trace is None:
            trace = _telemetry.new_trace()
        _inc("gen_requests")
        t_submit = time.monotonic()
        tried: set = set()
        attempts = 0
        last_exc: "Exception|None" = None

        def _terminal(mark=None):
            if trace:
                if mark:
                    trace.mark(mark)
                _telemetry.maybe_spool(
                    trace, (time.monotonic() - t_submit) * 1000.0,
                    role="router")

        while True:
            picked = self._gen_pick(tried)
            if picked is None:
                if self._stopped.is_set():
                    _terminal()
                    raise EngineClosedError(f"router stopped{_tr(trace)}")
                if time.monotonic() - t_submit > self.no_replica_timeout_s:
                    _terminal()
                    raise ServiceUnavailableError(
                        "no dispatchable replica for generation within "
                        f"{self.no_replica_timeout_s:.0f}s{_tr(trace)}")
                time.sleep(0.05)
                continue
            key, url = picked
            if trace:
                trace.attempt = attempts
            client = ServingClient(
                url, timeout_s=(timeout_s if timeout_s is not None
                                else self.request_timeout_s))
            got_first = False
            outcome = "ok"
            t_d0 = _telemetry._wall_us() if trace else 0
            t0 = time.monotonic()
            try:
                it = client.generate_stream(
                    tokens, max_new_tokens=max_new_tokens, eos_id=eos_id,
                    trace=trace)
                while True:
                    try:
                        tok = next(it)
                    except StopIteration as stop:
                        result = dict(stop.value or {})
                        break
                    got_first = True
                    yield tok
                self._breaker_success(
                    key, (time.monotonic() - t0) * 1000.0)
                _terminal()
                return result
            except GenerationStreamBroken as e:
                # the replica died holding the stream's KV cache
                self._breaker_failure(key)
                self._suspect(key)
                if got_first or e.tokens:
                    outcome = "broken"
                    _inc("gen_broken")
                    _terminal(mark="stream_broken")
                    raise
                outcome = "safe"     # headers only: nothing consumed
                last_exc = e
            except QueueFullError as e:
                # replica admission reject: never entered the batch
                outcome = "safe"
                self._breaker_failure(key)
                last_exc = e
            except ServiceUnavailableError as e:
                outcome = "safe"
                self._breaker_failure(key)
                self._suspect(key)
                last_exc = e
            except (DeadlineExceededError, ServingError):
                # a definitive server answer: re-routing cannot help
                outcome = "final"
                self._breaker_neutral(key)
                _terminal()
                raise
            except Exception as e:   # noqa: BLE001 — connection level
                self._breaker_failure(key)
                self._suspect(key)
                if got_first:
                    # client-side surprise after tokens flowed: same
                    # non-reroutable contract as a wire-reported break
                    outcome = "broken"
                    _inc("gen_broken")
                    _terminal(mark="stream_broken")
                    raise GenerationStreamBroken(
                        f"stream failed after first token: {e!r}"
                        f"{_tr(trace)}",
                        trace_id=trace.trace_id if trace else None) from e
                outcome = "safe"     # request may never have been seen
                last_exc = e
            finally:
                self._gen_release(key)
                if trace:
                    trace.add_span(
                        "router_generate", t_d0,
                        max(0.0, _telemetry._wall_us() - t_d0),
                        replica=key, outcome=outcome)
            # prefill-only re-route: nothing reached the caller yet
            tried.add(key)
            attempts += 1
            if attempts > self.max_redispatch:
                _terminal()
                raise last_exc if isinstance(last_exc, Exception) else \
                    ServiceUnavailableError(
                        f"generation gave up after {attempts} dispatch "
                        f"attempts{_tr(trace)}")
            _inc("gen_reroutes")
            if trace:
                trace.mark("rerouted")
            _log.info("generation failed safe on replica %s%s; "
                      "re-routing (attempt %d): %r",
                      key, _tr(trace), attempts, last_exc)

    def generate(self, tokens, max_new_tokens=32, eos_id=None, trace=None,
                 midstream="fail", timeout_s=None):
        """Route one generation and block for the whole completion.

        ``midstream`` picks the policy for a stream that breaks AFTER
        tokens were produced (the non-re-routable case): ``"fail"``
        (default) re-raises the typed :class:`GenerationStreamBroken`;
        ``"restart"`` resubmits the WHOLE generation from the prompt to
        another replica — an explicit, caller-chosen retry that may
        return a different continuation, which is only coherent here
        because no partial tokens were handed out (for the streaming
        path that choice belongs to the consumer, so
        :meth:`generate_stream` always fails typed).  Restarts are
        bounded by ``max_redispatch``."""
        if midstream not in ("fail", "restart"):
            raise ValueError(
                f"midstream must be 'fail' or 'restart', got {midstream!r}")
        if trace is None:
            trace = _telemetry.new_trace()
        restarts = 0
        while True:
            toks = []
            it = self.generate_stream(
                tokens, max_new_tokens=max_new_tokens, eos_id=eos_id,
                trace=trace, timeout_s=timeout_s)
            try:
                while True:
                    try:
                        toks.append(next(it))
                    except StopIteration as stop:
                        result = dict(stop.value or {})
                        result.setdefault("tokens", toks)
                        if restarts:
                            result["restarts"] = restarts
                        return result
            except GenerationStreamBroken:
                restarts += 1
                if midstream != "restart" or restarts > self.max_redispatch:
                    raise
                _inc("gen_restarts")
                if trace:
                    trace.mark("gen_restart")


# ---------------------------------------------------------------------------
# federated exposition
# ---------------------------------------------------------------------------
def _fed_prom_name(prefix, name):
    # `serving/completed` under prefix `worker` -> the worker-labeled
    # prom family — one sanitizer with the registry
    # (telemetry.MetricsRegistry._prom_name)
    return _telemetry.MetricsRegistry._prom_name(
        f"{prefix}/{name.replace('/', '_')}")


def _fed_fmt(v):
    return _telemetry.MetricsRegistry._fmt(v)


def federation_prometheus_text(supervisor):
    """Prometheus text for the fleet-federated worker metrics
    (docs/OBSERVABILITY.md "Fleet metric federation"):

    * ``mxnet_worker_<subsystem>_<name>{replica="i"}`` — per-replica
      counters and gauges (a dead replica's counters freeze at their
      last value and never decrease);
    * ``mxnet_worker_stale{replica="i"}`` / ``..._snapshot_age_seconds``
      — the staleness guard, so a frozen series is distinguishable from
      a quiet one;
    * ``mxnet_workers_<subsystem>_<name>`` — the fleet sum (histograms
      are exposed in summed form only).

    Appended to the registry's own exposition by the RouterServer's
    ``/metrics``."""
    fed = supervisor.federated()
    lines = []
    per = fed["replicas"]
    names: dict = {}                    # prom name -> (type, samples)
    for idx in sorted(per):
        rep = per[idx]
        for name, v in sorted(rep["counters"].items()):
            names.setdefault(_fed_prom_name("worker", name),
                             ("counter", []))[1].append((idx, v))
        for name, v in sorted(rep["gauges"].items()):
            names.setdefault(_fed_prom_name("worker", name),
                             ("gauge", []))[1].append((idx, v))
    for pn in sorted(names):
        typ, samples = names[pn]
        lines.append(f"# TYPE {pn} {typ}")
        for idx, v in samples:
            lines.append(f'{pn}{{replica="{idx}"}} {_fed_fmt(v)}')
    if per:
        lines.append("# TYPE mxnet_worker_stale gauge")
        for idx in sorted(per):
            lines.append(f'mxnet_worker_stale{{replica="{idx}"}} '
                         f'{1 if per[idx]["stale"] else 0}')
        lines.append("# TYPE mxnet_worker_snapshot_age_seconds gauge")
        for idx in sorted(per):
            age = per[idx]["age_s"]
            if age is not None:
                lines.append(
                    f'mxnet_worker_snapshot_age_seconds{{replica="{idx}"}}'
                    f" {_fed_fmt(age)}")
    summed = fed["summed"]
    for name, v in sorted(summed["counters"].items()):
        pn = _fed_prom_name("workers", name)
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {_fed_fmt(v)}")
    for name, v in sorted(summed["gauges"].items()):
        pn = _fed_prom_name("workers", name)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {_fed_fmt(v)}")
    for name, h in sorted(summed["histograms"].items()):
        pn = _fed_prom_name("workers", name)
        lines.append(f"# TYPE {pn} histogram")
        for le, cum in h.get("buckets", []):
            # pulled snapshots spell +Inf as a string (RFC 8259 statusz)
            le_s = le if isinstance(le, str) else _fed_fmt(float(le))
            lines.append(f'{pn}_bucket{{le="{le_s}"}} {int(cum)}')
        lines.append(f"{pn}_sum {_fed_fmt(float(h.get('sum', 0.0)))}")
        lines.append(f"{pn}_count {int(h.get('count', 0))}")
    return "\n".join(lines) + "\n" if lines else ""


def crash_report_payload():
    """The crash report's ``fleet`` section (schema 5,
    docs/RESILIENCE.md): per-router breaker states and hedge
    bookkeeping, the fleet counters (breaker/hedge/scale included), and
    every live autoscaler's target + last-K decision log — so a fleet
    crash report answers "which replicas were routed around, was
    hedging active, and what did the autoscaler just do".  Federates
    per-replica through the same ``/statusz`` path as every other
    section."""
    with _fleet_lock:
        counters = dict(_fleet_counters)
    routers = []
    for r in list(_live_routers):
        try:
            routers.append({
                "breakers": r.breaker_status(),
                "outstanding": r.outstanding,
                "hedge_delay_ms": r.hedge_delay_ms(),
                "hedging_enabled": r.hedging_enabled,
                "breakers_enabled": r.breakers_enabled,
            })
        except Exception:           # noqa: BLE001 — report must build
            pass
    autoscalers = []
    for a in list(_live_autoscalers):
        try:
            autoscalers.append(a.status())
        except Exception:           # noqa: BLE001 — report must build
            pass
    return {"schema": 1, "counters": counters, "routers": routers,
            "autoscalers": autoscalers}


# ---------------------------------------------------------------------------
# HTTP front-end
# ---------------------------------------------------------------------------
class RouterServer:
    """Loopback HTTP front over a :class:`Router` (the fleet twin of
    ``ModelServer``): ``POST /predict`` (same wire format, plus an
    ``"idempotent"`` flag), ``GET /metrics`` (Prometheus — ``fleet/*``
    included), ``GET /statusz`` (telemetry snapshot + per-replica fleet
    status), ``GET /healthz`` (503 until at least one replica serves)."""

    _DEFAULT_RESULT_TIMEOUT_S = 30.0

    def __init__(self, router, host="127.0.0.1", port=0):
        import json
        from http.server import BaseHTTPRequestHandler
        from .http import _FleetHTTPServer, try_reply

        outer = self

        class _Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 keep-alive with an idle reaper and TCP_NODELAY —
            # one wire policy with the replica front
            # (serving.http._Handler)
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True

            def setup(self):
                self.timeout = getattr(self.server, "idle_timeout_s",
                                       None)
                if self.timeout is None:
                    from ..util import getenv as _getenv
                    self.timeout = float(_getenv("MXNET_HTTP_IDLE_S"))
                super().setup()

            def log_message(self, fmt, *args):   # noqa: A003
                pass

            def _drain_body(self):
                # under keep-alive an unread POST body would be parsed
                # as the NEXT request on the persistent connection
                length = int(self.headers.get("Content-Length") or 0)
                if length > 0:
                    try:
                        self.rfile.read(length)
                    except OSError:
                        self.close_connection = True

            def _reply(self, code, payload, **kw):
                body = json.dumps(payload, **kw).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if getattr(self.server, "draining", False):
                    self.send_header("Connection", "close")
                self.end_headers()
                self.wfile.write(body)

            def _try_reply(self, code, payload, **kw):
                # a deadline-capped client hanging up mid-wait is
                # routine: the request's spool/metrics bookkeeping must
                # survive the dead socket — ONE policy with the replica
                # front (serving.http.try_reply)
                try_reply(self, code, payload, **kw)

            def do_GET(self):                    # noqa: N802
                if self.path == "/healthz":
                    up = len(outer.router._live_endpoints())
                    self._reply(200 if up else 503,
                                {"status": "ok" if up else "degraded",
                                 "replicas_up": up})
                elif self.path == "/metrics":
                    # the registry's own exposition PLUS the federated
                    # worker metrics the supervisor has been pulling —
                    # the whole fleet in one scrape
                    text = _telemetry.prometheus_text()
                    if outer.router._sup is not None:
                        text += federation_prometheus_text(
                            outer.router._sup)
                    body = text.encode("utf-8")
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/statusz":
                    payload = _telemetry.statusz_payload()
                    fleet = outer.router.status()
                    if outer.router._sup is not None:
                        fleet["federation"] = \
                            outer.router._sup.federated()
                    # federated histograms carry +Inf bounds: spell them
                    # as strings so the body stays RFC 8259 JSON
                    payload["fleet"] = _telemetry._json_safe(fleet)
                    self._reply(200, payload, default=str)
                elif self.path == "/leases":
                    # the zero-hop control plane: replica endpoints +
                    # admission credits for direct-dispatch clients
                    # (docs/SERVING.md "Zero-hop data path")
                    self._reply(200, outer.router.lease_table())
                else:
                    self._reply(404, {"error": "not_found",
                                      "path": self.path})

            def do_POST(self):                   # noqa: N802
                if self.path != "/predict":
                    self._drain_body()
                    self._reply(404, {"error": "not_found",
                                      "path": self.path})
                    return
                t_wall0 = _telemetry._wall_us() \
                    if _telemetry.tracing_enabled() else 0
                trace = _telemetry.NULL_TRACE
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    obj = json.loads(self.rfile.read(length))
                    # continue the client's trace context, or mint one
                    # for an untraced request when tracing is on
                    trace = _telemetry.continue_trace(obj.get("trace")) \
                        or _telemetry.new_trace()
                    inputs = tuple(decode_array(o) for o in obj["inputs"])
                    deadline_ms = obj.get("deadline_ms")
                    if deadline_ms is not None:
                        deadline_ms = float(deadline_ms)
                    idempotent = bool(obj.get("idempotent", True))
                    if trace:
                        # wire + accept-queue gap (client sent_us ->
                        # this handler) then the decode itself
                        trace.accept_span("router_accept", t_wall0)
                        trace.add_span("router_parse", t_wall0,
                                       _telemetry._wall_us() - t_wall0,
                                       bytes=length)
                except Exception as e:           # noqa: BLE001
                    self._reply(400, {"error": "bad_request",
                                      "detail": str(e)})
                    return
                t0 = time.perf_counter()

                def spool():
                    # the router-role spool is deferred to here (after
                    # the reply) so the router_reply span, and every
                    # error outcome, make the record
                    if trace:
                        _telemetry.maybe_spool(
                            trace,
                            (_telemetry._wall_us() - t_wall0) / 1000.0,
                            role="router")

                try:
                    fut = outer.router.submit(inputs,
                                              deadline_ms=deadline_ms,
                                              idempotent=idempotent,
                                              trace=trace,
                                              defer_spool=True)
                    wait_s = (deadline_ms / 1000.0 + 1.0) \
                        if deadline_ms is not None \
                        else outer._DEFAULT_RESULT_TIMEOUT_S
                    out = fut.result(timeout=wait_s)
                except QueueFullError as e:
                    self._try_reply(429, {"error": "queue_full",
                                      "detail": str(e)})
                    spool()
                    return
                except DeadlineExceededError as e:
                    self._try_reply(504, {"error": "deadline_exceeded",
                                      "detail": str(e)})
                    spool()
                    return
                except (ServiceUnavailableError, EngineClosedError) as e:
                    self._try_reply(503, {"error": "unavailable",
                                      "detail": str(e)})
                    spool()
                    return
                except (_FutTimeout, TimeoutError):
                    fut.cancel()
                    self._try_reply(504, {"error": "result_timeout",
                                      "detail": "result timeout"
                                      + _tr(trace)})
                    spool()
                    return
                except Exception as e:           # noqa: BLE001
                    self._try_reply(500, {"error": "model_error",
                                      "detail": str(e)})
                    spool()
                    return
                outs = out if isinstance(out, tuple) else (out,)
                t_ser0 = _telemetry._wall_us() if trace else 0
                encoded = [encode_array(o) for o in outs]
                resp = {"outputs": encoded,
                        "latency_ms": round(
                            (time.perf_counter() - t0) * 1000.0, 3)}
                if trace:
                    trace.add_span("router_reply", t_ser0,
                                   _telemetry._wall_us() - t_ser0)
                    resp["trace"] = trace.response_payload(
                        proc=f"router:{os.getpid()}")
                self._try_reply(200, resp)
                spool()

        self.router = router
        self._httpd = _FleetHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.block_on_close = False
        self._httpd.draining = False
        self._httpd.idle_timeout_s = None
        self._thread = None
        self._closed = False

    @property
    def host(self):
        return self._httpd.server_address[0]

    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def start(self):
        if self._closed:
            raise EngineClosedError(
                "RouterServer stopped; construct a new one")
        self.router.start()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name="mxnet-tpu-router-http", daemon=True)
            self._thread.start()
        return self

    def stop(self):
        self._closed = True
        # drain-aware close: replies from here on tell keep-alive peers
        # to stop parking connections against a dying front-end
        self._httpd.draining = True
        if self._thread is not None:
            self._httpd.shutdown()
            self._thread.join(5.0)
            self._thread = None
        self._httpd.server_close()
        self.router.stop()
        # router.stop() resolved every outstanding future (handlers have
        # replied); what remains are idle keep-alive peers — sever them
        # so no handler thread outlives the front-end
        self._httpd.sever_idle()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
