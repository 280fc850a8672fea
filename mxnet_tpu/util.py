"""Runtime config (reference: env-var layer ``dmlc::GetEnv`` +
``docs/.../env_var.md``, SURVEY.md §5.6).

A typed registry of MXNET_* environment variables.  Unknown vars are
tolerated (reference behavior); reads go through ``getenv`` so the effective
config is introspectable via ``config()``.
"""
from __future__ import annotations

import os

__all__ = ["getenv", "setenv", "config", "register_env", "get_gpu_count",
           "set_np", "reset_np", "is_np_array"]

_ENV_REGISTRY: dict[str, tuple[type, object, str]] = {}


def register_env(name, typ, default, doc=""):
    _ENV_REGISTRY[name] = (typ, default, doc)
    return name


# the env surface, mirroring the reference's key vars where they still mean
# something on this architecture (the CUDA-specific ones are intentionally
# absent — no mem-pool knobs, XLA owns memory):
register_env("MXNET_ENGINE_TYPE", str, "ThreadedEngine",
             "ThreadedEngine (async jax dispatch), LazyEngine (record eager "
             "op chains and flush them as fused jit programs at "
             "materialization boundaries — docs/ENGINE.md) or NaiveEngine "
             "(synchronous: block after every op — deterministic debugging, "
             "reference src/engine/naive_engine.cc)")
register_env("MXNET_ENGINE_BULK_SIZE", int, 16,
             "max ops per lazy segment before an automatic flush "
             "(LazyEngine / engine.bulk scopes; reference "
             "MXNET_ENGINE_BULK_EXEC_MAX_NODE_TRAIN)")
register_env("MXNET_STEP_CAPTURE", bool, True,
             "whole-step lazy capture: when the lazy engine is recording "
             "(LazyEngine / engine.bulk), autograd.record() continues the "
             "pending segment instead of flushing it, backward() extends "
             "it with the tape-walk VJP ops and gluon.Trainer.step() "
             "splices the fused update in — the full eager "
             "forward/backward/update step compiles as ONE cached "
             "executable at the first materialization boundary "
             "(docs/ENGINE.md).  0 restores the PR-3 behavior where "
             "record() entry is a flush boundary")
register_env("MXNET_STEP_DONATE", bool, True,
             "ONE buffer-donation policy for fused training steps: the "
             "captured gluon step donates its param/optimizer-state "
             "buffers into the sealed whole-step executable (updated "
             "values land in the old buffers' memory — in-place update "
             "semantics, docs/ENGINE.md 'Memory-lean fused steps'), and "
             "SPMDTrainer(donate_params=None) resolves here.  0 disables "
             "donation everywhere the policy is consulted")
register_env("MXNET_STEP_CAPTURE_MAX_OPS", int, 100000,
             "op cap for segments that carry autograd tape ops (whole-step "
             "capture); replaces MXNET_ENGINE_BULK_SIZE for those segments "
             "— a training step must not be chopped into bulk-sized "
             "fragments")
register_env("MXNET_OP_CACHE", bool, True,
             "per-op executable cache: eager non-recording ops run through "
             "a jit-compiled program keyed by (fun, static kwargs, input "
             "avals) instead of re-tracing per call")
register_env("MXNET_OP_CACHE_PERSIST_MIN_MS", float, 50.0,
             "op/segment compiles at least this slow also persist into the "
             "mxnet_tpu.compile ProgramCache for cross-process warm starts "
             "(cheaper ones recompile faster than a disk round-trip)")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
             "compat flag; XLA always bulks (whole-program compile)")
register_env("MXNET_EXEC_BULK_EXEC_INFERENCE", bool, True, "compat flag")
register_env("MXNET_ENFORCE_DETERMINISM", bool, False,
             "disable non-deterministic reductions (maps to XLA "
             "deterministic ops flag)")
register_env("MXNET_PROFILER_AUTOSTART", bool, False,
             "start the profiler at import")
register_env("MXNET_KVSTORE_REDUCTION_NTHREADS", int, 4, "compat flag")
register_env("MXNET_TEST_SEED", int, -1, "fixed test seed (-1 = random)")
register_env("MXNET_BARRIER_TIMEOUT", float, 0.0,
             "seconds before global_barrier declares a peer dead and aborts "
             "this worker (0 = wait forever); launcher --barrier-timeout")
register_env("MXNET_SAFE_ACCUMULATION", bool, True,
             "accumulate bf16 reductions in fp32 (XLA default on TPU)")
register_env("MXNET_COMPILE_CACHE", bool, True,
             "master switch for the persistent compilation cache and the "
             "AOT program-artifact index (mxnet_tpu.compile)")
register_env("MXNET_COMPILE_CACHE_MAX_BYTES", int, 2 << 30,
             "size cap of the on-disk ProgramCache index (LRU eviction "
             "past it); XLA's own cache is capped by jax's "
             "JAX_COMPILATION_CACHE_MAX_SIZE")
register_env("MXNET_COMPILE_AOT_WORKERS", int, 0,
             "thread count for parallel AOT bucket compilation "
             "(0 = min(jobs, cpu count))")
register_env("MXNET_COMPILE_PASSES", str, "",
             "comma-separated rewrite passes applied to captured programs "
             "before AOT compile/persistence, e.g. 'dce,int8_residency' "
             "(mxnet_tpu.compile.passes; empty = no pipeline, programs "
             "serve unrewritten)")
register_env("MXNET_FAULT_PLAN", str, "",
             "deterministic fault-injection plan, e.g. "
             "'trainer.step@7:transient,checkpoint.save@2:crash' "
             "(grammar + fault-point registry: docs/RESILIENCE.md)")
register_env("MXNET_FAULT_SEED", int, 0,
             "seed for probabilistic fault-plan entries (@pFLOAT): a "
             "given seed reproduces the exact same fault schedule")
register_env("MXNET_FAULT_HANG_S", float, 30.0,
             "default sleep for 'hang'-kind injected faults when the plan "
             "entry carries no explicit duration")
register_env("MXNET_DEVICE_PREFETCH", int, 2,
             "DevicePrefetcher depth: how many batches the staging thread "
             "places onto the device sharding ahead of the consuming step "
             "(docs/IO.md); 2 hides one upload while capping the device "
             "memory pinned in flight")
register_env("MXNET_STEP_WATCHDOG_S", float, 0.0,
             "default ResilientStep watchdog: seconds before a training "
             "step is declared hung and a crash report is dumped "
             "(0 = disabled)")
register_env("MXNET_TELEMETRY", bool, True,
             "master switch for mxnet_tpu.telemetry step-phase spans and "
             "the flight-recorder ring (docs/OBSERVABILITY.md); the "
             "metrics registry itself stays readable either way — 0 only "
             "stops span recording")
register_env("MXNET_TELEMETRY_RING", int, 4096,
             "flight-recorder capacity in spans (~6 spans per training "
             "step); the ring backs telemetry.flight_recorder_payload and "
             "the crash report's telemetry section")
register_env("MXNET_MEMORY", bool, True,
             "device-memory observability (mxnet_tpu.memory): live-array "
             "census registration + span-boundary memory sampling "
             "(docs/OBSERVABILITY.md memory/* tables); the per-program "
             "ledger is never gated — 0 only stops census/sampling")
register_env("MXNET_MEMORY_RING", int, 4096,
             "memory sample-ring capacity (one sample per telemetry span "
             "boundary); backs the crash report's memory.samples tail and "
             "tools/memory_report.py --leaks")
register_env("MXNET_FLEET_HEARTBEAT_S", float, 0.5,
             "replica-fleet heartbeat interval: how often each worker "
             "process reports liveness/progress to the ReplicaSupervisor "
             "(docs/SERVING.md fleet section)")
register_env("MXNET_FLEET_HANG_GRACE_S", float, 10.0,
             "how long a replica may show no progress while busy (or no "
             "heartbeat at all) before the supervisor declares it hung, "
             "kills it and restarts it")
register_env("MXNET_FLEET_MAX_RESTARTS", int, 5,
             "consecutive failed replica starts before the supervisor "
             "marks a replica failed instead of restarting it (the "
             "counter resets every time the replica comes up)")
register_env("MXNET_FLEET_MAX_OUTSTANDING", int, 512,
             "fleet-level admission control: Router.submit fast-rejects "
             "(QueueFullError) when this many accepted requests are "
             "queued + in flight across the fleet — the aggregate "
             "queue-depth SLO knob")
register_env("MXNET_FLEET_BREAKER", bool, True,
             "per-replica circuit breakers in the fleet Router "
             "(docs/SERVING.md): consecutive-failure or latency-EWMA "
             "trips open the breaker and the replica is routed around "
             "within milliseconds instead of heartbeat granularity; 0 "
             "disables breakers (every live replica stays routable)")
register_env("MXNET_FLEET_BREAKER_FAILURES", int, 3,
             "consecutive dispatch failures against one replica before "
             "its breaker opens")
register_env("MXNET_FLEET_BREAKER_LATENCY_MS", float, 50.0,
             "latency floor for the breaker's EWMA trip: a replica's "
             "success-latency EWMA must exceed BOTH this floor and "
             "ratio x the fleet-median EWMA (Router(breaker_latency_"
             "ratio=), default 3.0) to trip — a uniformly slow fleet "
             "never trips on latency")
register_env("MXNET_FLEET_BREAKER_OPEN_S", float, 1.0,
             "how long an open breaker blocks dispatch before admitting "
             "one half-open probe request (probe success closes the "
             "breaker, failure re-opens it)")
register_env("MXNET_FLEET_HEDGE", bool, True,
             "hedged dispatch for idempotent fleet requests "
             "(docs/SERVING.md): once a request has been in flight for "
             "the p95-derived hedge delay, re-issue it to a different "
             "replica and take the first response; 0 disables hedging")
register_env("MXNET_FLEET_HEDGE_RATE", float, 0.1,
             "hard hedge-rate budget: hedged attempts may never exceed "
             "this fraction of accepted requests (token bucket), so "
             "hedging cannot amplify an overload")
register_env("MXNET_FLEET_SCALE_MIN", int, 1,
             "Autoscaler lower bound on the replica count "
             "(docs/SERVING.md autoscaler recipe)")
register_env("MXNET_FLEET_SCALE_MAX", int, 8,
             "Autoscaler upper bound on the replica count")
register_env("MXNET_FLEET_SCALE_INTERVAL_S", float, 1.0,
             "Autoscaler policy-tick cadence: how often the federated "
             "fleet/worker gauges are evaluated")
register_env("MXNET_FLEET_SCALE_COOLDOWN_S", float, 10.0,
             "Autoscaler cooldown after any scale action before the "
             "next one may fire (lets the fleet absorb the change "
             "instead of oscillating)")
register_env("MXNET_FLEET_SCALE_QUEUE_HIGH", float, 4.0,
             "Autoscaler scale-UP threshold: federated queued requests "
             "per up replica above this (for up_ticks consecutive "
             "ticks) grows the fleet")
register_env("MXNET_FLEET_SCALE_QUEUE_LOW", float, 0.5,
             "Autoscaler scale-DOWN threshold: federated queued "
             "requests per up replica below this (and p99 healthy, for "
             "down_ticks consecutive ticks) shrinks the fleet through "
             "the zero-drop drain path")
register_env("MXNET_TRANSPORT_POOL", int, 8,
             "serving transport: max idle keep-alive connections parked "
             "per endpoint in the shared ConnectionPool (0 = no parking, "
             "every request dials a fresh connection — the legacy wire; "
             "docs/SERVING.md zero-hop section)")
register_env("MXNET_LEASE_TTL_S", float, 2.0,
             "zero-hop serving: how long a direct-dispatch client may "
             "act on a replica lease table before re-fetching it from "
             "RouterServer /leases — the router-mediated backpressure "
             "refresh interval (docs/SERVING.md)")
register_env("MXNET_HTTP_IDLE_S", float, 60.0,
             "serving HTTP servers: idle keep-alive connections are "
             "closed after this many seconds without a request (the "
             "bounded idle-connection reaper on ModelServer and "
             "RouterServer)")
register_env("MXNET_KV_SLOTS", int, 8,
             "generation KV-cache slots = the max in-flight decode batch "
             "(GenerationEngine default; docs/SERVING.md generative "
             "serving)")
register_env("MXNET_KV_MAX_LEN", int, 128,
             "generation KV ring-buffer length per slot: the attention "
             "window — positions past it slide (docs/SERVING.md)")
register_env("MXNET_KV_BUDGET_BYTES", int, 0,
             "refuse to build a GenerationEngine whose device-resident "
             "KV rings exceed this many bytes (0 = unbounded); the live "
             "bytes census tracks the actual residency under the "
             "kv_cache origin")
register_env("MXNET_FLEET_SCALE_KV_LOW", float, 0.0,
             "Autoscaler scale-UP threshold on KV-slot pressure: "
             "federated free generation KV slots per up replica BELOW "
             "this grows the fleet (0 = KV signal disabled)")
register_env("MXNET_FLEET_SCALE_KV_HIGH", float, 0.0,
             "Autoscaler scale-DOWN gate on KV-slot pressure: shrinking "
             "additionally requires federated free KV slots per up "
             "replica ABOVE this (0 = KV signal disabled)")
register_env("MXNET_TRACE_SAMPLE", float, 0.0,
             "request-trace head-sampling rate in [0, 1] "
             "(docs/OBSERVABILITY.md tracing section): 0 disables "
             "request-scoped distributed tracing entirely, and a "
             "sampled-out request (head-sample miss) pays the same "
             "shared no-op constant — like MXNET_TELEMETRY=0.  A "
             "head-sample hit is traced at every hop and guaranteed a "
             "spool record; traces continued from a foreign context are "
             "additionally kept whenever an always-keep rule fires "
             "(slow/retried/re-routed/shed)")
register_env("MXNET_TRACE_SLOW_MS", float, 250.0,
             "always-keep threshold for the trace spool: a completed "
             "request whose hop-local wall meets this many ms is spooled "
             "even when the head-sample coin said no (tail sampling for "
             "the latency forensics that matter)")
register_env("MXNET_TRACE_SPOOL_DIR", str, "",
             "directory for completed-request trace records (one "
             "append-only JSONL file per process, one record per line; "
             "a crash can tear at most the final line, which readers "
             "skip); empty disables spooling — traces still ride the "
             "wire into client-visible response breakdowns.  Merge "
             "across processes with tools/trace_report.py --fleet <dir>")
register_env("MXNET_COSTS", bool, True,
             "compute-cost observability (mxnet_tpu.costs): per-program "
             "cost ledger capture at compile/AOT/warm-load time + "
             "per-execution MFU accounting on span-recording paths "
             "(docs/OBSERVABILITY.md costs/* tables); capture is "
             "compile-time-only either way")
register_env("MXNET_COST_ATTRIBUTION", bool, True,
             "block-level flop attribution of captured segments at "
             "segment COMPILE time (one abstract trace per distinct op "
             "signature, cached) — feeds tools/cost_report.py's "
             "per-block cost table")
register_env("MXNET_PEAK_FLOPS", float, 0.0,
             "peak FLOP/s override for MFU accounting on chips the "
             "mxnet_tpu.costs.PEAKS table does not know (0 = use the "
             "table; an unknown accelerator is then an error)")
register_env("MXNET_PEAK_BYTES_PER_S", float, 0.0,
             "peak memory bandwidth override for the roofline ridge in "
             "tools/cost_report.py (0 = the mxnet_tpu.costs.PEAKS table)")
register_env("MXNET_STEP_DIAGNOSTICS", bool, True,
             "training-dynamics observability (mxnet_tpu.health): fuse a "
             "diagnostics tail (loss, grad/param/update norms, per-block "
             "norms, nonfinite counts) into the captured gluon step and "
             "the SPMD fused step as extra program outputs — one batched "
             "host read per step, training math bit-identical on/off "
             "(docs/OBSERVABILITY.md 'Training-dynamics observability')")
register_env("MXNET_RUN_LEDGER", bool, True,
             "persistent run ledger gate: per-run JSONL time series of "
             "step diagnostics (loss/norms/lr/throughput/MFU) written "
             "under MXNET_RUN_LEDGER_DIR; resume-safe — a restarted run "
             "rewinds rows past the restored checkpoint so steps are "
             "never duplicated (tools/run_report.py renders it)")
register_env("MXNET_RUN_LEDGER_DIR", str, "",
             "directory for run-ledger JSONL files (run_<id>.jsonl); "
             "empty disables the ledger (in-memory diagnostics, "
             "detectors and crash-report rows still work)")
register_env("MXNET_RUN_ID", str, "",
             "run id for the run ledger and anomaly events (empty = one "
             "generated per process); set it across restarts so a "
             "relaunched job continues the SAME ledger file")
register_env("MXNET_AUTOPILOT", bool, True,
             "master switch for health.Autopilot policy loop (an "
             "Autopilot constructed with enabled=None reads this; "
             "disabled, every policy is inert)")
register_env("MXNET_AUTOPILOT_LR_BACKOFF", float, 0.5,
             "per-rewind learning-rate backoff factor: after a rewind "
             "the effective lr is capped at last_good_lr * "
             "backoff**attempt while the anomaly window is open")
register_env("MXNET_AUTOPILOT_MAX_REWINDS", int, 4,
             "global Autopilot rewind budget; exhausting it raises "
             "AutopilotAbort (permanent — elastic_run gives up with "
             "the decision log in the crash report)")
register_env("MXNET_AUTOPILOT_COOLDOWN", int, 8,
             "steps past the anomaly an Autopilot rewind window (and "
             "its lr cap) stays open; a recurrence inside the window "
             "escalates, surviving it closes the window")
register_env("MXNET_PROFILER_MAX_EVENTS", int, 200000,
             "profiler event-ring capacity: oldest op-span/counter events "
             "drop past it (dropped count surfaced in dump()) so a long "
             "profiled run cannot grow host memory without bound")


def _parse(typ, raw):
    if typ is bool:
        return raw not in ("0", "false", "False", "")
    return typ(raw)


def getenv(name):
    """Typed read of a registered MXNET_* variable."""
    if name in _ENV_REGISTRY:
        typ, default, _ = _ENV_REGISTRY[name]
        raw = os.environ.get(name)
        return default if raw is None else _parse(typ, raw)
    return os.environ.get(name)


def setenv(name, value):
    os.environ[name] = str(value)


def config():
    """The full effective configuration."""
    return {name: getenv(name) for name in sorted(_ENV_REGISTRY)}


def get_gpu_count():
    from .context import num_tpus
    return num_tpus()


# -- numpy-semantics switches (reference mx.util.set_np) --------------------
_np_flag = {"array": False, "shape": False}


def set_np(shape=True, array=True):
    _np_flag["array"] = array
    _np_flag["shape"] = shape


def reset_np():
    set_np(False, False)


def is_np_array():
    return _np_flag["array"]


def use_np(func):
    """Decorator compat (nd already follows numpy semantics)."""
    return func
