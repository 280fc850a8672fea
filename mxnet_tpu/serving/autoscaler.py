"""Fleet autoscaler: a policy loop over the federated fleet gauges.

The supervisor already federates every worker's telemetry snapshot
(queue depth, latency histograms, the device-memory census — PR 9/10);
nothing consumed them for *control* until now.  :class:`Autoscaler`
closes the loop: every ``interval_s`` it reads the federated ``summed``
view plus the supervisor's replica states and decides grow / shrink /
hold with the boring-but-essential guardrails — hysteresis (separate
high/low thresholds + consecutive-tick streaks so one noisy sample
never resizes the fleet), a cooldown after every action, and hard
min/max bounds.

Scaling actions go strictly through the existing zero-drop machinery:

* **up** — ``supervisor.add_replica()`` spawns a worker on a fresh
  index (never reused, so router-side breaker/drain state cannot alias)
  and the router picks it up from ``endpoints()`` automatically;
* **down** — ``router.drain(victim)`` (stop dispatching, in-flight
  work FINISHES), ``supervisor.remove_replica(victim)`` (the worker
  still exits through the graceful ``ModelServer.stop`` drain), then
  ``router.forget(victim)`` — no accepted request is ever dropped, the
  same contract as ``rolling_swap``, and the two compose: concurrent
  drains of one replica are counted, a replica removed mid-rollout is
  skipped by the swap (``tests/test_fleet.py`` proves the race).

Every decision — including the denied ones — lands in a bounded log
surfaced through ``Router.status()`` → ``/statusz`` (``autoscaler``
section), the crash report's ``fleet`` section, and the
``fleet/scale_*`` metrics (docs/OBSERVABILITY.md).  A scale-down that
lands in a storm or races a rolling swap must lose zero idempotent
requests and converge to the target size (``tests/test_fleet.py``;
docs/SERVING.md "Autoscaler lifecycle").
"""
from __future__ import annotations

import collections
import logging
import threading
import time
import weakref

from ..base import MXNetError
from . import fleet as _fleet

__all__ = ["Autoscaler"]

_log = logging.getLogger("mxnet_tpu.serving.autoscaler")


def _hist_window_p99(prev, cur):
    """p99 (ms) of the requests observed BETWEEN two cumulative
    expo-histogram snapshots (the federated ``serving/latency_ms``) —
    recency matters for a control loop, lifetime percentiles do not.
    Returns None when the window saw no requests."""
    if not cur or not cur.get("buckets"):
        return None
    pb = {le: c for le, c in (prev or {}).get("buckets") or []}
    window = []
    total = 0
    prev_cum = 0
    for le, cum in cur["buckets"]:
        delta = (cum - pb.get(le, 0)) - prev_cum
        prev_cum = cum - pb.get(le, 0)
        window.append((le, max(0, delta)))
        total += max(0, delta)
    if total <= 0:
        return None
    target = 0.99 * total
    seen = 0
    last_finite = 0.0
    for le, n in window:
        finite = not (isinstance(le, str) or le == float("inf"))
        if finite:
            last_finite = float(le)
        seen += n
        if seen >= target and n:
            return last_finite if not finite else float(le)
    return last_finite


class Autoscaler:
    """Grow/shrink a supervised replica fleet off the federated gauges.

    ``queue_high`` / ``queue_low`` are per-up-replica federated queue
    depths (the hysteresis band); ``p99_high_ms`` optionally adds a
    latency leg (window p99 over the federated latency histogram — above
    it is overload, below half of it is calm); ``hbm_high_bytes``
    optionally treats per-replica device-memory occupancy from the
    federated memory census the same way; ``kv_slot_low`` /
    ``kv_slot_high`` add the generative-serving leg over the federated
    ``generate/free_kv_slots`` gauge — fewer free KV slots per up
    replica than ``kv_slot_low`` is overload (generations about to
    queue on cache capacity, whatever the request queue says), and
    scale-down is additionally gated on more than ``kv_slot_high`` free
    slots per replica; both legs are disabled at 0/None, or whenever no
    replica serves ``/generate`` (the gauge is simply absent).
    ``up_ticks`` /
    ``down_ticks`` are the consecutive-tick streaks required before
    acting (scale-down deliberately needs the longer streak), and every
    action starts a ``cooldown_s`` window in which only observation
    happens.  Defaults come from the ``MXNET_FLEET_SCALE_*`` env knobs
    (docs/SERVING.md).
    """

    def __init__(self, supervisor, router, min_replicas=None,
                 max_replicas=None, interval_s=None, cooldown_s=None,
                 queue_high=None, queue_low=None, p99_high_ms=None,
                 hbm_high_bytes=None, kv_slot_low=None, kv_slot_high=None,
                 up_ticks=2, down_ticks=5,
                 drain_timeout_s=30.0, add_timeout_s=120.0,
                 decisions_cap=64):
        from ..util import getenv
        if router._sup is not supervisor:
            raise MXNetError(
                "Autoscaler needs the Router that fronts this supervisor "
                "(scale-down drains through it)")
        self._sup = supervisor
        self._router = router
        self.min_replicas = int(
            min_replicas if min_replicas is not None
            else getenv("MXNET_FLEET_SCALE_MIN"))
        self.max_replicas = int(
            max_replicas if max_replicas is not None
            else getenv("MXNET_FLEET_SCALE_MAX"))
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise MXNetError(
                f"need 1 <= min_replicas ({self.min_replicas}) <= "
                f"max_replicas ({self.max_replicas})")
        self.interval_s = float(
            interval_s if interval_s is not None
            else getenv("MXNET_FLEET_SCALE_INTERVAL_S"))
        self.cooldown_s = float(
            cooldown_s if cooldown_s is not None
            else getenv("MXNET_FLEET_SCALE_COOLDOWN_S"))
        self.queue_high = float(
            queue_high if queue_high is not None
            else getenv("MXNET_FLEET_SCALE_QUEUE_HIGH"))
        self.queue_low = float(
            queue_low if queue_low is not None
            else getenv("MXNET_FLEET_SCALE_QUEUE_LOW"))
        if self.queue_low >= self.queue_high:
            raise MXNetError("queue_low must sit below queue_high "
                             "(the hysteresis band)")
        self.p99_high_ms = float(p99_high_ms) if p99_high_ms else None
        self.hbm_high_bytes = float(hbm_high_bytes) \
            if hbm_high_bytes else None
        kv_low = (kv_slot_low if kv_slot_low is not None
                  else getenv("MXNET_FLEET_SCALE_KV_LOW"))
        self.kv_slot_low = float(kv_low) if kv_low else None
        kv_high = (kv_slot_high if kv_slot_high is not None
                   else getenv("MXNET_FLEET_SCALE_KV_HIGH"))
        self.kv_slot_high = float(kv_high) if kv_high else None
        if self.kv_slot_low is not None and self.kv_slot_high is not None \
                and self.kv_slot_low >= self.kv_slot_high:
            raise MXNetError("kv_slot_low must sit below kv_slot_high "
                             "(the KV-slot hysteresis band)")
        self.up_ticks = max(1, int(up_ticks))
        self.down_ticks = max(1, int(down_ticks))
        self.drain_timeout_s = float(drain_timeout_s)
        self.add_timeout_s = float(add_timeout_s)
        self.target = max(self.min_replicas,
                          min(self.max_replicas,
                              len(supervisor._list())))
        # appended by the policy thread, read by /statusz + crash-report
        # builders on other threads: iterating a deque during a
        # concurrent append raises (the PR-10 sample-ring lesson)
        self._dec_lock = threading.Lock()
        self._decisions: collections.deque = collections.deque(
            maxlen=int(decisions_cap))
        self._prev_hist = None
        self._up_streak = 0
        self._down_streak = 0
        self._cooldown_until = 0.0
        self._stop = threading.Event()
        self._thread = None
        router._autoscaler = weakref.ref(self)
        _fleet._live_autoscalers.add(self)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="mxnet-tpu-autoscaler",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout=10.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self._tick()
            except Exception:       # noqa: BLE001 — policy must survive
                _log.exception("autoscaler tick failed")

    # -- signals -----------------------------------------------------------
    def _signals(self):
        """One policy-tick reading of the federated fleet state."""
        st = self._sup.status()
        n_up = sum(1 for v in st.values() if v["state"] == "up")
        fed = self._sup.federated()["summed"]
        gauges = fed.get("gauges") or {}
        cur_hist = (fed.get("histograms") or {}).get("serving/latency_ms")
        p99 = _hist_window_p99(self._prev_hist, cur_hist)
        self._prev_hist = cur_hist
        queue = float(gauges.get("serving/queue_depth", 0) or 0)
        hbm = float(gauges.get("memory/device_bytes_in_use", 0) or 0)
        # absent (no replica serves /generate) is None, NOT 0 — zero
        # free slots means saturated, missing means no generative fleet
        kv_free = gauges.get("generate/free_kv_slots")
        return {
            "replicas": len(st),
            "replicas_up": n_up,
            "queue_depth": queue,
            "queue_per_replica": round(queue / n_up, 3) if n_up else None,
            "window_p99_ms": round(p99, 3) if p99 is not None else None,
            "hbm_per_replica_bytes": round(hbm / n_up) if n_up else None,
            "free_kv_slots_per_replica": round(float(kv_free) / n_up, 3)
            if kv_free is not None and n_up else None,
            "router_outstanding": self._router.outstanding,
        }

    # -- policy ------------------------------------------------------------
    def _tick(self, now=None):
        """One policy evaluation (the loop calls this every
        ``interval_s``; tests call it directly)."""
        now = time.monotonic() if now is None else now
        sig = self._signals()
        n_up = sig["replicas_up"]
        if n_up == 0:
            # restart window / total brownout: the supervisor's restart
            # machinery owns this — resizing a dead fleet only thrashes
            self._up_streak = self._down_streak = 0
            return None
        per = sig["queue_per_replica"] or 0.0
        p99 = sig["window_p99_ms"]
        hbm = sig["hbm_per_replica_bytes"]
        reasons = []
        overload = per > self.queue_high
        if overload:
            reasons.append(f"queue/replica {per:.2f} > {self.queue_high}")
        if self.p99_high_ms is not None and p99 is not None \
                and p99 > self.p99_high_ms:
            overload = True
            reasons.append(f"window p99 {p99:.0f} ms > "
                           f"{self.p99_high_ms:.0f}")
        if self.hbm_high_bytes is not None and hbm is not None \
                and hbm > self.hbm_high_bytes:
            overload = True
            reasons.append(f"hbm/replica {hbm} > "
                           f"{self.hbm_high_bytes:.0f}")
        kv = sig["free_kv_slots_per_replica"]
        if self.kv_slot_low is not None and kv is not None \
                and kv < self.kv_slot_low:
            overload = True
            reasons.append(f"free KV slots/replica {kv} < "
                           f"{self.kv_slot_low:.0f}")
        calm_p99 = self.p99_high_ms is None or p99 is None \
            or p99 < 0.5 * self.p99_high_ms
        calm_kv = self.kv_slot_high is None or kv is None \
            or kv > self.kv_slot_high
        underload = (not overload) and per < self.queue_low \
            and calm_p99 and calm_kv
        if overload:
            self._up_streak += 1
            self._down_streak = 0
        elif underload:
            self._down_streak += 1
            self._up_streak = 0
        else:
            self._up_streak = self._down_streak = 0
        action = None
        if self._up_streak >= self.up_ticks:
            action = "up"
        elif self._down_streak >= self.down_ticks:
            action = "down"
            reasons.append(
                f"queue/replica {per:.2f} < {self.queue_low} "
                f"for {self._down_streak} ticks")
        if action is None:
            return None
        # a decision (even a denied one) consumes the streak: a fleet
        # pinned at a bound or inside a cooldown re-accumulates the full
        # streak before the NEXT decision, instead of emitting one
        # denial per tick forever (which would flood the log and churn
        # the real up/down history out of the bounded decision deque)
        self._up_streak = self._down_streak = 0
        reason = "; ".join(reasons) or "streak"
        if now < self._cooldown_until:
            left = self._cooldown_until - now
            return self._decide(f"denied_{action}",
                                f"cooldown ({left:.1f}s left): {reason}",
                                sig)
        if action == "up" and self.target >= self.max_replicas:
            return self._decide("denied_up",
                                f"at max_replicas={self.max_replicas}: "
                                f"{reason}", sig)
        if action == "down" and self.target <= self.min_replicas:
            return self._decide("denied_down",
                                f"at min_replicas={self.min_replicas}: "
                                f"{reason}", sig)
        if action == "up":
            return self._scale_up(now, reason, sig)
        return self._scale_down(now, reason, sig)

    def _decide(self, action, reason, sig):
        rec = dict(sig)
        rec.update(ts=time.time(), action=action, reason=reason,
                   target=self.target)
        with self._dec_lock:
            self._decisions.append(rec)
        if action.startswith("denied"):
            _fleet._inc("scale_denied")
        _log.info("autoscaler %s (target=%d): %s", action, self.target,
                  reason)
        return rec

    def _scale_up(self, now, reason, sig):
        self._cooldown_until = now + self.cooldown_s
        try:
            idx = self._sup.add_replica(timeout_s=self.add_timeout_s)
        except MXNetError as e:
            return self._decide("denied_up", f"spawn failed: {e}", sig)
        self.target = min(self.max_replicas, self.target + 1)
        _fleet._inc("scale_ups")
        # revoke outstanding zero-hop leases promptly so direct clients
        # pick up the new replica on their next refresh instead of
        # waiting out the TTL (scale-down revokes via drain/forget);
        # getattr: router doubles (tests) need not speak leases
        bump = getattr(self._router, "lease_bump", None)
        if bump is not None:
            bump("scale_up")
        return self._decide("up", f"{reason} -> added replica {idx}", sig)

    def _scale_down(self, now, reason, sig):
        self._cooldown_until = now + self.cooldown_s
        # victim: the newest up replica not already being drained by
        # someone else (a rolling swap holds its own drain count — its
        # drain is temporary, so it still counts toward the survivors)
        st = self._sup.status()
        total_up = sum(1 for v in st.values() if v["state"] == "up")
        draining = set(self._router.status()["draining"])
        ups = [idx for idx, v in st.items()
               if v["state"] == "up" and idx not in draining]
        if total_up - 1 < self.min_replicas or not ups:
            return self._decide("denied_down",
                                "no drainable victim above min_replicas",
                                sig)
        victim = max(ups)
        try:
            # the zero-drop path: stop dispatching, let in-flight work
            # FINISH, only then stop the worker
            self._router.drain(victim, timeout=self.drain_timeout_s)
        except Exception as e:      # noqa: BLE001 — drain timeout
            return self._decide("denied_down",
                                f"drain of replica {victim} failed: "
                                f"{e}", sig)
        try:
            self._sup.remove_replica(victim)
        finally:
            self._router.admit(victim)
            self._router.forget(victim)
        self.target = max(self.min_replicas, self.target - 1)
        _fleet._inc("scale_downs")
        return self._decide(
            "down", f"{reason} -> drained and removed replica {victim}",
            sig)

    # -- observability -----------------------------------------------------
    def decisions(self):
        """The last-K decision log (newest last), including denied
        decisions — surfaced in ``/statusz`` and crash reports."""
        with self._dec_lock:
            return list(self._decisions)

    def status(self):
        now = time.monotonic()
        return {
            "target": self.target,
            "min_replicas": self.min_replicas,
            "max_replicas": self.max_replicas,
            "up_streak": self._up_streak,
            "down_streak": self._down_streak,
            "cooldown_remaining_s": round(
                max(0.0, self._cooldown_until - now), 3),
            "decisions": self.decisions(),
        }
