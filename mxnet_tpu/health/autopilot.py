"""Self-driving training: the detector-to-recovery policy loop
(docs/RESILIENCE.md "Self-driving training").

The observability arc made training anomalies *visible* (PR-14 run
ledger + detectors); the resilience arc made recovery *cheap*
(checkpoint rewinds that restore bit-identically, resume extras, the
elastic restart loop).  :class:`Autopilot` closes the loop: it consumes
the same step rows and anomaly events the ledger already carries —
delivered at step boundaries through ``health.poll()``, so it adds zero
sync points — and turns them into typed, bounded, decision-logged
interventions in the PR-13 autoscaler mold:

- **loss-spike / divergence / grad-explosion / nonfinite-streak ->
  rewind**: restore the last-good checkpoint (poisoned ones discarded
  first), replay with the recorded RNG/iterator state, and clamp the
  anomalous learning-rate excursion (``MXNET_AUTOPILOT_LR_BACKOFF``).
  Bounded retries per anomaly window; exhausting ``max_rewinds`` raises
  :class:`AutopilotAbort` (a permanent fault) so ``elastic_run`` stops
  burning the pod allocation and the crash report says WHY;
- **device OOM -> degrade gracefully**: double the
  ``SPMDTrainer(grad_accum=...)`` microbatch split (global batch and
  bitwise grad sums held fixed) or tighten ``remat='auto'``;
- **sustained MFU regression -> flag (or abort)** against a baseline
  band, relative to the baseline's own noise;
- **plateau -> early stop** with a final checkpoint.

Every decision — including denied ones — lands in a lock-guarded
bounded log (the PR-13 deque-lock lesson), the run ledger (as
``event: "autopilot"`` rows keyed ``at_step`` so checkpoint rewinds
cannot erase them), the flight recorder, ``health/autopilot_*``
counters, and the crash report's ``training.autopilot`` section.
A rewind interrupted by a crash is re-armed from the ledger on restart
(a ``rewind`` decision without its ``rewound`` completion), so recovery
itself is recoverable.
"""
from __future__ import annotations

import threading
import time

from ..faults import PermanentFault
from ..util import getenv

__all__ = ["Autopilot", "AutopilotAbort", "Decision", "RewindRequest"]

# anomaly kinds that request a checkpoint rewind (plateau stops instead)
REWIND_KINDS = ("loss_spike", "divergence", "grad_explosion",
                "nonfinite_streak")

_COUNTER_KEYS = ("decisions", "interventions", "rewinds", "lr_backoffs",
                 "degrades", "flags", "stops", "denied")


class AutopilotAbort(PermanentFault):
    """Autopilot exhausted its intervention budget (``max_rewinds`` /
    per-window retries) or was configured to abort: classified PERMANENT
    so ``elastic_run`` gives up instead of blindly restarting into the
    same divergence."""


class Decision:
    """One typed Autopilot decision (including denied ones)."""

    __slots__ = ("seq", "ts", "policy", "action", "at_step", "reason",
                 "params", "outcome")

    def __init__(self, seq, policy, action, at_step, reason, params=None,
                 outcome="ok"):
        self.seq = int(seq)
        self.ts = time.time()
        self.policy = policy
        self.action = action
        self.at_step = None if at_step is None else int(at_step)
        self.reason = reason
        self.params = dict(params or {})
        self.outcome = outcome

    def as_dict(self):
        return {"seq": self.seq, "ts": round(self.ts, 6),
                "policy": self.policy, "action": self.action,
                "at_step": self.at_step, "reason": self.reason,
                "params": dict(self.params), "outcome": self.outcome}

    def as_row(self):
        """The ledger representation.  The step lives under ``at_step``
        (NOT ``step``): the ledger's resume rewind drops every row with
        an integer ``step`` at/past the restored step, and the decision
        trail must survive the very rewind it explains."""
        d = self.as_dict()
        d["event"] = "autopilot"
        return d

    def __repr__(self):
        return (f"Decision({self.policy}/{self.action} @ {self.at_step}: "
                f"{self.reason!r})")


class RewindRequest:
    """A pending (not yet executed) rewind: armed by the anomaly
    callback, executed by ``ResilientStep`` at the next step boundary."""

    __slots__ = ("anomaly_step", "kind", "attempt")

    def __init__(self, anomaly_step, kind, attempt):
        self.anomaly_step = int(anomaly_step)
        self.kind = kind
        self.attempt = int(attempt)


class Autopilot:
    """The policy loop.  Construct once, pass to
    ``ResilientStep(autopilot=...)`` (which attaches it) or call
    :meth:`attach` directly in a hand-rolled loop.

    Parameters
    ----------
    enabled : bool, optional
        Master switch (default: ``MXNET_AUTOPILOT``).  Disabled, the
        callbacks stay unregistered and every policy is inert.
    lr_backoff : float, optional
        Per-rewind learning-rate backoff factor
        (default ``MXNET_AUTOPILOT_LR_BACKOFF``).  The post-rewind cap is
        ``last_good_lr * lr_backoff**attempt``.
    max_rewinds : int, optional
        Global rewind budget (default ``MXNET_AUTOPILOT_MAX_REWINDS``);
        exhausting it aborts the run with :class:`AutopilotAbort`.
    rewinds_per_window : int
        Retries inside ONE anomaly window before escalating to abort.
    cooldown_steps : int, optional
        Steps past the anomaly the window (and its LR cap) stays open
        (default ``MXNET_AUTOPILOT_COOLDOWN``).  Hysteresis: a recurrence
        inside the window escalates; surviving it closes the window.
    lr_clamp_guard : float
        First-attempt clamp threshold: only a learning rate more than
        this factor above the last good one is capped, so the replay of
        healthy steps stays bit-identical to the original trajectory.
        Attempts >= 2 cap unconditionally (true LR backoff).
    mfu_window / mfu_patience / mfu_band_pct : int / int / float
        MFU policy: the first ``mfu_window`` MFU samples fix a baseline;
        ``mfu_patience`` consecutive samples more than ``mfu_band_pct``
        percent below it flag a sustained regression (once per
        excursion — re-arms when MFU returns inside half the band).
    mfu_abort : bool
        Escalate a sustained MFU regression from flag to abort.
    plateau_stop : bool
        Turn a ``plateau`` anomaly into an early stop (with a final
        checkpoint when a manager is attached).
    nonfinite_skip_streak : int
        Guard-skipped steps write no ledger rows, so the detector bank
        cannot see a non-finite streak under ``ResilientStep``'s
        skip-step guard; the guard reports skips here instead, and this
        many consecutive ones request a rewind (kind
        ``nonfinite_streak``) — long before the guard's own
        ``max_consecutive_skips`` abort.
    max_grad_accum : int
        Hard bound for the OOM-degrade microbatching lever.
    decisions_cap : int
        Bounded decision-log depth (oldest dropped).
    """

    def __init__(self, enabled=None, lr_backoff=None, max_rewinds=None,
                 rewinds_per_window=2, cooldown_steps=None,
                 lr_clamp_guard=2.0, mfu_window=16, mfu_patience=8,
                 mfu_band_pct=20.0, mfu_abort=False, plateau_stop=True,
                 nonfinite_skip_streak=3, max_grad_accum=8,
                 decisions_cap=256):
        import collections
        self.enabled = bool(getenv("MXNET_AUTOPILOT")) \
            if enabled is None else bool(enabled)
        self.lr_backoff = float(getenv("MXNET_AUTOPILOT_LR_BACKOFF")) \
            if lr_backoff is None else float(lr_backoff)
        self.max_rewinds = int(getenv("MXNET_AUTOPILOT_MAX_REWINDS")) \
            if max_rewinds is None else int(max_rewinds)
        self.rewinds_per_window = max(1, int(rewinds_per_window))
        self.cooldown_steps = int(getenv("MXNET_AUTOPILOT_COOLDOWN")) \
            if cooldown_steps is None else int(cooldown_steps)
        self.lr_clamp_guard = float(lr_clamp_guard)
        self.mfu_window = max(2, int(mfu_window))
        self.mfu_patience = max(1, int(mfu_patience))
        self.mfu_band_pct = float(mfu_band_pct)
        self.mfu_abort = bool(mfu_abort)
        self.plateau_stop = bool(plateau_stop)
        self.nonfinite_skip_streak = max(1, int(nonfinite_skip_streak))
        self.max_grad_accum = max(1, int(max_grad_accum))
        # appended by the policy callbacks on the training thread, read
        # by /statusz + crash-report builders on other threads: iterating
        # a deque during a concurrent append raises (the PR-13
        # autoscaler / PR-10 sample-ring lesson), so every access holds
        # the lock
        self._lock = threading.RLock()
        self._decisions: "collections.deque" = collections.deque(
            maxlen=int(decisions_cap))
        self._seq = 0
        self._counters = {k: 0 for k in _COUNTER_KEYS}
        # rewind policy state
        self._pending = None            # RewindRequest or None
        self._win = None                # open anomaly window (dict)
        self._nf_skips = 0              # consecutive guard-skipped steps
        self._rewinds_total = 0
        self._last_good_lr = None
        # (step, lr) trail: an LR excursion lands in row s while its
        # loss consequence only shows in row s+1, so at rewind time the
        # trusted "last good" LR is the one recorded AT the restored
        # step — not the latest finite-loss row's (that may be the
        # spike itself)
        self._lr_hist = collections.deque(maxlen=256)
        # stop/abort state
        self._should_stop = False
        self._stop_decision = None
        self._abort_reason = None
        # MFU policy state
        self._mfu_samples = []
        self._mfu_baseline = None
        self._mfu_bad = 0
        self._mfu_armed = True
        # wiring
        self._manager = None
        self._trainer = None
        self._net = None
        self._data_iter = None
        self._attached = False

    # -- wiring ------------------------------------------------------------
    def attach(self, manager=None, trainer=None, net=None, data_iter=None):
        """Register the policy callbacks on the health stream and adopt
        the recovery machinery (checkpoint manager, trainer, net,
        iterator).  Recovers in-flight state — an armed-but-unexecuted
        rewind, the open window, spent budgets — from the run ledger's
        decision rows, so a crash mid-intervention resumes it."""
        from . import on_anomaly, on_row, set_autopilot
        if manager is not None:
            self._manager = manager
        if trainer is not None:
            self._trainer = trainer
        if net is not None:
            self._net = net
        if data_iter is not None:
            self._data_iter = data_iter
        if not self.enabled or self._attached:
            set_autopilot(self)
            return self
        self.recover_from_ledger()
        on_anomaly(self._on_anomaly)
        on_row(self._on_row)
        set_autopilot(self)
        self._attached = True
        return self

    def detach(self):
        from . import current_autopilot, remove_on_anomaly, remove_on_row, \
            set_autopilot
        if self._attached:
            remove_on_anomaly(self._on_anomaly)
            remove_on_row(self._on_row)
            self._attached = False
        if current_autopilot() is self:
            set_autopilot(None)

    # -- the decision log (the only mutation path) -------------------------
    def _decide(self, policy, action, at_step, reason, params=None,
                outcome="ok", intervention=False):
        with self._lock:
            self._seq += 1
            d = Decision(self._seq, policy, action, at_step, reason,
                         params, outcome)
            self._decisions.append(d)
            self._counters["decisions"] += 1
            if action in ("denied", "abort") or outcome == "denied":
                self._counters["denied"] += 1
            if intervention:
                self._counters["interventions"] += 1
        # every decision out every surface: flight recorder + run ledger
        from .. import telemetry as _telemetry
        _telemetry.add_span("autopilot", time.perf_counter_ns() // 1000,
                            0.0, policy=policy, action=action,
                            at_step=at_step, reason=reason)
        led = self._ledger()
        if led is not None:
            led.append(d.as_row())
        return d

    def _ledger(self):
        from . import run_ledger
        try:
            return run_ledger()
        except Exception:       # noqa: BLE001 — policy must not die on
            return None         # a broken ledger

    def _inc(self, key, n=1):
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    # -- policy inputs -----------------------------------------------------
    def _on_anomaly(self, anom):
        """Route one TrainingAnomaly (called from ``health.poll()`` on
        the training thread — record-only, never heavy work)."""
        try:
            if anom.kind in REWIND_KINDS:
                self._request_rewind(anom)
            elif anom.kind == "plateau" and self.plateau_stop:
                self._request_stop(anom)
        except Exception:       # noqa: BLE001 — a policy bug must never
            pass                # fail the observed step

    def _on_row(self, row):
        """Consume one step row: window lifecycle, last-good LR tracking
        and the MFU policy."""
        try:
            step = row.get("step")
            if not isinstance(step, int):
                return
            self._window_tick(step)
            lr = row.get("lr")
            loss = row.get("loss")
            import math
            if lr is not None and math.isfinite(lr):
                self._lr_hist.append((step, float(lr)))
            if lr is not None and self._pending is None \
                    and loss is not None and math.isfinite(loss):
                # one step behind by construction: an anomalous row sets
                # a pending rewind (the anomaly callback runs first), so
                # a spiked LR never becomes the "last good" one
                self._last_good_lr = float(lr)
            self._mfu_tick(step, row.get("mfu"))
        except Exception:       # noqa: BLE001
            pass

    # -- rewind policy -----------------------------------------------------
    def _request_rewind(self, anom):
        step = anom.step if isinstance(anom.step, int) else None
        sig = {"kind": anom.kind, "anomaly_step": step,
               "value": anom.value, "threshold": anom.threshold}
        with self._lock:
            if self._abort_reason is not None or self._should_stop:
                return
            if self._pending is not None:
                self._decide(
                    "rewind", "denied", step,
                    f"{anom.kind}: rewind to before step "
                    f"{self._pending.anomaly_step} already pending", sig,
                    outcome="denied")
                return
            if self._manager is None:
                self._decide(
                    "rewind", "denied", step,
                    f"{anom.kind}: no CheckpointManager attached — "
                    "nothing to rewind to", sig, outcome="denied")
                return
            win = self._win
            in_window = win is not None and step is not None \
                and step <= win["until"]
            attempt = win["attempt"] + 1 if in_window else 1
            if attempt > self.rewinds_per_window \
                    or self._rewinds_total >= self.max_rewinds:
                why = (f"{anom.kind} recurred: window retries "
                       f"({self.rewinds_per_window}) exhausted"
                       if attempt > self.rewinds_per_window else
                       f"{anom.kind}: global rewind budget "
                       f"({self.max_rewinds}) exhausted")
                self._abort_reason = why
                self._decide("rewind", "abort", step, why, sig)
                return
            self._pending = RewindRequest(step, anom.kind, attempt)
        self._decide(
            "rewind", "rewind", step,
            f"{anom.kind} at step {step}: rewinding to the last good "
            f"checkpoint (attempt {attempt}, lr backoff "
            f"{self.lr_backoff ** attempt:g}x)",
            dict(sig, attempt=attempt,
                 last_good_lr=self._last_good_lr),
            intervention=True)

    def note_nonfinite(self, step, finite):
        """Per-step report from ``ResilientStep``'s skip-step guard.  A
        skipped (non-finite) step dispatches nothing, so no ledger row is
        written and the detector bank is blind to the streak; after
        ``nonfinite_skip_streak`` consecutive skips this requests a
        rewind directly — the run rolls back to a finite checkpoint
        instead of burning ``max_consecutive_skips`` no-op steps toward
        the guard's permanent abort."""
        if not self.enabled:
            return
        if finite:
            self._nf_skips = 0
            return
        self._nf_skips += 1
        if self._nf_skips < self.nonfinite_skip_streak \
                or not isinstance(step, int):
            return
        streak, self._nf_skips = self._nf_skips, 0
        from .detectors import TrainingAnomaly
        self._request_rewind(TrainingAnomaly(
            "nonfinite_streak", step, streak, self.nonfinite_skip_streak,
            f"{streak} consecutive guard-skipped (non-finite) steps"))

    def pending_rewind(self):
        """The armed-but-unexecuted rewind (None when idle).  Stays
        armed until :meth:`on_rewound` — an execution killed halfway is
        retried by the restarted attempt."""
        with self._lock:
            return self._pending

    def discard_margin(self):
        """Checkpoints at/after ``anomaly_step - 1`` are suspect: the
        anomalous row's loss was computed on weights the PREVIOUS step
        already updated, so a checkpoint saved at that previous step
        carries the poison too."""
        return 1

    def on_rewound(self, restored_step, request=None):
        """Called by the executor after a successful restore: open the
        anomaly window (arming the LR cap), account the spent budget,
        and re-warm a fresh detector bank from the pre-rewind ledger
        rows so the replay sees exactly the detector state the original
        pass saw."""
        req = request if request is not None else self.pending_rewind()
        if req is None:
            return
        with self._lock:
            # trust the LR recorded AT (or before) the restored step:
            # the latest finite-loss row's LR may BE the excursion (an
            # LR spike at step s shows in row s, its loss blowup only in
            # row s+1)
            for s, lr in reversed(self._lr_hist):
                if isinstance(s, int) and s <= int(restored_step):
                    self._last_good_lr = lr
                    break
            cap = None
            if self._last_good_lr is not None:
                cap = self._last_good_lr * (self.lr_backoff ** req.attempt)
            self._win = {
                "anomaly_step": req.anomaly_step,
                "restored_step": int(restored_step),
                "attempt": req.attempt,
                "cap": cap,
                "last_good_lr": self._last_good_lr,
                "until": req.anomaly_step + self.cooldown_steps,
            }
            self._rewinds_total += 1
            self._counters["rewinds"] += 1
            if cap is not None:
                self._counters["lr_backoffs"] += 1
            self._pending = None
        self._decide(
            "rewind", "rewound", req.anomaly_step,
            f"restored step {restored_step}; replaying with lr cap "
            f"{cap if cap is not None else 'none'} through step "
            f"{req.anomaly_step + self.cooldown_steps}",
            {"restored_step": int(restored_step), "cap": cap,
             "attempt": req.attempt, "kind": req.kind,
             "last_good_lr": self._last_good_lr})
        self._rewarm_detectors(int(restored_step))

    def _rewarm_detectors(self, restored_step):
        """Install a fresh DetectorBank (same thresholds) re-warmed by
        replaying the surviving ledger rows, so EWMA state at the replay
        start matches the original pass bit-for-bit where the rows do."""
        from . import detector_bank, last_rows, set_detector_bank
        from .detectors import DetectorBank
        old = detector_bank()
        try:
            bank = DetectorBank(
                ewma_alpha=old._loss.alpha,
                warmup_steps=old.warmup_steps, spike_z=old.spike_z,
                spike_min_rel=old.spike_min_rel,
                divergence_factor=old.divergence_factor,
                divergence_patience=old.divergence_patience,
                plateau_window=old.plateau_window,
                plateau_rel_eps=old.plateau_rel_eps,
                grad_jump=old.grad_jump,
                nonfinite_streak=old.nonfinite_streak)
        except Exception:       # noqa: BLE001 — a custom bank without
            return              # the stock attrs keeps its state
        led = self._ledger()
        rows = led.rows() if led is not None else last_rows(64)
        for r in rows:
            s = r.get("step")
            if r.get("event") == "step" and isinstance(s, int) \
                    and s <= restored_step:
                # replay for state only: anomalies on historical rows
                # were already emitted by the original pass
                bank.observe(r)
        set_detector_bank(bank)

    def lr_for(self, step, lr):
        """The learning rate the next step should actually use.  Inside
        an open anomaly window the first attempt clamps only an
        anomalous excursion (> ``lr_clamp_guard`` x the last good LR) so
        healthy replayed steps stay bit-identical; later attempts apply
        the backoff cap unconditionally."""
        if lr is None:
            return lr
        with self._lock:
            win = self._win
            if win is None or win["cap"] is None:
                return lr
            if not (win["restored_step"] < step <= win["until"]):
                return lr
            cap, guard_base = win["cap"], win["last_good_lr"]
            first = win["attempt"] == 1
        if first and guard_base is not None \
                and lr <= self.lr_clamp_guard * guard_base:
            return lr
        return min(lr, cap)

    def _window_tick(self, step):
        win = self._win
        if win is None or self._pending is not None:
            return
        if step > win["until"]:
            with self._lock:
                if self._win is not win:
                    return
                self._win = None
            self._decide(
                "rewind", "window_close", step,
                f"no recurrence within {self.cooldown_steps} steps of "
                f"the step-{win['anomaly_step']} anomaly: lr cap lifted",
                {"anomaly_step": win["anomaly_step"],
                 "attempt": win["attempt"]})

    # -- stop / abort ------------------------------------------------------
    def _request_stop(self, anom):
        with self._lock:
            if self._should_stop or self._abort_reason is not None:
                return
            self._should_stop = True
        self._stop_decision = self._decide(
            "plateau", "stop", anom.step,
            f"plateau at step {anom.step}: {anom.message} — stopping "
            "early with a final checkpoint",
            {"value": anom.value, "threshold": anom.threshold},
            intervention=True)
        self._inc("stops")

    @property
    def should_stop(self):
        """The training loop's early-stop flag (plateau policy)."""
        with self._lock:
            return self._should_stop

    def note_stopped(self, step):
        """The executor saved the final checkpoint for an early stop."""
        with self._lock:
            if self._stop_decision is not None:
                self._stop_decision.outcome = f"checkpointed@{step}"

    def check_abort(self):
        """Raise :class:`AutopilotAbort` when a policy escalated to
        abort — called at step boundaries so the abort is a clean
        permanent fault, not a mid-step corruption."""
        with self._lock:
            reason = self._abort_reason
        if reason is not None:
            raise AutopilotAbort(f"autopilot abort: {reason}")

    # -- OOM degrade -------------------------------------------------------
    def note_oom(self, step, trainer=None):
        """Called by ``ResilientStep``'s RESOURCE branch before its
        one-purge-retry: pick a degrade lever so the retry actually fits.
        Doubling ``grad_accum`` halves the live microbatch while keeping
        the global batch (and bitwise fp32 grad sums) fixed; failing
        that, tighten the remat policy; failing both, log the denial so
        the crash report says no lever was left."""
        tr = trainer if trainer is not None else self._trainer
        sig = {"step": None if step is None else int(step)}
        if not self.enabled:
            return False
        accum = getattr(tr, "grad_accum", None)
        if tr is not None and hasattr(tr, "set_grad_accum") \
                and isinstance(accum, int) \
                and accum * 2 <= self.max_grad_accum:
            tr.set_grad_accum(accum * 2)
            self._decide(
                "oom", "degrade", step,
                f"device OOM at step {step}: grad_accum {accum} -> "
                f"{accum * 2} (global batch and grad sums unchanged)",
                dict(sig, lever="grad_accum", before=accum,
                     after=accum * 2),
                intervention=True)
            self._inc("degrades")
            return True
        if tr is not None and hasattr(tr, "tighten_remat"):
            try:
                desc = tr.tighten_remat()
            except Exception:   # noqa: BLE001
                desc = None
            if desc:
                self._decide(
                    "oom", "degrade", step,
                    f"device OOM at step {step}: {desc}",
                    dict(sig, lever="remat"), intervention=True)
                self._inc("degrades")
                return True
        self._decide(
            "oom", "denied", step,
            f"device OOM at step {step}: no degrade lever left "
            f"(grad_accum={accum}, max {self.max_grad_accum})",
            dict(sig, lever=None), outcome="denied")
        return False

    # -- MFU policy --------------------------------------------------------
    def _mfu_tick(self, step, mfu):
        import math
        if mfu is None or not isinstance(mfu, (int, float)) \
                or not math.isfinite(mfu) or mfu <= 0:
            return
        if self._mfu_baseline is None:
            self._mfu_samples.append(float(mfu))
            if len(self._mfu_samples) >= self.mfu_window:
                s = sorted(self._mfu_samples)
                self._mfu_baseline = s[len(s) // 2]
                self._mfu_samples = []
            return
        floor = self._mfu_baseline * (1.0 - self.mfu_band_pct / 100.0)
        if mfu < floor:
            self._mfu_bad += 1
            if self._mfu_bad >= self.mfu_patience and self._mfu_armed:
                self._mfu_armed = False
                self._decide(
                    "mfu", "flag", step,
                    f"MFU {mfu:.4f} below the baseline "
                    f"{self._mfu_baseline:.4f} noise band "
                    f"(-{self.mfu_band_pct:g}%) for {self._mfu_bad} "
                    "consecutive steps",
                    {"mfu": float(mfu),
                     "baseline": self._mfu_baseline,
                     "band_pct": self.mfu_band_pct},
                    intervention=True)
                self._inc("flags")
                if self.mfu_abort:
                    with self._lock:
                        self._abort_reason = (
                            f"sustained MFU regression ({mfu:.4f} vs "
                            f"baseline {self._mfu_baseline:.4f})")
        else:
            self._mfu_bad = 0
            # hysteresis: re-arm only once MFU is back inside HALF the
            # band, so a value oscillating on the floor flags once
            if mfu >= self._mfu_baseline * \
                    (1.0 - self.mfu_band_pct / 200.0):
                self._mfu_armed = True

    # -- restart recovery --------------------------------------------------
    def recover_from_ledger(self):
        """Rebuild intervention state from the surviving ledger decision
        rows (they carry ``at_step``, so checkpoint rewinds cannot have
        erased them): spent budgets, the open window, a ``rewind``
        decision with no ``rewound`` completion re-arms the pending
        rewind, ``abort``/``stop`` stick."""
        led = self._ledger()
        if led is None:
            return
        try:
            rows = led.rows()
        except Exception:       # noqa: BLE001
            return
        import math
        pending = None
        with self._lock:
            for r in rows:
                if r.get("event") == "step":
                    # rebuild the (step, lr) trail: a recovered rewind's
                    # cap must come from the lr AT the restored step, and
                    # the "rewind" decision's last_good_lr param can be
                    # the excursion itself (recorded one row before its
                    # loss consequence)
                    s, lr = r.get("step"), r.get("lr")
                    if isinstance(s, int) \
                            and isinstance(lr, (int, float)) \
                            and math.isfinite(lr):
                        self._lr_hist.append((s, float(lr)))
                    continue
                if r.get("event") != "autopilot":
                    continue
                action = r.get("action")
                params = r.get("params") or {}
                self._seq = max(self._seq, int(r.get("seq") or 0))
                if action == "rewind":
                    a = params.get("attempt") or 1
                    pending = RewindRequest(r.get("at_step") or 0,
                                            params.get("kind") or "?",
                                            a)
                    lg = params.get("last_good_lr")
                    if lg is not None:
                        self._last_good_lr = float(lg)
                elif action == "rewound":
                    self._rewinds_total += 1
                    lg = params.get("last_good_lr")
                    if lg is not None:
                        self._last_good_lr = float(lg)
                    if pending is not None:
                        self._win = {
                            "anomaly_step": pending.anomaly_step,
                            "restored_step":
                                int(params.get("restored_step") or 0),
                            "attempt": pending.attempt,
                            "cap": params.get("cap"),
                            "last_good_lr": self._last_good_lr,
                            "until": pending.anomaly_step
                            + self.cooldown_steps,
                        }
                    pending = None
                elif action == "window_close":
                    self._win = None
                elif action == "abort":
                    self._abort_reason = r.get("reason") or "recovered"
                elif action == "stop":
                    self._should_stop = True
            if pending is not None:
                self._pending = pending

    # -- observability -----------------------------------------------------
    def decisions(self):
        """The bounded decision log (oldest first), denied included."""
        with self._lock:
            return [d.as_dict() for d in self._decisions]

    def counters(self):
        with self._lock:
            return dict(self._counters)

    def status(self):
        with self._lock:
            win = dict(self._win) if self._win is not None else None
            return {
                "enabled": self.enabled,
                "pending_rewind": None if self._pending is None else {
                    "anomaly_step": self._pending.anomaly_step,
                    "kind": self._pending.kind,
                    "attempt": self._pending.attempt,
                },
                "window": win,
                "rewinds_total": self._rewinds_total,
                "max_rewinds": self.max_rewinds,
                "last_good_lr": self._last_good_lr,
                "should_stop": self._should_stop,
                "abort_reason": self._abort_reason,
                "mfu_baseline": self._mfu_baseline,
                "counters": dict(self._counters),
            }

    def report_payload(self, last_k=8):
        """The crash report's ``training.autopilot`` section: status +
        the last-K decisions (schema v7, docs/RESILIENCE.md)."""
        out = self.status()
        with self._lock:
            out["decisions"] = [d.as_dict()
                                for d in list(self._decisions)[-int(last_k):]]
        return out
