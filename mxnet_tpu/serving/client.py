"""Loopback HTTP client with optional retry-with-backoff.

The client half of graceful degradation: a 429 (queue full) is a signal
to back off and retry — exponential backoff with decorrelated jitter —
and so are a 503 (server draining/restarting: the request was never
executed) and a connection-level failure (refused/reset/timeout/torn
response while a replica restarts or the network degrades), while a 504
(deadline exceeded) is final for that request.  Timeouts are split:
connection establishment gets its own small budget
(``connect_timeout_s``, default ``min(timeout_s, 5)``) separate from
the read budget, and a request carrying ``deadline_ms`` caps EVERY
attempt's connect and read by the remaining deadline — a hung connect
can no longer eat the whole deadline before the first retry fires.
The transient-vs-permanent split for raw socket errors is
``mxnet_tpu.faults.classify`` — the same policy every retry loop in the
repo uses — so a permanent failure (malformed request, model bug) still
fails fast instead of burning the retry budget.  stdlib-only
(``http.client`` for the split-timeout POST — http or https by scheme —
urllib for the GET endpoints), mirroring the server's JSON+base64
tensor encoding.

Request tracing (docs/OBSERVABILITY.md): with ``MXNET_TRACE_SAMPLE`` > 0
the client mints a trace id per logical request; the id rides the wire
(alongside ``deadline_ms``), stays stable across client retries and
router re-dispatches (only the attempt counter moves), shows up in every
:class:`~mxnet_tpu.serving.errors.ServingError` message and retry log
line, and — because the 200 response carries the server-side breakdown —
:meth:`ServingClient.predict_traced` hands back a per-request waterfall
with zero scraping.
"""
from __future__ import annotations

import http.client
import io
import json
import logging
import os
import queue as _queue
import random as _pyrandom
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

from .. import telemetry as _telemetry
from . import transport as _transport
from .errors import (DeadlineExceededError, GenerationStreamBroken,
                     QueueFullError, ServiceUnavailableError, ServingError)
from .http import decode_array, encode_array

__all__ = ["ServingClient"]

_log = logging.getLogger("mxnet_tpu.serving.client")


def _tr(trace):
    """The ``[trace <id> attempt <n>]`` suffix for error messages and
    log lines (empty when the request is untraced)."""
    return f" [trace {trace.trace_id} attempt {trace.attempt}]" \
        if trace else ""


class ServingClient:
    """Serving HTTP client.

    ``timeout_s`` is the per-attempt **read** budget (request sent →
    response fully read).  ``connect_timeout_s`` bounds connection
    establishment separately — it defaults to ``min(timeout_s, 5.0)``
    so a hung connect (replica restarting, SYN blackholed) surfaces in
    seconds instead of eating the whole read budget before the first
    retry can fire.  When a request carries ``deadline_ms``, every
    attempt's connect *and* read budgets are additionally capped by the
    **remaining** deadline, so the retry loop in :meth:`predict` always
    gets its turn inside the deadline instead of the first attempt
    spending it all.
    """

    def __init__(self, base_url, timeout_s=30.0, connect_timeout_s=None,
                 read_timeout_s=None, pool=None, direct=False):
        self.base_url = base_url.rstrip("/")
        self.timeout_s = float(timeout_s)
        self.read_timeout_s = float(
            read_timeout_s if read_timeout_s is not None else timeout_s)
        self.connect_timeout_s = float(
            connect_timeout_s if connect_timeout_s is not None
            else min(self.timeout_s, 5.0))
        # ``pool``: None -> the process-wide shared keep-alive pool;
        # False -> a fresh connection per request (chipbench's warm-up
        # client uses it so that no parked connection outlives it); or a
        # ConnectionPool instance of your own
        self._pool = _transport.shared_pool() if pool is None \
            else (pool or None)
        self.direct = bool(direct)
        if self.direct:
            from ..util import getenv as _getenv
            import collections as _collections
            self._lease_lock = threading.Lock()
            self._lease = None          # last /leases table
            self._lease_expire = 0.0    # monotonic; 0 = fetch now
            self._credits = {}          # replica key -> admission credits
            self._dinflight = {}        # replica key -> in-flight directs
            self._breakers = {}         # key -> [consec_failures, open_until]
            self._breaker_failures = int(
                _getenv("MXNET_FLEET_BREAKER_FAILURES"))
            self._breaker_open_s = float(_getenv("MXNET_FLEET_BREAKER_OPEN_S"))
            self._hedge_on = bool(_getenv("MXNET_FLEET_HEDGE"))
            self._hedge_rate = float(_getenv("MXNET_FLEET_HEDGE_RATE"))
            self._hedge_tokens = 1.0
            self._lat_ms = _collections.deque(maxlen=256)

    def _post(self, path, payload, deadline_at=None, base=None):
        """One POST with split connect/read timeouts, each capped by the
        remaining deadline (``deadline_at`` = ``time.monotonic()``-clock
        absolute).  Non-200 responses raise ``urllib.error.HTTPError``
        (same surface as the urlopen-based predecessor); socket-level
        failures propagate raw for :meth:`_retryable` to classify.
        ``base`` overrides the target origin (the zero-hop path posts
        straight to a leased replica)."""
        from .. import faults as _faults
        connect_t, read_t = self.connect_timeout_s, self.read_timeout_s
        if deadline_at is not None:
            remaining = deadline_at - time.monotonic()
            if remaining <= 0:
                raise DeadlineExceededError(
                    "client deadline expired before the attempt was sent")
            connect_t = min(connect_t, remaining)
            read_t = min(read_t, remaining)
        url = (base if base is not None else self.base_url) + path
        body = json.dumps(payload).encode("utf-8")
        act = _faults.wire_point("net.connect")
        if act is not None:
            raise act.client_error()
        try:
            if self._pool is not None:
                resp = self._pool.request(
                    url, "POST", body,
                    {"Content-Type": "application/json"},
                    connect_timeout_s=connect_t, read_timeout_s=read_t)
                if resp.status != 200:
                    raise resp.http_error(url)
                return json.loads(resp.data)
            return self._post_fresh(url, body, connect_t, read_t)
        except TimeoutError as e:
            if deadline_at is not None and \
                    time.monotonic() >= deadline_at - 1e-3:
                # the DEADLINE cut this attempt, not the configured
                # socket budget: surface it as the typed final error
                raise DeadlineExceededError(
                    "client deadline expired waiting for the "
                    "response") from e
            raise

    @staticmethod
    def _post_fresh(url, body, connect_t, read_t):
        """The pre-pool wire: dial, POST, read, close."""
        u = urllib.parse.urlsplit(url)
        conn_cls = http.client.HTTPSConnection if u.scheme == "https" \
            else http.client.HTTPConnection
        conn = conn_cls(u.hostname, u.port, timeout=max(connect_t, 1e-3))
        try:
            conn.connect()
            # connection is up: the rest of the attempt runs on the
            # read budget
            conn.sock.settimeout(max(read_t, 1e-3))
            conn.request("POST", u.path or "/", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                raise urllib.error.HTTPError(
                    url, resp.status, resp.reason,
                    resp.headers, io.BytesIO(data))
            return json.loads(data)
        finally:
            conn.close()

    def predict_once(self, arrays, deadline_ms=None, trace=None,
                     idempotent=True):
        """One POST /predict; raises the typed serving errors on
        429/503/504 (connection-level failures propagate raw — see
        :meth:`predict` for the classified retry policy over them).
        ``idempotent=False`` opts a direct-mode request out of hedging
        and post-send re-routing (the router's orphan rule)."""
        outs, _report = self._predict_once(arrays, deadline_ms=deadline_ms,
                                           trace=trace,
                                           idempotent=idempotent)
        return outs

    def predict_traced(self, arrays, deadline_ms=None, trace=None):
        """:meth:`predict_once` returning ``(outputs, report)`` where
        ``report`` is the merged per-request trace: the client-measured
        wall plus the server-side span breakdown the response carried
        (``telemetry.format_request_waterfall(report)`` renders it).
        ``report`` is None when tracing is off or the request was
        sampled out."""
        return self._predict_once(arrays, deadline_ms=deadline_ms,
                                  trace=trace, want_report=True)

    def _predict_once(self, arrays, deadline_ms=None, trace=None,
                      want_report=False, deadline_at=None, idempotent=True):
        if not isinstance(arrays, (tuple, list)):
            arrays = (arrays,)
        if trace is None:
            trace = _telemetry.new_trace()
        if deadline_at is None and deadline_ms is not None:
            deadline_at = time.monotonic() + deadline_ms / 1000.0
        payload = {"inputs": [encode_array(a) for a in arrays]}
        if not idempotent:
            payload["idempotent"] = False
        if deadline_at is not None:
            # the REMAINING budget rides the wire (a retried attempt
            # never hands the server a fresh clock)
            payload["deadline_ms"] = max(
                0.0, (deadline_at - time.monotonic()) * 1000.0)
        if trace:
            payload["trace"] = trace.wire()
        t_wall0 = _telemetry._wall_us() if trace else 0
        hop = "routed"
        try:
            if self.direct:
                out, hop = self._route_direct(payload, deadline_at, trace,
                                              idempotent)
            else:
                out = self._post("/predict", payload,
                                 deadline_at=deadline_at)
        except urllib.error.HTTPError as e:
            body = e.read()
            try:
                obj = json.loads(body)
                # prefer the server's diagnostic detail over the short
                # error code — it carries the actual exception text
                detail = obj.get("detail") or obj.get("error", "")
            except Exception:       # noqa: BLE001
                detail = body[:200].decode("utf-8", "replace")
            detail = f"{detail}{_tr(trace)}"
            if e.code == 429:
                raise QueueFullError(detail) from None
            if e.code == 503:
                raise ServiceUnavailableError(detail) from None
            if e.code == 504:
                raise DeadlineExceededError(detail) from None
            raise ServingError(f"HTTP {e.code}: {detail}") from None
        t_recv = _telemetry._wall_us() if trace else 0
        wall_ms = (t_recv - t_wall0) / 1000.0 if trace else None
        report = None
        if trace:
            # own spans carry NO proc tag (so the spool keeps them, like
            # every other hop); the report below labels them for display
            trace.add_span("client_request", t_wall0, wall_ms * 1000.0,
                           url=self.base_url, hop=hop)
            resp_trace = out.get("trace")
            if resp_trace:
                # reply transport: the server stamped sent_us right
                # before writing the response body
                sent = resp_trace.get("sent_us")
                if sent and t_recv > sent:
                    trace.add_span("client_receive", sent, t_recv - sent)
                for reason in resp_trace.get("keep") or ():
                    if reason not in ("sampled", "slow"):
                        trace.mark(reason)
                if want_report:
                    trace.merge(resp_trace.get("spans"))
            _telemetry.maybe_spool(trace, wall_ms, role="client")
            if want_report:
                spans = trace.spans()
                for s in spans:
                    s.setdefault("proc", f"client:{os.getpid()}")
                report = {"trace_id": trace.trace_id, "wall_ms": wall_ms,
                          "keep": trace.marks, "spans": spans}
        outs = tuple(decode_array(o) for o in out["outputs"])
        return (outs if len(outs) > 1 else outs[0]), report

    # -- zero-hop data path (docs/SERVING.md) ------------------------------
    # The router stays the control plane: this client leases replica
    # endpoints + admission credits from RouterServer /leases and posts
    # straight to the replica ModelServers, skipping the router hop.
    # Backpressure is router-mediated — credits run out or the lease TTL
    # expires and the client must re-ask; an epoch bump (scale-down,
    # rolling swap, breaker trip) revokes the table wholesale.  ANY
    # failure on the direct path falls back to the routed POST — never a
    # lost request.
    def leases(self, force=False):
        """Fetch/refresh the lease table (direct mode); returns it."""
        with self._lease_lock:
            self._refresh_lease_locked(force=force)
            return self._lease

    def _refresh_lease_locked(self, force=False):
        now = time.monotonic()
        if not force and self._lease is not None \
                and now < self._lease_expire:
            return
        try:
            table = self._get_json("/leases")
        except Exception:               # noqa: BLE001 — router unreachable:
            # the routed fallback path will surface real failures
            self._lease = None
            self._lease_expire = now + 0.05
            return
        _transport._inc("lease_refreshes")
        self._credits = {
            str(k): int(v.get("credits", 0))
            for k, v in (table.get("replicas") or {}).items()}
        self._lease = table
        self._lease_expire = now + max(0.05, float(table.get("ttl_s", 1.0)))

    def _direct_pick(self, exclude=()):
        """Checkout a leased replica: credits > 0, breaker closed,
        least in-flight.  Burns one credit; returns (key, url) or None.
        An empty first scan force-refreshes the lease once — exhausted
        credits are the router's backpressure signal, and re-asking is
        how the client honors a raised grant."""
        with self._lease_lock:
            for attempt in (0, 1):
                self._refresh_lease_locked(force=(attempt == 1))
                lease = self._lease
                if not lease:
                    return None
                now = time.monotonic()
                best = None
                for key, rep in (lease.get("replicas") or {}).items():
                    key = str(key)
                    if key in exclude or self._credits.get(key, 0) <= 0:
                        continue
                    br = self._breakers.get(key)
                    if br is not None and now < br[1]:
                        continue
                    load = self._dinflight.get(key, 0)
                    if best is None or load < best[2]:
                        best = (key, rep["url"], load)
                if best is not None:
                    key, url, _ = best
                    self._credits[key] -= 1
                    self._dinflight[key] = self._dinflight.get(key, 0) + 1
                    return key, url
            return None

    def _direct_release(self, key, ok):
        with self._lease_lock:
            self._dinflight[key] = max(0, self._dinflight.get(key, 1) - 1)
            br = self._breakers.setdefault(key, [0, 0.0])
            if ok:
                br[0] = 0
            else:
                br[0] += 1
                if br[0] >= self._breaker_failures:
                    # client-side breaker: stop picking this replica for
                    # the open window, and re-ask the router early (it
                    # sees the same failures and revokes via epoch bump)
                    br[:] = [0, time.monotonic() + self._breaker_open_s]
                    self._lease_expire = 0.0
                    _transport._inc("direct_breaker_opens")

    def _direct_attempt(self, pick, payload, deadline_at, trace,
                        idempotent, hedged=False):
        """One POST straight at a leased replica.  Returns ``("ok",
        out)``, ``("fallback", exc)`` (re-route through the router), or
        ``("final", exc)`` (raise — deadline/model errors, and post-send
        failures of non-idempotent work, which a re-route could
        double-execute)."""
        key, url = pick
        t0 = _telemetry._wall_us() if trace else 0
        t_perf = time.perf_counter()

        def span(outcome):
            if trace:
                trace.add_span("direct_dispatch", t0,
                               _telemetry._wall_us() - t0, replica=key,
                               outcome=outcome, hedge=hedged, hop="direct")
        try:
            out = self._post("/predict", payload, deadline_at=deadline_at,
                             base=url)
        except urllib.error.HTTPError as e:
            # 429: replica queue full — healthy, just loaded (no breaker
            # strike); 503: draining/restarting.  Both re-route.
            self._direct_release(key, ok=(e.code == 429))
            span(f"http_{e.code}")
            if e.code in (429, 503):
                return ("fallback", e)
            return ("final", e)
        except DeadlineExceededError as e:
            self._direct_release(key, ok=True)
            span("deadline")
            return ("final", e)
        except (ConnectionRefusedError, ConnectionError, TimeoutError,
                OSError, http.client.HTTPException) as e:
            self._direct_release(key, ok=False)
            span("connection_error")
            if idempotent or isinstance(e, ConnectionRefusedError):
                # refused = nothing was sent (safe for everyone); other
                # connection-level failures may have executed — only
                # idempotent work re-routes (the router's orphan rule)
                return ("fallback", e)
            return ("final", e)
        self._direct_release(key, ok=True)
        _transport._inc("direct_dispatches")
        span("ok")
        with self._lease_lock:
            self._lat_ms.append((time.perf_counter() - t_perf) * 1000.0)
        return ("ok", out)

    def _hedge_delay_s(self):
        """p95-derived hedge delay over recent direct latencies (None
        until warm — mirrors the router's hedge scheduler)."""
        with self._lease_lock:
            if not self._hedge_on or len(self._lat_ms) < 32:
                return None
            xs = sorted(self._lat_ms)
            return max(xs[int(len(xs) * 0.95)] / 1000.0, 1e-3)

    def _hedge_admit(self):
        """Token bucket: hedges cost 1, deposits are ``hedge_rate`` per
        direct request (same budget shape as the router's)."""
        with self._lease_lock:
            self._hedge_tokens = min(self._hedge_tokens + self._hedge_rate,
                                     10.0)
            if self._hedge_tokens >= 1.0:
                self._hedge_tokens -= 1.0
                return True
            return False

    def _direct_predict(self, payload, deadline_at, trace, idempotent):
        """One direct-path attempt, hedged when warm + idempotent +
        budget allows.  None = no usable lease (go routed)."""
        pick = self._direct_pick()
        if pick is None:
            return None
        delay_s = self._hedge_delay_s() if idempotent else None
        if delay_s is None:
            return self._direct_attempt(pick, payload, deadline_at, trace,
                                        idempotent)
        box = _queue.Queue()

        def run(p, hedged):
            box.put((self._direct_attempt(p, payload, deadline_at, trace,
                                          idempotent, hedged=hedged),
                     hedged))

        threading.Thread(target=run, args=(pick, False),
                         daemon=True).start()
        budget_s = self.connect_timeout_s + self.read_timeout_s + 1.0
        try:
            res, hedged = box.get(timeout=delay_s)
        except _queue.Empty:
            pick2 = self._direct_pick(exclude={pick[0]}) \
                if self._hedge_admit() else None
            if pick2 is not None:
                _transport._inc("direct_hedges")
                threading.Thread(target=run, args=(pick2, True),
                                 daemon=True).start()
            try:
                res, hedged = box.get(timeout=budget_s)
            except _queue.Empty:        # pragma: no cover — socket budgets
                return ("fallback", TimeoutError("direct attempt hung"))
            if hedged and res[0] == "ok":
                _transport._inc("direct_hedge_wins")
        return res

    def _route_direct(self, payload, deadline_at, trace, idempotent):
        """The zero-hop dispatch decision: direct when a lease allows,
        the routed POST otherwise or on any re-routable direct failure.
        Returns ``(out, hop)``."""
        res = self._direct_predict(payload, deadline_at, trace, idempotent)
        if res is not None:
            status, value = res
            if status == "ok":
                return value, "direct"
            if status == "final":
                raise value
        # revoked lease / exhausted credits / replica failure: through
        # the router — it re-routes, sheds, or fails authoritatively
        _transport._inc("direct_fallbacks")
        if trace:
            trace.mark("direct_fallback")
        if deadline_at is not None:
            payload["deadline_ms"] = max(
                0.0, (deadline_at - time.monotonic()) * 1000.0)
        return (self._post("/predict", payload, deadline_at=deadline_at),
                "routed_fallback")

    @staticmethod
    def _retryable(exc):
        """Is this failure worth another attempt?

        429 (nothing was enqueued) and 503 (server refusing work while
        draining/restarting) are always safe.  Connection-level errors —
        refused/reset during a replica restart window, timeouts — go
        through ``faults.classify`` so deterministic failures stay fatal;
        note a reset/timeout can land AFTER the server started executing,
        so only retry non-idempotent work against a server you know sheds
        duplicates.  504s and HTTP-level model errors are final.
        """
        if isinstance(exc, (QueueFullError, ServiceUnavailableError)):
            return True
        if isinstance(exc, (DeadlineExceededError, ServingError)):
            return False
        if isinstance(exc, (urllib.error.URLError, ConnectionError,
                            TimeoutError, OSError,
                            http.client.HTTPException)):
            # http.client.HTTPException covers the torn-wire shapes a
            # degraded network produces (IncompleteRead: the connection
            # died mid-response; BadStatusLine: mid-status) — classified
            # like any other connection-level failure
            from .. import faults as _faults
            root = exc.reason if isinstance(exc, urllib.error.URLError) \
                and exc.reason is not None else exc
            return _faults.classify(root) == _faults.TRANSIENT
        return False

    def predict(self, arrays, deadline_ms=None, max_retries=0,
                backoff_ms=25.0, max_backoff_ms=1000.0):
        """:meth:`predict_once` + retry-with-backoff on retryable failures
        (queue-full, 503-unavailable, and transient connection-level
        errors — see :meth:`_retryable`); deadline expiries and model
        errors are final.  ``deadline_ms`` is the budget for the WHOLE
        retry loop: each attempt's connect/read timeouts are capped by
        what remains, backoff sleeps never overrun it, and an exhausted
        budget raises :class:`DeadlineExceededError` carrying the last
        failure as ``__cause__``.  One trace id covers every attempt —
        the attempt counter moves, the id never does."""
        delay = backoff_ms / 1000.0
        trace = _telemetry.new_trace()
        deadline_at = time.monotonic() + deadline_ms / 1000.0 \
            if deadline_ms is not None else None
        for attempt in range(max_retries + 1):
            try:
                outs, _report = self._predict_once(
                    arrays, deadline_ms=deadline_ms, trace=trace,
                    deadline_at=deadline_at)
                return outs
            except Exception as e:          # noqa: BLE001 — classified below
                if attempt == max_retries or not self._retryable(e):
                    raise
                # decorrelated jitter keeps retry storms from re-synching
                sleep_s = delay * (0.5 + _pyrandom.random())
                if deadline_at is not None:
                    remaining = deadline_at - time.monotonic()
                    if remaining <= sleep_s:
                        raise DeadlineExceededError(
                            f"client deadline ({deadline_ms:.0f} ms) "
                            f"exhausted after {attempt + 1} attempt(s); "
                            f"last failure: {e!r}{_tr(trace)}") from e
                _log.info("retrying request%s after %r (client attempt "
                          "%d/%d)", _tr(trace), e, attempt + 1,
                          max_retries)
                if trace:
                    trace.mark("retried")
                    trace.attempt += 1
                time.sleep(sleep_s)
                delay = min(delay * 2.0, max_backoff_ms / 1000.0)

    # -- generation --------------------------------------------------------
    @staticmethod
    def _gen_error(e, trace):
        """Map a /generate HTTPError to the typed serving errors."""
        body = e.read()
        try:
            obj = json.loads(body)
            detail = obj.get("detail") or obj.get("error", "")
        except Exception:           # noqa: BLE001
            detail = body[:200].decode("utf-8", "replace")
        detail = f"{detail}{_tr(trace)}"
        if e.code == 429:
            return QueueFullError(detail)
        if e.code == 503:
            return ServiceUnavailableError(detail)
        if e.code == 504:
            return DeadlineExceededError(detail)
        return ServingError(f"HTTP {e.code}: {detail}")

    def _gen_payload(self, tokens, max_new_tokens, eos_id, trace, stream):
        payload = {"tokens": [int(t) for t in tokens],
                   "max_new_tokens": int(max_new_tokens),
                   "stream": bool(stream)}
        if eos_id is not None:
            payload["eos_id"] = int(eos_id)
        if trace:
            payload["trace"] = trace.wire()
        return payload

    def generate(self, tokens, max_new_tokens=32, eos_id=None, trace=None):
        """One non-streaming ``POST /generate``: blocks for the whole
        completion, returns the result dict (``tokens``, ``finish_reason``,
        ``ttft_ms``, ``tokens_per_s``, ``latency_ms`` and, when traced,
        the server-side ``trace`` breakdown)."""
        if trace is None:
            trace = _telemetry.new_trace()
        payload = self._gen_payload(tokens, max_new_tokens, eos_id, trace,
                                    stream=False)
        try:
            return self._post("/generate", payload)
        except urllib.error.HTTPError as e:
            raise self._gen_error(e, trace) from None

    def generate_stream(self, tokens, max_new_tokens=32, eos_id=None,
                        trace=None):
        """Streaming ``POST /generate``: a generator yielding token ids
        as the JSONL lines land; its ``return`` value (``StopIteration
        .value`` / the result of ``yield from``) is the final result
        dict.  A stream that dies after delivering tokens raises
        :class:`GenerationStreamBroken` carrying the tokens seen so far;
        a failure before ANY line is a plain connection error (safe to
        retry elsewhere — nothing was consumed)."""
        if trace is None:
            trace = _telemetry.new_trace()
        payload = self._gen_payload(tokens, max_new_tokens, eos_id, trace,
                                    stream=True)
        u = urllib.parse.urlsplit(self.base_url + "/generate")
        body = json.dumps(payload).encode("utf-8")
        conn_cls = http.client.HTTPSConnection if u.scheme == "https" \
            else http.client.HTTPConnection
        conn = conn_cls(u.hostname, u.port,
                        timeout=max(self.connect_timeout_s, 1e-3))
        seen = []
        try:
            conn.connect()
            conn.sock.settimeout(max(self.read_timeout_s, 1e-3))
            conn.request("POST", u.path or "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise urllib.error.HTTPError(
                    self.base_url + "/generate", resp.status, resp.reason,
                    resp.headers, io.BytesIO(resp.read()))
            while True:
                line = resp.readline()
                if not line:
                    # close-delimited stream ended with no final record:
                    # the replica died mid-generation
                    raise GenerationStreamBroken(
                        f"stream closed after {len(seen)} token(s) with "
                        f"no final record{_tr(trace)}",
                        trace_id=trace.trace_id if trace else None,
                        tokens=seen)
                obj = json.loads(line)
                if "token" in obj:
                    seen.append(int(obj["token"]))
                    yield int(obj["token"])
                    continue
                if obj.get("error"):
                    raise GenerationStreamBroken(
                        f"{obj.get('detail') or obj['error']}{_tr(trace)}",
                        trace_id=obj.get("trace_id") or
                        (trace.trace_id if trace else None), tokens=seen)
                return obj          # the final record
        except urllib.error.HTTPError as e:
            raise self._gen_error(e, trace) from None
        except (ConnectionError, TimeoutError, OSError,
                http.client.HTTPException) as e:
            if seen:
                # tokens were consumed: NOT transparently retryable —
                # surface the typed mid-stream break (docs/RESILIENCE.md)
                raise GenerationStreamBroken(
                    f"connection died after {len(seen)} token(s): "
                    f"{e!r}{_tr(trace)}",
                    trace_id=trace.trace_id if trace else None,
                    tokens=seen) from e
            raise
        finally:
            conn.close()

    def _get_json(self, path):
        """GET through the shared pool with the same split
        connect/read budgets and error surface as the POST machinery
        (non-200 raises ``urllib.error.HTTPError``)."""
        url = self.base_url + path
        if self._pool is not None:
            return self._pool.get_json(
                url, connect_timeout_s=self.connect_timeout_s,
                read_timeout_s=self.read_timeout_s)
        with urllib.request.urlopen(url, timeout=self.timeout_s) as resp:
            return json.loads(resp.read())

    def stats(self):
        return self._get_json("/stats")

    def healthy(self):
        try:
            return self._get_json("/healthz").get("status") == "ok"
        except Exception:           # noqa: BLE001
            return False
