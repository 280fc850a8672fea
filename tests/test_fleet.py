"""mxnet_tpu.serving.fleet — router dispatch/retry/shed semantics (fast,
tier-1, in-process stub replicas) and the supervised multi-process chaos
proofs (``@pytest.mark.slow`` per the standing tier-1 rule): injected
kill + hang at ``serving.replica``, supervisor restart, router retry
with no double-execution, and the zero-drop rolling weight swap."""
import socket
import struct
import threading
import time

import numpy as onp
import pytest

from mxnet_tpu import faults, serving, telemetry
from mxnet_tpu.base import MXNetError


def _identity2x(x):
    return (onp.asarray(x) * 2.0,)


class _SlowModel:
    def __init__(self, delay_s):
        self.delay_s = delay_s

    def __call__(self, x):
        time.sleep(self.delay_s)
        return (onp.asarray(x) * 2.0,)


def _server(model=_identity2x, buckets=(1, 2, 4), max_delay_ms=0.5,
            max_queue=64):
    engine = serving.InferenceEngine(model, batch_buckets=buckets)
    batcher = serving.DynamicBatcher(engine, max_batch_size=buckets[-1],
                                     max_delay_ms=max_delay_ms,
                                     max_queue=max_queue)
    return serving.ModelServer(batcher, port=0).start(), engine


def _fleet_counter(name):
    return telemetry.snapshot()["counters"]["fleet/" + name]


def _dead_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _ResetStub:
    """Raw TCP stub that accepts a connection, counts it, then resets it
    mid-request — a replica dying after the request was sent."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.hits = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.hits += 1
            try:
                conn.recv(65536)
                # SO_LINGER(1, 0): close() sends RST — an unambiguous
                # connection-reset, not a clean EOF
                conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                struct.pack("ii", 1, 0))
            finally:
                conn.close()

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


# -- classification ---------------------------------------------------------

def test_fleet_parent_initialises_no_backend():
    """A chip belongs to one process, and the replicas are the processes
    that need it: importing ``mxnet_tpu.serving`` and constructing the
    supervisor and router in the parent must start no jax backend
    (docs/SERVING.md "Replicas and chips")."""
    import os
    import subprocess
    import sys
    code = (
        "import mxnet_tpu.serving\n"
        "from mxnet_tpu.serving import fleet\n"
        "sup = fleet.ReplicaSupervisor("
        "fleet.ReplicaSpec(model_factory=int), n_replicas=2)\n"
        "router = fleet.Router(sup)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('NO_BACKEND')\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=repo))
    assert r.returncode == 0 and "NO_BACKEND" in r.stdout, r.stderr[-2000:]


def test_classify_exit():
    assert faults.classify_exit(None) == faults.TRANSIENT
    assert faults.classify_exit(-9) == faults.TRANSIENT       # SIGKILL
    assert faults.classify_exit(-15) == faults.TRANSIENT      # SIGTERM
    assert faults.classify_exit(faults.FAULT_CRASH_EXIT_CODE) \
        == faults.TRANSIENT                                   # injected crash
    assert faults.classify_exit(0) == faults.TRANSIENT        # clean surprise
    assert faults.classify_exit(1) == faults.PERMANENT        # uncaught exc


# -- router over static backends -------------------------------------------

def test_router_least_loaded_dispatch_spreads_load():
    s1, e1 = _server()
    s2, e2 = _server()
    x = onp.ones(4, dtype="float32")
    # hedging off: this test counts EXACT executions per replica, and a
    # hedged attempt is by design a second execution of the same request
    with serving.Router([s1.url, s2.url], hedging=False) as router:
        futs = [router.submit(x) for _ in range(40)]
        outs = [f.result(timeout=30) for f in futs]
    for o in outs:
        onp.testing.assert_allclose(o, x * 2.0)
    n1 = e1.metrics.stats()["counters"]["batched_requests"]
    n2 = e2.metrics.stats()["counters"]["batched_requests"]
    assert n1 + n2 == 40
    # least-loaded, not primary/backup: both replicas saw traffic
    assert n1 > 0 and n2 > 0
    assert router.outstanding == 0
    s1.stop()
    s2.stop()


def test_router_dispatch_fault_point_transient_retries():
    s1, _ = _server()
    x = onp.ones(4, dtype="float32")
    before = _fleet_counter("retries")
    with serving.Router([s1.url]) as router:
        with faults.inject("router.dispatch@1:transient"):
            out = router.predict(x, timeout=30)
    onp.testing.assert_allclose(out, x * 2.0)
    # the injected failure fired before anything was sent: safely
    # re-dispatched, transparently to the caller
    assert _fleet_counter("retries") >= before + 1
    s1.stop()


def test_router_dispatch_permanent_fault_fails_fast():
    s1, engine = _server()
    x = onp.ones(4, dtype="float32")
    with serving.Router([s1.url]) as router:
        with faults.inject("router.dispatch@1:permanent"):
            with pytest.raises(faults.PermanentFault):
                router.predict(x, timeout=30)
    # permanent means permanent: the replica never saw the request
    assert engine.metrics.stats()["counters"]["batched_requests"] == 0
    s1.stop()


def test_router_retries_connection_refused_to_live_replica():
    s1, _ = _server()
    dead = f"http://127.0.0.1:{_dead_port()}"
    x = onp.ones(4, dtype="float32")
    before = _fleet_counter("retries")
    # dead endpoint sorts first (key 0): every first dispatch is refused
    with serving.Router([dead, s1.url]) as router:
        out = router.predict(x, timeout=30)
    onp.testing.assert_allclose(out, x * 2.0)
    assert _fleet_counter("retries") >= before + 1
    s1.stop()


def test_router_no_double_execution_of_non_idempotent_request():
    stub = _ResetStub()
    s1, engine = _server()
    x = onp.ones(4, dtype="float32")
    # non-idempotent: the connection died after the request was sent —
    # the stub may have executed it, so the router must NOT re-dispatch
    with serving.Router([stub.url, s1.url]) as router:
        with pytest.raises(serving.ServiceUnavailableError):
            router.predict(x, idempotent=False, timeout=30)
    assert stub.hits == 1
    assert engine.metrics.stats()["counters"]["batched_requests"] == 0
    # idempotent (the default): the same orphaning failure re-dispatches
    before = _fleet_counter("orphans")
    with serving.Router([stub.url, s1.url], cooldown_s=0.0) as router:
        out = router.predict(x, timeout=30)
    onp.testing.assert_allclose(out, x * 2.0)
    assert stub.hits == 2
    assert engine.metrics.stats()["counters"]["batched_requests"] == 1
    assert _fleet_counter("orphans") >= before + 1
    stub.close()
    s1.stop()


def test_fleet_level_shedding_on_outstanding_cap():
    s1, _ = _server(model=_SlowModel(0.5), buckets=(1,), max_delay_ms=0.0)
    x = onp.ones(4, dtype="float32")
    before = _fleet_counter("shed")
    with serving.Router([s1.url], max_outstanding=2) as router:
        f1 = router.submit(x)
        f2 = router.submit(x)
        t0 = time.perf_counter()
        with pytest.raises(serving.QueueFullError):
            router.submit(x)
        # fast-reject: the SLO breach answers immediately, no queueing
        assert time.perf_counter() - t0 < 0.05
        assert f1.result(timeout=30) is not None
        assert f2.result(timeout=30) is not None
        # the burst over, the fleet serves again: shedding left no
        # outstanding count behind
        assert router.submit(x).result(timeout=30) is not None
    assert _fleet_counter("shed") >= before + 1
    s1.stop()


def test_router_drain_blocks_dispatch_and_deadline_sheds():
    s1, _ = _server()
    x = onp.ones(4, dtype="float32")
    with serving.Router([s1.url]) as router:
        router.drain(0)           # nothing in flight: returns immediately
        # the only replica is draining: the request cannot dispatch and
        # its deadline expires router-side
        fut = router.submit(x, deadline_ms=80)
        with pytest.raises(serving.DeadlineExceededError):
            fut.result(timeout=10)
        router.admit(0)
        onp.testing.assert_allclose(router.predict(x, timeout=30), x * 2.0)
    s1.stop()


def test_router_server_http_front():
    s1, _ = _server()
    x = onp.random.RandomState(0).randn(4).astype("float32")
    router = serving.Router([s1.url])
    with serving.RouterServer(router, port=0) as srv:
        client = serving.ServingClient(srv.url)
        assert client.healthy()
        out = client.predict(x, deadline_ms=5000)
        onp.testing.assert_allclose(out, x * 2.0, rtol=1e-6)
        import json
        import urllib.request
        with urllib.request.urlopen(srv.url + "/statusz", timeout=10) as r:
            payload = json.loads(r.read())
        assert "fleet" in payload and "endpoints" in payload["fleet"]
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "mxnet_fleet_dispatches" in text
        assert "mxnet_fleet_replicas_up" in text
    s1.stop()


# -- wire-level fault injection (net.* points, docs/RESILIENCE.md) ----------

def test_net_response_delay_slows_the_wire():
    s1, _ = _server()
    x = onp.ones(4, dtype="float32")
    client = serving.ServingClient(s1.url)
    with faults.inject("net.response@1:delay(120)"):
        t0 = time.perf_counter()
        out = client.predict_once(x)
        dt = time.perf_counter() - t0
    onp.testing.assert_allclose(out, x * 2.0)
    assert dt >= 0.1, dt
    s1.stop()


def test_net_response_torn_is_retryable_and_router_reroutes():
    import http.client as _hc
    s1, _ = _server()
    x = onp.ones(4, dtype="float32")
    client = serving.ServingClient(s1.url)
    # torn mid-body: the client sees an incomplete read off a closed
    # socket — a transient connection-level failure, retried
    with faults.inject("net.response@1:torn(8)"):
        with pytest.raises((_hc.HTTPException, ConnectionError)) as ei:
            client.predict_once(x)
        assert serving.ServingClient._retryable(ei.value)
    with faults.inject("net.response@1:torn(8)"):
        out = client.predict(x, max_retries=2)
    onp.testing.assert_allclose(out, x * 2.0)
    # at the router, a torn response is an ORPHAN (the replica may have
    # executed): idempotent requests re-route, transparently
    s2, _ = _server()
    before = _fleet_counter("orphans")
    with serving.Router([s1.url, s2.url], cooldown_s=0.0) as router:
        with faults.inject("net.response@1:torn(4)"):
            out = router.predict(x, timeout=30)
    onp.testing.assert_allclose(out, x * 2.0)
    assert _fleet_counter("orphans") >= before + 1
    s1.stop()
    s2.stop()


def test_net_request_reset_abandons_exchange_and_client_retries():
    s1, _ = _server()
    x = onp.ones(4, dtype="float32")
    client = serving.ServingClient(s1.url)
    # the server drops the inbound request without a reply: the client
    # sees the connection die and its classified retry recovers
    with faults.inject("net.request@1:reset"):
        out = client.predict(x, max_retries=2)
    onp.testing.assert_allclose(out, x * 2.0)
    s1.stop()


def test_net_connect_blackhole_partitions_then_reroutes():
    s1, _ = _server()
    s2, _ = _server()
    x = onp.ones(4, dtype="float32")
    before = _fleet_counter("retries")
    # the router->replica connect is blackholed (sleeps the partition
    # window, then times out): nothing was sent, so ANY request
    # re-routes safely — the wire-level partition analogue of a refused
    # connection
    with serving.Router([s1.url, s2.url], cooldown_s=0.0) as router:
        with faults.inject("net.connect@1:blackhole(0.2)"):
            t0 = time.perf_counter()
            out = router.predict(x, timeout=30)
            dt = time.perf_counter() - t0
    onp.testing.assert_allclose(out, x * 2.0)
    assert dt >= 0.15, dt
    assert _fleet_counter("retries") >= before + 1
    s1.stop()
    s2.stop()


# -- circuit breakers --------------------------------------------------------

def test_breaker_trips_on_consecutive_failures_probe_reopens_then_closes():
    s1, _ = _server()
    dead = _dead_port()
    x = onp.ones(4, dtype="float32")
    trips0 = _fleet_counter("breaker_trips")
    closes0 = _fleet_counter("breaker_closes")
    router = serving.Router(
        [f"http://127.0.0.1:{dead}", s1.url], cooldown_s=0.0,
        breaker_failures=2, breaker_open_s=0.2, hedging=False).start()
    try:
        # two requests = two refused connects on replica 0 -> trip
        for _ in range(2):
            onp.testing.assert_allclose(router.predict(x, timeout=30),
                                        x * 2.0)
        st = router.breaker_status()
        assert st[0]["state"] == "open" and st[0]["trips"] >= 1
        assert _fleet_counter("breaker_trips") >= trips0 + 1
        # while open, dispatch skips replica 0 entirely (no more
        # connection attempts, no retry churn)
        before = _fleet_counter("retries")
        onp.testing.assert_allclose(router.predict(x, timeout=30), x * 2.0)
        assert _fleet_counter("retries") == before
        # a replica comes up on the dead port; the half-open probe
        # (admitted after open_s) closes the breaker
        engine = serving.InferenceEngine(_identity2x, batch_buckets=(1, 2))
        batcher = serving.DynamicBatcher(engine, max_batch_size=2,
                                         max_delay_ms=0.5)
        s_revived = serving.ModelServer(batcher, port=dead).start()
        time.sleep(0.25)             # open_s elapses: probe window
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                router.breaker_status()[0]["state"] != "closed":
            router.predict(x, timeout=30)
            time.sleep(0.05)
        assert router.breaker_status()[0]["state"] == "closed"
        assert _fleet_counter("breaker_closes") >= closes0 + 1
        s_revived.stop()
    finally:
        router.stop()
        s1.stop()


def test_breaker_latency_ewma_routes_around_slow_replica():
    slow_model = _SlowModel(0.12)
    s_slow, _ = _server(model=slow_model, buckets=(1,), max_delay_ms=0.0)
    s_fast, _ = _server(buckets=(1,), max_delay_ms=0.0)
    x = onp.ones(4, dtype="float32")
    router = serving.Router(
        [s_slow.url, s_fast.url], cooldown_s=0.0, hedging=False,
        breaker_failures=1000, breaker_latency_ms=40.0,
        breaker_latency_ratio=2.0, breaker_open_s=0.25).start()
    try:
        # parallel pairs: least-loaded spreads one request to each
        # replica, so BOTH build a latency EWMA (the slow one needs 5+
        # samples before the trip arms)
        for _ in range(8):
            futs = [router.submit(x) for _ in range(2)]
            for f in futs:
                f.result(timeout=30)
        st = router.breaker_status()
        assert st[0]["state"] == "open", st
        assert st[0]["trip_reason"] == "latency"
        # routed around within milliseconds now: requests stop paying
        # the slow replica's 120 ms
        t0 = time.perf_counter()
        for _ in range(3):
            router.predict(x, timeout=30)
        assert time.perf_counter() - t0 < 0.25
        # the replica heals; the half-open probe sees a fast response
        # and closes the breaker
        slow_model.delay_s = 0.0
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                router.breaker_status()[0]["state"] != "closed":
            router.predict(x, timeout=30)
            time.sleep(0.05)
        assert router.breaker_status()[0]["state"] == "closed"
    finally:
        router.stop()
        s_slow.stop()
        s_fast.stop()


# -- hedged dispatch ---------------------------------------------------------

def _warm_hedge_p95(router, x, n=12, exclude=None):
    """Build the router's latency ring off the fast replica(s) so the
    p95-derived hedge delay arms."""
    if exclude is not None:
        router.drain(exclude, timeout=30)
    for _ in range(n):
        router.predict(x, timeout=30)
    if exclude is not None:
        router.admit(exclude)


def test_hedged_dispatch_first_response_wins():
    slow = _SlowModel(0.6)
    s_slow, _ = _server(model=slow, buckets=(1,), max_delay_ms=0.0)
    s_fast, _ = _server(buckets=(1,), max_delay_ms=0.0)
    x = onp.ones(4, dtype="float32")
    hedges0 = _fleet_counter("hedges")
    wins0 = _fleet_counter("hedge_wins")
    router = serving.Router(
        [s_slow.url, s_fast.url], cooldown_s=0.0, breakers=False,
        hedging=True, hedge_rate=1.0, hedge_min_samples=8).start()
    try:
        _warm_hedge_p95(router, x, exclude=0)
        assert router.hedge_delay_ms() is not None
        # idle fleet: key 0 (slow) wins the least-loaded tie; after the
        # p95-derived delay the hedge races the fast replica and wins
        t0 = time.perf_counter()
        out = router.predict(x, timeout=30)
        dt = time.perf_counter() - t0
        onp.testing.assert_allclose(out, x * 2.0)
        assert dt < 0.5, dt          # never paid the slow replica's 600ms
        assert _fleet_counter("hedges") >= hedges0 + 1
        assert _fleet_counter("hedge_wins") >= wins0 + 1
    finally:
        router.stop()
        s_slow.stop()
        s_fast.stop()


def test_hedge_budget_bounds_and_non_idempotent_never_hedges():
    slow = _SlowModel(0.4)
    s_slow, _ = _server(model=slow, buckets=(1,), max_delay_ms=0.0)
    s_fast, _ = _server(buckets=(1,), max_delay_ms=0.0)
    x = onp.ones(4, dtype="float32")
    denied0 = _fleet_counter("hedge_denied")
    router = serving.Router(
        [s_slow.url, s_fast.url], cooldown_s=0.0, breakers=False,
        hedging=True, hedge_rate=0.0, hedge_min_samples=8).start()
    try:
        _warm_hedge_p95(router, x, exclude=0)
        hedges0 = _fleet_counter("hedges")
        # rate cap 0: the token bucket never funds a hedge — the hard
        # budget means hedging cannot amplify load, ever
        t0 = time.perf_counter()
        router.predict(x, timeout=30)
        assert time.perf_counter() - t0 >= 0.35
        assert _fleet_counter("hedges") == hedges0
        assert _fleet_counter("hedge_denied") >= denied0 + 1
    finally:
        router.stop()
    router = serving.Router(
        [s_slow.url, s_fast.url], cooldown_s=0.0, breakers=False,
        hedging=True, hedge_rate=1.0, hedge_min_samples=8).start()
    try:
        _warm_hedge_p95(router, x, exclude=0)
        hedges0 = _fleet_counter("hedges")
        # non-idempotent requests are never hedged: a hedge IS a second
        # execution
        t0 = time.perf_counter()
        router.predict(x, idempotent=False, timeout=30)
        assert time.perf_counter() - t0 >= 0.35
        assert _fleet_counter("hedges") == hedges0
    finally:
        router.stop()
        s_slow.stop()
        s_fast.stop()


# -- autoscaler policy (fast: fake fleet) ------------------------------------

class _FakeRouter:
    def __init__(self, sup):
        self._sup = sup
        self.outstanding = 0
        self.drained, self.admitted, self.forgotten = [], [], []
        self._draining: dict = {}

    def status(self):
        return {"draining": sorted(self._draining)}

    def drain(self, key, timeout=None):
        self.drained.append(key)

    def admit(self, key):
        self.admitted.append(key)

    def forget(self, key):
        self.forgotten.append(key)


class _FakeSup:
    def __init__(self, n):
        self.idxs = list(range(n))
        self.queue_depth = 0.0
        self.added, self.removed = 0, []

    def _list(self):
        return list(self.idxs)

    def status(self):
        return {i: {"state": "up"} for i in self.idxs}

    def federated(self):
        return {"summed": {
            "counters": {},
            "gauges": {"serving/queue_depth": self.queue_depth},
            "histograms": {}}}

    def add_replica(self, timeout_s=None):
        idx = max(self.idxs, default=-1) + 1
        self.idxs.append(idx)
        self.added += 1
        return idx

    def remove_replica(self, idx, timeout=15.0):
        self.idxs.remove(idx)
        self.removed.append(idx)
        return idx


def _fake_autoscaler(n=2, **kw):
    sup = _FakeSup(n)
    router = _FakeRouter(sup)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("queue_high", 4.0)
    kw.setdefault("queue_low", 0.5)
    kw.setdefault("up_ticks", 2)
    kw.setdefault("down_ticks", 3)
    kw.setdefault("cooldown_s", 5.0)
    auto = serving.Autoscaler(sup, router, **kw)
    return auto, sup, router


def test_autoscaler_scales_up_with_hysteresis_and_cooldown():
    auto, sup, router = _fake_autoscaler(n=2)
    sup.queue_depth = 20.0           # 10 per replica > queue_high
    assert auto._tick(now=0.0) is None            # streak 1: no action
    assert sup.added == 0
    rec = auto._tick(now=1.0)                     # streak 2: scale up
    assert rec["action"] == "up" and sup.added == 1
    assert auto.target == 3
    # still overloaded, but the cooldown window holds the fleet steady
    auto._tick(now=1.5)
    rec = auto._tick(now=2.0)
    assert rec is not None and rec["action"] == "denied_up"
    # cooldown over, but the fleet is at max_replicas: bounded
    auto._tick(now=10.0)
    rec = auto._tick(now=11.0)
    assert rec["action"] == "denied_up" and "max_replicas" in rec["reason"]
    assert auto.target == 3 and sup.added == 1
    decisions = auto.decisions()
    assert [d["action"] for d in decisions].count("up") == 1


def test_autoscaler_scale_down_drains_newest_replica_zero_drop():
    auto, sup, router = _fake_autoscaler(n=3, cooldown_s=0.5)
    sup.queue_depth = 0.0            # idle fleet
    assert auto._tick(now=100.0) is None
    assert auto._tick(now=101.0) is None
    rec = auto._tick(now=102.0)      # down_ticks=3 reached
    assert rec["action"] == "down"
    # the zero-drop order: drain at the router FIRST, then remove, then
    # forget the router-side state
    assert router.drained == [2] and sup.removed == [2]
    assert router.admitted == [2] and router.forgotten == [2]
    assert auto.target == 2
    # bounded below: shrink to min_replicas and no further
    for t in (110.0, 111.0, 112.0):
        auto._tick(now=t)
    assert auto.target == 1 and sup.removed == [2, 1]
    for t in (120.0, 121.0, 122.0, 123.0):
        rec = auto._tick(now=t) or rec
    assert auto.target == 1
    assert any(d["action"] == "denied_down" for d in auto.decisions())


def test_autoscaler_mixed_signals_reset_streaks_and_statusz_surface():
    auto, sup, router = _fake_autoscaler(n=2)
    sup.queue_depth = 20.0
    auto._tick(now=0.0)
    sup.queue_depth = 2.0            # back inside the hysteresis band
    assert auto._tick(now=1.0) is None
    sup.queue_depth = 20.0
    assert auto._tick(now=2.0) is None   # streak restarted at 1
    st = auto.status()
    assert st["target"] == 2 and st["up_streak"] == 1
    # the real Router surfaces the autoscaler in status() (-> /statusz)
    s1, _ = _server()
    real = serving.Router([s1.url])
    with pytest.raises(MXNetError):
        serving.Autoscaler(_FakeSup(1), real)    # router/sup mismatch
    assert real.status()["autoscaler"] is None
    s1.stop()

class _FleetModel:
    """Numpy-only model served by spawned workers (picklable by module
    reference; no XLA compile so workers start fast)."""

    def __init__(self):
        self.w = 2.0

    def __call__(self, x):
        return (onp.asarray(x) * self.w,)

    def apply_weights(self, payload):
        self.w = float(payload["w"])


def _fleet_factory():
    return _FleetModel()


def _spec(**kw):
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_delay_ms", 0.5)
    kw.setdefault("heartbeat_s", 0.2)
    return serving.ReplicaSpec(_fleet_factory, **kw)


def _storm(router, n, x, deadline_ms=None, timeout=60):
    futs = [router.submit(x, deadline_ms=deadline_ms) for _ in range(n)]
    return [f.result(timeout=timeout) for f in futs]


@pytest.mark.slow
def test_fleet_crash_mid_storm_restarts_and_loses_nothing():
    # replica 0 hard-crashes (os._exit 41) at its 5th dispatched batch;
    # every accepted idempotent request must still resolve, and the
    # supervisor must bring the replica back
    spec = _spec(per_replica_env={
        0: {"MXNET_FAULT_PLAN": "serving.replica@5:crash"}})
    restarts0 = _fleet_counter("restarts")
    with serving.ReplicaSupervisor(spec, n_replicas=2, hang_grace_s=5.0,
                                   backoff_s=0.1) as sup:
        with serving.Router(sup, request_timeout_s=10.0) as router:
            x = onp.ones(3, dtype="float32")
            outs = _storm(router, 40, x)
            for o in outs:
                onp.testing.assert_allclose(o, x * 2.0)
            # the respawn happens after classified backoff — wait for
            # the fleet to heal before asserting on it
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    not all(v["state"] == "up" for v in
                            sup.status().values()):
                time.sleep(0.2)
            st = sup.status()
            assert all(v["state"] == "up" for v in st.values())
            assert st[0]["restarts"] >= 1
            # the restarted replica serves again
            onp.testing.assert_allclose(router.predict(x, timeout=30),
                                        x * 2.0)
    assert _fleet_counter("restarts") >= restarts0 + 1


@pytest.mark.slow
def test_fleet_hung_replica_detected_killed_and_restarted():
    # replica 0 wedges for 60 s inside an engine dispatch; the router
    # orphan-retries its in-flight requests on replica 1 and the
    # supervisor's progress watchdog kills + restarts the hung worker
    spec = _spec(per_replica_env={
        0: {"MXNET_FAULT_PLAN": "serving.replica@4:hang(60)"}})
    hangs0 = _fleet_counter("hangs")
    with serving.ReplicaSupervisor(spec, n_replicas=2, hang_grace_s=1.5,
                                   backoff_s=0.1) as sup:
        with serving.Router(sup, request_timeout_s=2.0) as router:
            x = onp.ones(3, dtype="float32")
            outs = _storm(router, 30, x, timeout=90)
            for o in outs:
                onp.testing.assert_allclose(o, x * 2.0)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    _fleet_counter("hangs") < hangs0 + 1:
                time.sleep(0.2)
            assert _fleet_counter("hangs") >= hangs0 + 1
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    not all(v["state"] == "up" for v in
                            sup.status().values()):
                time.sleep(0.2)
            assert all(v["state"] == "up" for v in sup.status().values())


@pytest.mark.slow
def test_rolling_weight_swap_zero_drop_under_load():
    spec = _spec()
    with serving.ReplicaSupervisor(spec, n_replicas=2,
                                   backoff_s=0.1) as sup:
        with serving.Router(sup) as router:
            x = onp.ones(3, dtype="float32")
            onp.testing.assert_allclose(router.predict(x, timeout=60),
                                        x * 2.0)
            stop_flag = threading.Event()
            errors, served = [], [0]

            def load():
                while not stop_flag.is_set():
                    try:
                        router.predict(x, timeout=60)
                        served[0] += 1
                    except Exception as e:      # noqa: BLE001
                        errors.append(e)
                        return

            threads = [threading.Thread(target=load) for _ in range(4)]
            for t in threads:
                t.start()
            report = router.rolling_swap({"w": 5.0})
            stop_flag.set()
            for t in threads:
                t.join(30)
            # ZERO dropped requests across the full-fleet rollout
            assert not errors, errors[:1]
            assert served[0] > 0
            assert len(report) == 2
            # every replica serves the new weights
            for _ in range(8):
                onp.testing.assert_allclose(router.predict(x, timeout=60),
                                            x * 5.0)


class _SlowFleetModel:
    """Worker model slow enough to build real queue depth (picklable by
    module reference)."""

    def __init__(self):
        self.w = 2.0

    def __call__(self, x):
        time.sleep(0.05)
        return (onp.asarray(x) * self.w,)

    def apply_weights(self, payload):
        self.w = float(payload["w"])


def _slow_fleet_factory():
    return _SlowFleetModel()


@pytest.mark.slow
def test_autoscaler_grows_and_shrinks_real_fleet_zero_drop():
    # load storm -> federated queue depth per replica breaches
    # queue_high -> scale up; load stops -> scale down to min, draining
    # zero-drop.  The full control loop over real worker processes.
    spec = serving.ReplicaSpec(_slow_fleet_factory, batch_buckets=(1, 2),
                               max_batch_size=2, max_delay_ms=0.5,
                               max_queue=256, heartbeat_s=0.2)
    ups0 = _fleet_counter("scale_ups")
    downs0 = _fleet_counter("scale_downs")
    with serving.ReplicaSupervisor(spec, n_replicas=1, backoff_s=0.1,
                                   federate_s=0.2) as sup:
        with serving.Router(sup, request_timeout_s=30.0,
                            dispatch_threads=16) as router:
            auto = serving.Autoscaler(
                sup, router, min_replicas=1, max_replicas=2,
                interval_s=0.25, cooldown_s=1.0, queue_high=1.5,
                queue_low=0.2, up_ticks=2, down_ticks=4,
                drain_timeout_s=30.0).start()
            stop_flag = threading.Event()
            errors = []
            x = onp.ones(3, dtype="float32")

            def load():
                while not stop_flag.is_set():
                    try:
                        router.predict(x, timeout=60)
                    except Exception as e:      # noqa: BLE001
                        errors.append(e)
                        return

            threads = [threading.Thread(target=load) for _ in range(8)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and \
                    sum(1 for v in sup.status().values()
                        if v["state"] == "up") < 2:
                time.sleep(0.2)
            grown = {i: v["state"] for i, v in sup.status().items()}
            stop_flag.set()
            for t in threads:
                t.join(60)
            assert not errors, errors[:1]
            assert sum(1 for s in grown.values() if s == "up") == 2, grown
            # idle now: the policy loop shrinks back to min through the
            # zero-drop drain path
            deadline = time.monotonic() + 60
            # the replica leaves status() the moment the scale-down
            # unlists it, but target updates only after the worker is
            # fully joined — wait for BOTH
            while time.monotonic() < deadline and \
                    (len(sup.status()) > 1 or auto.target > 1):
                time.sleep(0.2)
            assert len(sup.status()) == 1
            assert auto.target == 1
            actions = [d["action"] for d in auto.decisions()]
            assert "up" in actions and "down" in actions
            # the survivor still serves
            onp.testing.assert_allclose(router.predict(x, timeout=60),
                                        x * 2.0)
            auto.stop()
    assert _fleet_counter("scale_ups") >= ups0 + 1
    assert _fleet_counter("scale_downs") >= downs0 + 1


@pytest.mark.slow
def test_rolling_swap_racing_scale_down_drops_nothing_and_converges():
    # both paths drain replicas; prove the interaction: a rolling swap
    # underway while the autoscaler removes a replica loses no request
    # and the fleet converges to the target size with the new weights
    spec = _spec()
    with serving.ReplicaSupervisor(spec, n_replicas=3,
                                   backoff_s=0.1) as sup:
        with serving.Router(sup) as router:
            auto = serving.Autoscaler(sup, router, min_replicas=2,
                                      max_replicas=3, queue_high=1e9,
                                      queue_low=1e-9, down_ticks=1,
                                      cooldown_s=0.0, interval_s=999.0)
            x = onp.ones(3, dtype="float32")
            onp.testing.assert_allclose(router.predict(x, timeout=60),
                                        x * 2.0)
            stop_flag = threading.Event()
            errors, served = [], [0]

            def load():
                while not stop_flag.is_set():
                    try:
                        router.predict(x, timeout=60)
                        served[0] += 1
                    except Exception as e:      # noqa: BLE001
                        errors.append(e)
                        return

            threads = [threading.Thread(target=load) for _ in range(4)]
            for t in threads:
                t.start()
            swap_report = [None]
            swap_exc = []

            def swap():
                try:
                    swap_report[0] = router.rolling_swap({"w": 5.0})
                except Exception as e:          # noqa: BLE001
                    swap_exc.append(e)

            swapper = threading.Thread(target=swap)
            swapper.start()
            # the race: a scale-down fires while the rollout is draining
            time.sleep(0.05)
            rec = auto._tick()
            assert rec is not None and rec["action"] == "down", rec
            swapper.join(120)
            stop_flag.set()
            for t in threads:
                t.join(60)
            assert not swap_exc, swap_exc[:1]
            # ZERO dropped requests across the racing drains
            assert not errors, errors[:1]
            assert served[0] > 0
            # converged: exactly 2 replicas, all up, autoscaler target 2
            st = sup.status()
            assert len(st) == 2 and \
                all(v["state"] == "up" for v in st.values()), st
            assert auto.target == 2
            # the rollout visited every replica that stayed; the one the
            # autoscaler removed mid-rollout is reported skipped or was
            # swapped before removal — either way the SURVIVORS serve
            # the new weights
            assert swap_report[0] is not None
            assert len(swap_report[0]) >= 2
            for _ in range(8):
                onp.testing.assert_allclose(router.predict(x, timeout=60),
                                            x * 5.0)


@pytest.mark.slow
def test_permanent_init_failure_is_not_restarted():
    spec = serving.ReplicaSpec(_broken_factory, heartbeat_s=0.2)
    sup = serving.ReplicaSupervisor(spec, n_replicas=1, backoff_s=0.1,
                                    start_timeout_s=60.0)
    with pytest.raises(MXNetError, match="permanently"):
        sup.start()
    sup.stop()


def _broken_factory():
    raise ValueError("deterministically broken model factory")
