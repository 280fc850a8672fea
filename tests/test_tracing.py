"""Request-scoped distributed tracing + fleet metric federation
(docs/OBSERVABILITY.md): trace-id stability with attempt increments
across transparent retry and orphan re-route, the sampled-out
no-op-constant contract, the JSONL spool + cross-process ``--fleet``
merge (real worker processes marked ``slow``), the crash-report
``in_flight_trace_ids`` field, and strict-JSON/Prometheus validity of
the federated exposition."""
import importlib.util
import json
import os
import re
import socket
import struct
import threading
import time
import urllib.request

import numpy as onp
import pytest

from mxnet_tpu import faults, serving, telemetry

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+( [0-9.e+-]+)?$")


def _load_trace_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    return tr


@pytest.fixture
def traced(monkeypatch, tmp_path):
    """Tracing on at rate 1.0 with a fresh spool dir; restored after."""
    spool = str(tmp_path / "spool")
    monkeypatch.setenv("MXNET_TRACE_SPOOL_DIR", spool)
    telemetry.set_trace_sample(1.0)
    yield spool
    telemetry.flush_trace_spool()
    telemetry.set_trace_sample(None)


def _server(model=None, buckets=(1, 2, 4), max_queue=64):
    if model is None:
        def model(x):
            return (onp.asarray(x) * 2.0,)
    engine = serving.InferenceEngine(model, batch_buckets=buckets)
    batcher = serving.DynamicBatcher(engine, max_batch_size=buckets[-1],
                                     max_delay_ms=0.5, max_queue=max_queue)
    return serving.ModelServer(batcher, port=0).start()


class _ResetStub:
    """Accepts a connection then RSTs it mid-request — a replica dying
    after the request was sent (the orphan-re-route trigger)."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.url = f"http://127.0.0.1:{self.sock.getsockname()[1]}"
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            try:
                conn.recv(65536)
                conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                struct.pack("ii", 1, 0))
            finally:
                conn.close()

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


# -- the no-op-constant contract --------------------------------------------

def test_sampling_off_and_sampled_out_are_the_shared_noop_constant():
    telemetry.set_trace_sample(0.0)
    try:
        assert telemetry.new_trace() is telemetry.NULL_TRACE
        # a head-sample miss pays the same constant as sampling-off
        telemetry.set_trace_sample(1e-12)
        for _ in range(64):
            assert telemetry.new_trace() is telemetry.NULL_TRACE
        nt = telemetry.NULL_TRACE
        assert not nt
        assert nt.wire() is None
        assert nt.span("x") is nt.span("y")         # shared constant
        nt.add_span("x", 0, 1)
        nt.mark("shed")
        nt.accept_span("x", 0)
        assert nt.spans() == [] and nt.marks == ()
        assert telemetry.maybe_spool(nt, 1e9, role="client") == ()
        # a head-sample hit is a real, spool-guaranteed trace
        telemetry.set_trace_sample(1.0)
        t = telemetry.new_trace()
        assert t and t.sampled and len(t.trace_id) == 16
    finally:
        telemetry.set_trace_sample(None)


def test_continue_trace_requires_local_tracing_and_valid_wire():
    telemetry.set_trace_sample(0.0)
    try:
        assert telemetry.continue_trace(
            {"id": "ab", "attempt": 1}) is telemetry.NULL_TRACE
        telemetry.set_trace_sample(1.0)
        assert telemetry.continue_trace(None) is telemetry.NULL_TRACE
        assert telemetry.continue_trace("junk") is telemetry.NULL_TRACE
        t = telemetry.continue_trace(
            {"id": "abcd", "attempt": 2, "sampled": False,
             "sent_us": telemetry._wall_us() - 500})
        assert t.trace_id == "abcd" and t.attempt == 2 and not t.sampled
        t.accept_span("router_accept", telemetry._wall_us())
        assert t.spans()[0]["phase"] == "router_accept"
        # sampled=False + no always-keep mark: not spooled
        assert telemetry.maybe_spool(t, 0.0, role="router") == ()
        t.mark("retried")
        assert "retried" in telemetry.maybe_spool(t, 0.0, role="router")
    finally:
        telemetry.set_trace_sample(None)


# -- id stability across retry / re-route -----------------------------------

def test_trace_id_stable_attempts_increment_across_transparent_retry(
        traced):
    srv = _server()
    x = onp.ones(4, dtype="float32")
    router = serving.Router([srv.url])
    with serving.RouterServer(router, port=0) as rs:
        cli = serving.ServingClient(rs.url)
        with faults.inject("router.dispatch@1:transient"):
            out, report = cli.predict_traced(x, deadline_ms=30000)
    onp.testing.assert_allclose(out, x * 2.0)
    assert "retried" in report["keep"]
    dispatches = [s for s in report["spans"]
                  if s["phase"] == "router_dispatch"]
    assert {s["attempt"] for s in dispatches} == {0, 1}
    assert [s for s in report["spans"] if s["phase"] == "router_retry"]
    # ONE id end to end: the replica's spans rode back under it too
    assert any(s["phase"] == "execute" for s in report["spans"])
    srv.stop()


def test_trace_id_stable_across_orphan_reroute(traced):
    stub = _ResetStub()
    srv = _server()
    x = onp.ones(4, dtype="float32")
    with serving.Router([stub.url, srv.url], cooldown_s=0.0) as router:
        fut = router.submit(x)                      # router mints
        onp.testing.assert_allclose(fut.result(timeout=30), x * 2.0)
    stub.close()
    srv.stop()
    telemetry.flush_trace_spool()
    tr = _load_trace_report()
    spool = os.environ["MXNET_TRACE_SPOOL_DIR"]
    merged = tr.merge_fleet(tr.load_spool_dir(spool))
    assert len(merged) == 1
    t = merged[0]
    assert "rerouted" in t["keep"]
    dispatches = [s for s in t["spans"] if s["phase"] == "router_dispatch"]
    assert {s["attempt"] for s in dispatches} == {0, 1}
    outcomes = {(s["args"] or {}).get("outcome") for s in dispatches}
    assert outcomes == {"orphan", "ok"}


def test_serving_error_messages_carry_trace_id(traced):
    srv = _server()
    x = onp.ones(4, dtype="float32")
    with serving.Router([srv.url]) as router:
        with faults.inject("router.dispatch@1:permanent"):
            with pytest.raises(faults.PermanentFault):
                router.predict(x, timeout=30)
        router.drain(0)
        fut = router.submit(x, deadline_ms=60)
        with pytest.raises(serving.DeadlineExceededError,
                           match=r"\[trace [0-9a-f]{16} attempt \d+\]"):
            fut.result(timeout=10)
    srv.stop()


# -- in-flight registry / crash reports -------------------------------------

def test_crash_report_names_in_flight_trace_ids(traced):
    release = threading.Event()

    def slow_model(x):
        release.wait(20)
        return (onp.asarray(x) * 2.0,)

    srv = _server(model=slow_model, buckets=(1,))
    cli = serving.ServingClient(srv.url)
    telemetry.set_trace_sample(1.0)
    err = []

    def call():
        try:
            cli.predict_once(onp.ones(4, dtype="float32"))
        except Exception as e:          # noqa: BLE001
            err.append(e)

    th = threading.Thread(target=call, daemon=True)
    th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not telemetry.inflight_trace_ids():
        time.sleep(0.02)
    held = telemetry.inflight_trace_ids()
    assert len(held) == 1
    payload = faults.crash_report_payload()
    assert payload["schema"] == 7
    assert payload["in_flight_trace_ids"] == held
    release.set()
    th.join(30)
    assert not err
    assert telemetry.inflight_trace_ids() == []
    srv.stop()


def test_rejected_request_leaves_inflight_registry(traced):
    # regression: a queue-full/stopped rejection used to leave the trace
    # id in the in-flight registry forever (future never settled)
    engine = serving.InferenceEngine(lambda x: (onp.asarray(x),),
                                     batch_buckets=(1,))
    batcher = serving.DynamicBatcher(engine, max_batch_size=1)
    with pytest.raises(serving.EngineClosedError):    # never started
        batcher.submit(onp.ones(2, dtype="float32"),
                       trace=telemetry.new_trace())
    assert telemetry.inflight_trace_ids() == []
    srv = _server(model=lambda x: (time.sleep(0.5), onp.asarray(x))[1:],
                  buckets=(1,))
    with serving.Router([srv.url], max_outstanding=1) as router:
        f1 = router.submit(onp.ones(2, dtype="float32"))
        with pytest.raises(serving.QueueFullError,
                           match=r"\[trace [0-9a-f]{16}"):
            router.submit(onp.ones(2, dtype="float32"))
        # only the accepted request may remain registered
        assert len(telemetry.inflight_trace_ids()) <= 1
        f1.result(timeout=30)
    assert telemetry.inflight_trace_ids() == []
    srv.stop()


# -- spool mechanics ---------------------------------------------------------

def test_spool_jsonl_append_and_torn_tail_line_skipped(traced, monkeypatch):
    def dropped():
        return telemetry.snapshot()["counters"]["trace/spool_dropped"]

    d0 = dropped()
    t = telemetry.new_trace()
    t.add_span("client_request", telemetry._wall_us(), 1000.0)
    assert "sampled" in telemetry.maybe_spool(t, 1.0, role="client")
    assert dropped() == d0          # under the cap nothing is dropped
    # past the cap a record is dropped and counted, never rotated in
    monkeypatch.setattr(telemetry, "_SPOOL_CAP",
                        telemetry._spool_accepted[0])
    late = telemetry.new_trace()
    late.add_span("client_request", telemetry._wall_us(), 1000.0)
    telemetry.maybe_spool(late, 1.0, role="client")
    assert dropped() == d0 + 1
    path = telemetry.flush_trace_spool()
    assert path and path.endswith(".jsonl")
    with open(path, "a") as f:
        f.write('{"trace_id": "torn-rec')        # writer killed mid-line
    tr = _load_trace_report()
    recs = tr.load_spool_dir(os.path.dirname(path))
    assert [r["trace_id"] for r in recs] == [t.trace_id]


def test_shed_request_always_keeps(traced):
    srv = _server(model=lambda x: (time.sleep(0.3), onp.asarray(x))[1:],
                  buckets=(1,))
    cli = serving.ServingClient(srv.url)
    x = onp.ones(2, dtype="float32")
    slow = threading.Thread(
        target=lambda: cli.predict_once(x), daemon=True)
    slow.start()
    time.sleep(0.05)
    with pytest.raises(serving.DeadlineExceededError):
        cli.predict_once(x, deadline_ms=30)
    slow.join(30)
    srv.stop()
    telemetry.flush_trace_spool()
    tr = _load_trace_report()
    merged = tr.merge_fleet(
        tr.load_spool_dir(os.environ["MXNET_TRACE_SPOOL_DIR"]))
    assert any("shed" in t["keep"] for t in merged)


# -- federation unit tests ---------------------------------------------------

def test_replica_federation_freeze_never_decreases():
    from mxnet_tpu.serving.fleet import _ReplicaFederation
    fed = _ReplicaFederation()
    h1 = {"count": 2, "sum": 3.0, "buckets": [[1.0, 1], ["+Inf", 2]]}
    fed.absorb({"counters": {"serving/completed": 5},
                "gauges": {"serving/queue_depth": 3},
                "histograms": {"serving/latency_ms": h1}},
               now=1.0, incarnation=1)
    c, g, h = fed.effective()
    assert c["serving/completed"] == 5 and g["serving/queue_depth"] == 3
    # the replica dies and restarts: the new incarnation reports ZEROS —
    # the federated counter must freeze at 5, then resume summing
    fed.fold()
    fed.absorb({"counters": {"serving/completed": 0},
                "gauges": {"serving/queue_depth": 0},
                "histograms": {}}, now=2.0, incarnation=2)
    c, g, h = fed.effective()
    assert c["serving/completed"] == 5
    assert h["serving/latency_ms"]["count"] == 2
    fed.absorb({"counters": {"serving/completed": 4},
                "gauges": {}, "histograms": {
                    "serving/latency_ms": h1}}, now=3.0, incarnation=2)
    c, _g, h = fed.effective()
    assert c["serving/completed"] == 9
    assert h["serving/latency_ms"]["count"] == 4
    # an unseen in-place reset (counter went backwards, same incarnation
    # handle) also folds instead of decreasing
    fed.absorb({"counters": {"serving/completed": 1},
                "gauges": {}, "histograms": {}}, now=4.0, incarnation=2)
    c, _g, _h = fed.effective()
    assert c["serving/completed"] == 10


def test_federation_prometheus_text_valid():
    class _Sup:
        def federated(self):
            return {"replicas": {
                0: {"counters": {"serving/completed": 7},
                    "gauges": {"serving/queue_depth": 1.5},
                    "histograms": {}, "age_s": 0.2, "stale": False,
                    "incarnation": 1},
                1: {"counters": {"serving/completed": 3},
                    "gauges": {}, "histograms": {}, "age_s": None,
                    "stale": True, "incarnation": 2},
            }, "summed": {
                "counters": {"serving/completed": 10},
                "gauges": {"serving/queue_depth": 1.5},
                "histograms": {"serving/latency_ms": {
                    "count": 2, "sum": 3.5,
                    "buckets": [[1.0, 1], ["+Inf", 2]]}},
            }}

    text = serving.federation_prometheus_text(_Sup())
    for line in text.splitlines():
        if line.startswith("#"):
            assert line.startswith("# TYPE "), line
            continue
        assert _SAMPLE_RE.match(line), line
    assert 'mxnet_worker_serving_completed{replica="0"} 7' in text
    assert 'mxnet_worker_stale{replica="1"} 1' in text
    assert "mxnet_workers_serving_completed 10" in text
    assert 'mxnet_workers_serving_latency_ms_bucket{le="+Inf"} 2' in text
    assert "mxnet_workers_serving_latency_ms_count 2" in text
    # a dead replica has no snapshot age sample, not a bogus one
    assert 'mxnet_worker_snapshot_age_seconds{replica="1"}' not in text


# -- multi-process: spool merge + federated exposition (slow) ---------------

class _FleetModel:
    def __call__(self, x):
        return (onp.asarray(x) * 2.0,)


def _fleet_factory():
    return _FleetModel()


@pytest.mark.slow
def test_spool_merge_and_federation_across_real_workers(
        traced, monkeypatch):
    spool = traced
    spec = serving.ReplicaSpec(
        _fleet_factory, batch_buckets=(1, 2), max_batch_size=2,
        max_delay_ms=0.5, heartbeat_s=0.2,
        env={"MXNET_TRACE_SAMPLE": "1.0",
             "MXNET_TRACE_SPOOL_DIR": spool})
    x = onp.ones(3, dtype="float32")
    with serving.ReplicaSupervisor(spec, n_replicas=2, backoff_s=0.1,
                                   federate_s=0.25) as sup:
        with serving.Router(sup) as router:
            rs = serving.RouterServer(router, port=0)
            # start() on the already-started router is idempotent here
            rs.start()
            cli = serving.ServingClient(rs.url)
            reports = []
            rep_lock = threading.Lock()
            errors = []

            def call():
                # concurrent clients so least-loaded dispatch actually
                # spreads the traces across BOTH worker processes
                try:
                    for _ in range(4):
                        out, rep = cli.predict_traced(x, deadline_ms=30000)
                        onp.testing.assert_allclose(out, x * 2.0)
                        with rep_lock:
                            reports.append(rep)
                except Exception as e:      # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=call, daemon=True)
                       for _ in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(60)
            assert not errors, errors[:1]
            assert len(reports) == 16
            # federation: wait until the supervisor's pulls have caught
            # up with the storm (snapshots ride the federate_s cadence)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and \
                    sup.federated()["summed"]["counters"].get(
                        "serving/completed", 0) < len(reports):
                time.sleep(0.1)
            with urllib.request.urlopen(rs.url + "/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            assert re.search(
                r'mxnet_worker_serving_completed\{replica="\d"\} \d+',
                text)
            assert "mxnet_workers_serving_completed" in text
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    assert _SAMPLE_RE.match(line), line
            with urllib.request.urlopen(rs.url + "/statusz",
                                        timeout=10) as r:
                body = r.read().decode()
            payload = json.loads(body)          # strict RFC 8259
            assert "Infinity" not in body
            fed = payload["fleet"]["federation"]
            assert set(fed["replicas"]) == {"0", "1"}
            summed = fed["summed"]["counters"]
            per = sum(v["counters"].get("serving/completed", 0)
                      for v in fed["replicas"].values())
            assert summed.get("serving/completed", 0) == per > 0
            rs.stop()
    telemetry.flush_trace_spool()
    tr = _load_trace_report()
    merged = {t["trace_id"]: t
              for t in tr.merge_fleet(tr.load_spool_dir(spool))}
    # every request merged across >= 2 real processes, all three roles
    assert len(merged) >= 16
    worker_pids = set()
    for rep in reports:
        t = merged[rep["trace_id"]]
        assert {"client", "router", "replica"} <= set(t["roles"])
        assert len(t["processes"]) >= 2
        assert t["span_union_ms"] <= t["wall_ms"] * 1.05
        for proc in t["processes"]:
            role, pid = proc.rsplit(":", 1)
            if role == "replica":
                worker_pids.add(pid)
                assert int(pid) != os.getpid()
        # wall-clock alignment: spans sorted by start time
        ts = [s["ts_us"] for s in t["spans"]]
        assert ts == sorted(ts)
    # the storm actually crossed multiple worker processes
    assert len(worker_pids) == 2
