"""mxnet_tpu.serving — engine bucketing, dynamic batching, admission
control, metrics, and the loopback HTTP front-end (all CPU, tier-1)."""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.gluon import nn


def _mlp(in_units=8, out_units=3):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=in_units, activation="relu"))
    net.add(nn.Dense(out_units, in_units=16))
    net.initialize()
    return net


def _slow_model(delay_s):
    """Callable model with a controllable per-batch latency — lets the
    admission-control tests force queue buildup deterministically."""
    def fn(x):
        time.sleep(delay_s)
        return (onp.asarray(x) * 2.0,)
    return fn


# -- engine: buckets, padding, chunking ------------------------------------

def test_bucket_padding_matches_unbatched_forward():
    net = _mlp()
    engine = serving.InferenceEngine(net, batch_buckets=(2, 4, 8))
    xs = onp.random.RandomState(0).randn(5, 8).astype("float32")
    # 5 rows pad to bucket 8; rows must equal the eager batched forward
    (out,) = engine.run_batch([xs])
    ref = net(mx.nd.array(xs)).asnumpy()
    assert out.shape == ref.shape
    onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # single-example path too (pads 1 -> bucket 2)
    one = engine.predict(xs[0])
    onp.testing.assert_allclose(one, ref[0], rtol=1e-5, atol=1e-5)


def test_bucket_selection_and_chunking():
    engine = serving.InferenceEngine(_slow_model(0.0), batch_buckets=(1, 2, 4))
    assert engine.bucket_for(1) == 1
    assert engine.bucket_for(3) == 4
    assert engine.bucket_for(4) == 4
    # above the top bucket: chunked into top-bucket pieces, then re-joined
    xs = onp.arange(11, dtype="float32").reshape(11, 1)
    (out,) = engine.run_batch([xs])
    onp.testing.assert_allclose(out, xs * 2.0)
    stats = engine.metrics.stats()
    assert stats["counters"]["batches"] == 3          # 4 + 4 + 3
    assert stats["counters"]["padded_examples"] == 1  # last chunk pads 3->4


def test_warmup_precompiles_buckets():
    engine = serving.InferenceEngine(_mlp(), batch_buckets=(1, 2, 4))
    warmed = engine.warmup(onp.zeros(8, dtype="float32"))
    assert warmed == [1, 2, 4]
    assert engine.metrics.stats()["counters"]["compiles"] == 3
    with pytest.raises(mx.base.MXNetError):
        engine.warmup(onp.zeros(8, dtype="float32"), buckets=(16,))


def test_engine_serves_hot_swapped_weights():
    # params are re-read per dispatch, so a load_parameters()/set_data
    # weight swap serves immediately (same avals => no recompile)
    net = _mlp()
    engine = serving.InferenceEngine(net, batch_buckets=(1, 2))
    x = onp.random.RandomState(0).randn(8).astype("float32")
    before = engine.predict(x)
    for p in net.collect_params().values():
        p.set_data(p.data() * 0.5)
    after = engine.predict(x)
    assert not onp.allclose(after, before)
    onp.testing.assert_allclose(after, net(mx.nd.array(x[None])).asnumpy()[0],
                                rtol=1e-5, atol=1e-5)
    assert engine.metrics.stats()["counters"]["compiles"] == 1


def test_engine_program_cache_lru_bound():
    engine = serving.InferenceEngine(_mlp(), batch_buckets=(1, 2, 4),
                                     max_programs=2)
    engine.warmup(onp.zeros(8, dtype="float32"))
    assert engine.metrics.stats()["counters"]["cache_evictions"] == 1


# -- dynamic batching -------------------------------------------------------

def test_batch_coalescing_under_concurrent_clients():
    engine = serving.InferenceEngine(_mlp(), batch_buckets=(1, 2, 4, 8))
    engine.warmup(onp.zeros(8, dtype="float32"))
    batcher = serving.DynamicBatcher(engine, max_batch_size=8,
                                     max_delay_ms=20.0, max_queue=64)
    n = 16
    xs = onp.random.RandomState(1).randn(n, 8).astype("float32")
    ref = engine.run_batch([xs])[0]
    outs = [None] * n
    barrier = threading.Barrier(n)

    def client(i):
        barrier.wait()
        outs[i] = batcher.submit(xs[i]).result(timeout=30)

    with batcher:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        stats = batcher.stats()
    # every client got ITS row back, not a neighbor's
    for i in range(n):
        onp.testing.assert_allclose(outs[i], ref[i], rtol=1e-5, atol=1e-5)
    c = stats["counters"]
    assert c["completed"] == n
    # coalescing actually happened: far fewer dispatches than requests
    assert c["batches"] < n
    assert stats["batch_occupancy_mean"] > 1.0


def test_deadline_shedding_before_dispatch():
    # one slow batch in flight forces the rest to queue past the deadline
    engine = serving.InferenceEngine(_slow_model(0.15), batch_buckets=(1,))
    batcher = serving.DynamicBatcher(engine, max_batch_size=1,
                                     max_delay_ms=0.0, max_queue=64)
    x = onp.zeros(4, dtype="float32")
    with batcher:
        first = batcher.submit(x)                      # occupies the engine
        doomed = [batcher.submit(x, deadline_ms=10) for _ in range(4)]
        assert first.result(timeout=10).shape == (4,)
        for f in doomed:
            with pytest.raises(serving.DeadlineExceededError):
                f.result(timeout=10)
        stats = batcher.stats()
        # the storm over, every request of a clean wave is served
        wave = [batcher.submit(x) for _ in range(3)]
        assert all(f.result(timeout=10).shape == (4,) for f in wave)
    assert stats["counters"]["shed_deadline"] == 4
    # shed requests never reached the engine: only the live one dispatched
    assert stats["counters"]["batched_requests"] == 1
    assert stats["shed_rate"] > 0


def test_queue_full_fast_reject():
    engine = serving.InferenceEngine(_slow_model(0.2), batch_buckets=(1,))
    batcher = serving.DynamicBatcher(engine, max_batch_size=1,
                                     max_delay_ms=0.0, max_queue=2)
    x = onp.zeros(2, dtype="float32")
    with batcher:
        batcher.submit(x)            # in flight
        time.sleep(0.05)             # let the dispatcher pick it up
        batcher.submit(x)            # queued 1
        batcher.submit(x)            # queued 2 = capacity
        t0 = time.perf_counter()
        with pytest.raises(serving.QueueFullError):
            batcher.submit(x)
        # fast-reject: no waiting in line
        assert time.perf_counter() - t0 < 0.05
        stats = batcher.stats()
    assert stats["counters"]["rejected_queue_full"] >= 1


def test_queue_bound_atomic_under_concurrent_submit():
    # the cap lives in the queue itself: a concurrent burst must never
    # overshoot max_queue (a qsize() pre-check would let it)
    engine = serving.InferenceEngine(_slow_model(0.5), batch_buckets=(1,))
    batcher = serving.DynamicBatcher(engine, max_batch_size=1,
                                     max_delay_ms=0.0, max_queue=4)
    x = onp.zeros(2, dtype="float32")
    with batcher:
        batcher.submit(x)              # dispatcher enters the 0.5s engine call
        time.sleep(0.1)
        n = 30
        accepted = [0] * n
        barrier = threading.Barrier(n)

        def burst(i):
            barrier.wait()
            try:
                batcher.submit(x)
                accepted[i] = 1
            except serving.QueueFullError:
                pass

        threads = [threading.Thread(target=burst, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        # dispatcher is stuck inside the engine, so nothing drained:
        # acceptances are exactly bounded by the queue capacity
        assert sum(accepted) <= 4
        stats = batcher.stats()
    assert stats["counters"]["rejected_queue_full"] >= n - 4


def test_engine_error_fails_batch_not_dispatcher():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("boom")
        return (onp.asarray(x) * 2.0,)

    batcher = serving.DynamicBatcher(
        serving.InferenceEngine(flaky, batch_buckets=(1,)),
        max_batch_size=1, max_delay_ms=0.0)
    x = onp.ones(2, dtype="float32")
    with batcher:
        with pytest.raises(ValueError):
            batcher.predict(x, timeout=10)
        # the dispatcher survived the bad batch and keeps serving
        onp.testing.assert_allclose(batcher.predict(x, timeout=10), x * 2.0)
        assert batcher.stats()["counters"]["errors"] == 1


def test_mismatched_shape_fails_alone_not_coriders():
    # a malformed request coalesced with valid ones must fail ALONE —
    # the dispatcher groups by input signature before stacking
    engine = serving.InferenceEngine(_mlp(), batch_buckets=(1, 2, 4, 8))
    engine.warmup(onp.zeros(8, dtype="float32"))
    batcher = serving.DynamicBatcher(engine, max_batch_size=8,
                                     max_delay_ms=50.0)
    good_x = onp.random.RandomState(4).randn(8).astype("float32")
    ref = engine.predict(good_x)
    with batcher:
        good = [batcher.submit(good_x) for _ in range(3)]
        bad = batcher.submit(onp.zeros(5, dtype="float32"))  # wrong in_units
        for f in good:
            onp.testing.assert_allclose(f.result(timeout=30), ref,
                                        rtol=1e-5, atol=1e-5)
        with pytest.raises(Exception):
            bad.result(timeout=30)
        stats = batcher.stats()
    assert stats["counters"]["completed"] == 3
    assert stats["counters"]["errors"] == 1


def test_submit_after_stop_raises():
    batcher = serving.DynamicBatcher(
        serving.InferenceEngine(_slow_model(0.0), batch_buckets=(1,)))
    batcher.start()
    batcher.stop()
    with pytest.raises(serving.EngineClosedError):
        batcher.submit(onp.zeros(1, dtype="float32"))


# -- metrics ----------------------------------------------------------------

def test_metrics_snapshot_sanity():
    import json
    engine = serving.InferenceEngine(_mlp(), batch_buckets=(1, 2, 4))
    batcher = serving.DynamicBatcher(engine, max_batch_size=4,
                                     max_delay_ms=1.0)
    x = onp.zeros(8, dtype="float32")
    with batcher:
        for _ in range(10):
            batcher.predict(x, timeout=30)
        stats = batcher.stats()
    json.dumps(stats)                          # snapshot must serialize
    c = stats["counters"]
    assert c["requests"] == c["completed"] == 10
    assert c["batched_requests"] == 10
    lat = stats["latency"]
    assert lat["count"] == 10
    assert 0 < lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"] <= lat["max_ms"]
    assert stats["queue_time"]["count"] == 10
    assert stats["batch_exec"]["count"] == c["batches"]
    assert stats["shed_rate"] == 0.0
    assert stats["gauges"]["queue_depth"] == 0


def test_latency_histogram_percentiles():
    h = serving.LatencyHistogram()
    assert h.percentile(99) == 0.0
    for ms in range(1, 101):                   # 1..100 ms, one each
        h.observe(float(ms))
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["max_ms"] == 100.0
    # log-bucketed: percentiles land within one bucket factor (1.25x)
    assert 45 <= snap["p50_ms"] <= 63
    assert 90 <= snap["p95_ms"] <= 100
    assert snap["p95_ms"] <= snap["p99_ms"] <= 100.0


def test_metrics_profiler_counter_wiring():
    from mxnet_tpu import profiler
    profiler.start()
    try:
        m = serving.ServingMetrics(name="t")
        m.set_gauge("queue_depth", 3)
        m.record_batch(2, 4, 1.5, time.perf_counter())
    finally:
        profiler.stop()
    events = list(profiler._state["events"])
    counters = [e for e in events if e.get("ph") == "C"]
    assert any(e["name"] == "t.queue_depth" for e in counters)
    assert any(e["name"] == "t.batch_occupancy" for e in counters)


# -- ServedModel path -------------------------------------------------------

def test_serving_exported_stablehlo_artifact(tmp_path):
    from mxnet_tpu import stablehlo
    net = _mlp()
    xs = onp.random.RandomState(2).randn(4, 8).astype("float32")
    path = str(tmp_path / "mlp.stablehlo")
    stablehlo.export_model(net, path, mx.nd.array(xs))
    model = stablehlo.import_model(path)
    assert model.batch_size == 4
    assert model.input_signature() == [((8,), onp.dtype("float32"))]
    engine = serving.InferenceEngine(model)
    # the artifact's frozen batch is the only bucket
    assert engine.batch_buckets == (4,)
    ref = net(mx.nd.array(xs)).asnumpy()
    onp.testing.assert_allclose(engine.run_batch([xs])[0], ref,
                                rtol=1e-5, atol=1e-5)
    # smaller requests pad to the frozen batch, larger chunk through it
    onp.testing.assert_allclose(engine.predict(xs[0]), ref[0],
                                rtol=1e-5, atol=1e-5)


# -- HTTP front-end ---------------------------------------------------------

def test_encode_decode_bfloat16_roundtrip():
    # ml_dtypes customs stringify as anonymous void ('<V2') which does not
    # round-trip through onp.dtype(); the wire format must use the name
    import ml_dtypes
    x = onp.arange(6, dtype=ml_dtypes.bfloat16).reshape(2, 3)
    obj = serving.encode_array(x)
    assert obj["dtype"] == "bfloat16"
    y = serving.decode_array(obj)
    assert y.dtype == x.dtype
    assert (y == x).all()


def test_http_round_trip_and_stats():
    net = _mlp()
    engine = serving.InferenceEngine(net, batch_buckets=(1, 2, 4))
    batcher = serving.DynamicBatcher(engine, max_batch_size=4,
                                     max_delay_ms=1.0)
    xs = onp.random.RandomState(3).randn(3, 8).astype("float32")
    ref = net(mx.nd.array(xs)).asnumpy()
    with serving.ModelServer(batcher, port=0) as srv:
        client = serving.ServingClient(srv.url)
        assert client.healthy()
        for i in range(3):
            out = client.predict(xs[i], deadline_ms=5000)
            onp.testing.assert_allclose(out, ref[i], rtol=1e-5, atol=1e-5)
        stats = client.stats()
        assert stats["counters"]["completed"] == 3
        assert stats["latency"]["count"] == 3


def test_stop_drains_inflight_requests_before_severing():
    # a stop mid-request must finish the active response (graceful
    # drain), not sever it; and a stopped server stays unrestartable
    engine = serving.InferenceEngine(_slow_model(0.4), batch_buckets=(1,))
    batcher = serving.DynamicBatcher(engine, max_batch_size=1,
                                     max_delay_ms=0.0)
    srv = serving.ModelServer(batcher, port=0).start()
    client = serving.ServingClient(srv.url)
    x = onp.ones(4, dtype="float32")
    result = {}

    def request():
        result["out"] = client.predict_once(x)

    t = threading.Thread(target=request)
    t.start()
    time.sleep(0.15)               # the request is inside the engine
    srv.stop()                     # default drain budget covers it
    t.join(10)
    onp.testing.assert_allclose(result["out"], x * 2.0)
    with pytest.raises(serving.EngineClosedError):
        srv.start()


def test_client_retries_connection_refused_during_restart_window():
    # a replica restart window looks like connection-refused to the
    # client; predict(max_retries=...) rides it out via faults.classify
    engine = serving.InferenceEngine(_slow_model(0.0), batch_buckets=(1,))
    srv = serving.ModelServer(serving.DynamicBatcher(
        engine, max_batch_size=1, max_delay_ms=0.0), port=0).start()
    host, port = srv.host, srv.port
    client = serving.ServingClient(srv.url)
    x = onp.ones(4, dtype="float32")
    onp.testing.assert_allclose(client.predict(x), x * 2.0)
    srv.stop()
    with pytest.raises(Exception):
        client.predict_once(x)     # nothing listening: refused

    replacement = {}

    def restart():
        time.sleep(0.3)
        eng2 = serving.InferenceEngine(_slow_model(0.0), batch_buckets=(1,))
        replacement["srv"] = serving.ModelServer(
            serving.DynamicBatcher(eng2, max_batch_size=1,
                                   max_delay_ms=0.0),
            host=host, port=port).start()

    t = threading.Thread(target=restart)
    t.start()
    out = client.predict(x, max_retries=10, backoff_ms=100.0)
    onp.testing.assert_allclose(out, x * 2.0)
    t.join(10)
    replacement["srv"].stop()


def test_client_permanent_error_fails_fast_no_retry():
    calls = {"n": 0}

    def broken(x):
        calls["n"] += 1
        raise ValueError("deterministic model bug")

    batcher = serving.DynamicBatcher(
        serving.InferenceEngine(broken, batch_buckets=(1,)),
        max_batch_size=1, max_delay_ms=0.0)
    x = onp.ones(2, dtype="float32")
    with serving.ModelServer(batcher, port=0) as srv:
        client = serving.ServingClient(srv.url)
        with pytest.raises(serving.ServingError):
            client.predict(x, max_retries=5, backoff_ms=10.0)
    # an HTTP 500 (model error) is permanent: one attempt, no retries
    assert calls["n"] == 1


def test_http_queue_full_maps_to_429_and_retry():
    engine = serving.InferenceEngine(_slow_model(0.25), batch_buckets=(1,))
    batcher = serving.DynamicBatcher(engine, max_batch_size=1,
                                     max_delay_ms=0.0, max_queue=1)
    x = onp.zeros(2, dtype="float32")
    with serving.ModelServer(batcher, port=0) as srv:
        client = serving.ServingClient(srv.url)
        # saturate: one in flight + one queued.  Staggered starts — two
        # simultaneous submits can race the dispatcher's pop on the
        # maxsize-1 queue and a SATURATOR would eat the 429 instead
        slow = [threading.Thread(target=lambda: client.predict_once(x))
                for _ in range(2)]
        for t in slow:
            t.start()
            time.sleep(0.05)   # let the dispatcher take it before the next
        time.sleep(0.05)
        with pytest.raises(serving.QueueFullError):
            client.predict_once(x)
        # the retry-with-backoff client rides out the congestion
        out = client.predict(x, max_retries=8, backoff_ms=50.0)
        onp.testing.assert_allclose(out, x * 2.0)
        for t in slow:
            t.join(10)
        assert batcher.stats()["counters"]["rejected_queue_full"] >= 1


# -- client connect/read timeout split + deadline caps ------------------------

def test_client_split_timeout_defaults():
    c = serving.ServingClient("http://127.0.0.1:1", timeout_s=30.0)
    # connect gets its own small budget so a hung connect surfaces in
    # seconds instead of eating the whole read budget
    assert c.connect_timeout_s == 5.0 and c.read_timeout_s == 30.0
    c = serving.ServingClient("http://127.0.0.1:1", timeout_s=2.0)
    assert c.connect_timeout_s == 2.0 and c.read_timeout_s == 2.0
    c = serving.ServingClient("http://127.0.0.1:1", timeout_s=30.0,
                              connect_timeout_s=0.5, read_timeout_s=3.0)
    assert c.connect_timeout_s == 0.5 and c.read_timeout_s == 3.0


def test_client_read_timeout_and_deadline_cap_attempt_wall():
    import socket
    # a server that accepts but never responds: connect succeeds fast,
    # the READ budget is what must cut the attempt
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    sock.listen(8)
    url = f"http://127.0.0.1:{sock.getsockname()[1]}"
    try:
        cli = serving.ServingClient(url, timeout_s=30.0,
                                    read_timeout_s=0.3)
        x = onp.ones(4, dtype="float32")
        t0 = time.perf_counter()
        with pytest.raises((TimeoutError, OSError)):
            cli.predict_once(x)
        assert time.perf_counter() - t0 < 5.0      # not the 30 s budget
        # a request deadline caps EVERY attempt of the retry loop: a
        # flat 30 s read timeout with deadline_ms=400 must fail as a
        # typed deadline error in well under a second per attempt — the
        # hung connect/read can no longer eat the whole deadline before
        # the retry loop gets a say
        cli = serving.ServingClient(url, timeout_s=30.0)
        t0 = time.perf_counter()
        with pytest.raises(serving.DeadlineExceededError):
            cli.predict(x, deadline_ms=400, max_retries=5)
        assert time.perf_counter() - t0 < 5.0
    finally:
        sock.close()
