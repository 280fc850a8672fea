"""The generation engine accounts for its own loop (all CPU).

* each iteration of ``GenerationEngine._loop`` with work is one ``generate``
  step whose phases (``admit``, ``stage``, ``dispatch``, ``readback``,
  ``emit``, ``release``) land in the flight-recorder ring and, inside a ``jax.profiler``
  session, as ``mx:generate.*`` events in the trace's host plane; the loop
  keeps one decode step in flight, so a step's ``readback`` and ``emit`` are
  of the decode step that the step before dispatched (``of_step``);
* the counters that stand in for spans a token, a request or a thread would
  make too many of (``loop_offcpu_us``, ``queue_wait_us``,
  ``emit_to_wire_us``, ``stream_write_us``, ``stream_tokens_written``);
* ``chipbench``'s readers of both (``host_span`` on a hand-built reduced
  trace, the counter metrics in a rehearsal run).
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import memory
from mxnet_tpu import ndarray as nd
from mxnet_tpu import serving
from mxnet_tpu import telemetry
from mxnet_tpu.serving.generate import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PHASES = ("stage", "dispatch", "readback", "emit", "release")


@pytest.fixture(scope="module")
def lm():
    from mxnet_tpu.models.lm import tiny_lm
    mx.random.seed(7)
    net = tiny_lm(vocab_size=64, num_layers=2, units=32, hidden_size=64,
                  num_heads=2, max_length=256)
    net.initialize()
    net(nd.array(onp.zeros((1, 4), onp.int32)),
        nd.array(onp.asarray([4], onp.int32)))       # materialize params
    return net


@pytest.fixture
def engine(lm):
    eng = GenerationEngine(lm, slots=4, max_len=64, prefill_buckets=(8, 16))
    telemetry.reset()
    yield eng
    eng.stop()
    telemetry.enable(None)


def generate_steps():
    """{step id: [ring records]} of the ``generate`` steps in the ring."""
    steps = {}
    for s in telemetry.flight_recorder():
        if s["kind"] == "generate":
            steps.setdefault(s["step"], []).append(s)
    return steps


def host_events(trace_dir):
    """{event name: [event, ...]} over the host plane of the newest trace."""
    from jax.profiler import ProfileData
    from chipbench import trace_reduce
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    out = {}
    for plane in data.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(e)
    return out


def traced(trace_dir, fn):
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return host_events(str(trace_dir))


# -- the ring ----------------------------------------------------------------
def test_each_step_is_an_envelope_with_its_phases_inside(engine):
    streams = [engine.submit([1, 2, 3], max_new_tokens=6) for _ in range(3)]
    for s in streams:
        s.result(30)
    engine.stop()
    steps = generate_steps()
    decode_steps = engine.metrics.stats()["counters"]["decode_steps"]
    assert decode_steps >= 5
    with_decode = 0
    admits = 0
    for sid, spans in steps.items():
        by_phase = {}
        for s in spans:
            by_phase.setdefault(s["phase"], []).append(s)
        assert len(by_phase["step"]) == 1, "one envelope a step"
        env = by_phase.pop("step")[0]
        assert set(by_phase) <= set(PHASES) | {"admit"}
        admits += len(by_phase.get("admit", ()))
        if "stage" in by_phase:
            with_decode += 1
            # a step that decodes has each of the five loop phases once
            assert all(len(by_phase[p]) == 1 for p in PHASES), by_phase
            order = [by_phase[p][0]["ts_us"] for p in PHASES]
            assert order == sorted(order)
            assert by_phase["emit"][0]["args"]["riders"] >= 1
        children = [s for ss in by_phase.values() for s in ss]
        for c in children:      # ring stamps are whole microseconds
            assert c["ts_us"] >= env["ts_us"] - 1
            assert c["ts_us"] + c["dur_us"] <= \
                env["ts_us"] + env["dur_us"] + 2
        assert sum(c["dur_us"] for c in children) <= env["dur_us"] + 2
    assert with_decode == decode_steps
    assert admits == 3
    # the last tokens are drained by a step that dispatches nothing
    last = sorted(steps)[-1]
    assert sorted(s["phase"] for s in steps[last]) == \
        ["emit", "readback", "release", "step"]
    first_admit = next(s for spans in steps.values() for s in spans
                       if s["phase"] == "admit")
    assert first_admit["args"] == {"bucket": 8, "slot": 0, "prompt_len": 3}


def test_the_wait_on_an_empty_queue_is_outside_any_step(engine):
    import time
    time.sleep(0.3)         # several 50 ms waits with nothing to do
    assert generate_steps() == {}
    engine.generate([4, 5], max_new_tokens=1, timeout=30)
    engine.stop()
    steps = generate_steps()
    # one token: the prefill's; a step with an admission and no decode,
    # which reads and emits what the admission dispatched (the drain)
    assert len(steps) == 1
    spans = sorted(next(iter(steps.values())), key=lambda s: s["ts_us"])
    assert [s["phase"] for s in spans if s["phase"] != "step"] == \
        ["admit", "readback", "emit", "release"]
    assert spans[[s["phase"] for s in spans].index("emit")]["args"] == \
        {"riders": 1}


def test_readback_of_a_decode_step_carries_that_steps_id(engine):
    """One decode step in flight: a loop step stages and dispatches decode
    step n+1, then reads and emits decode step n, which the loop step
    before dispatched; its ``readback`` says so."""
    engine.generate([1, 2, 3], max_new_tokens=6, timeout=30)
    engine.stop()
    steps = generate_steps()
    ids = sorted(steps)
    by_phase = [{s["phase"]: s for s in steps[i]} for i in ids]
    dispatched = [i for i, ph in zip(ids, by_phase) if "dispatch" in ph]
    assert len(dispatched) == 5
    # the first loop step admits, dispatches decode step 1 and reads the
    # prefill's token: no decode step is read yet
    assert by_phase[0]["readback"]["args"] == {"of_step": None}
    assert by_phase[0]["emit"]["args"] == {"riders": 1}
    # every later one reads the decode step of the loop step before it
    for before, ph in zip(ids, by_phase[1:]):
        assert ph["readback"]["args"] == {"of_step": before}
        assert ph["emit"]["args"] == {"riders": 1}
    # and the order inside a loop step that dispatches
    for ph in by_phase[:5]:
        order = [ph[p]["ts_us"] for p in PHASES]
        assert order == sorted(order)
    assert "stage" not in by_phase[5] and "dispatch" not in by_phase[5]
    c = engine.metrics.stats()["counters"]
    assert c["decode_steps"] == 5 and c["decode_steps_overlapped"] == 4


def test_telemetry_off_records_nothing_and_the_counters_still_count(engine):
    telemetry.enable(False)
    before = telemetry._SPANS.value
    assert telemetry.step_span("generate") is telemetry.phase("stage")
    out = engine.generate([1, 2, 3], max_new_tokens=5, timeout=30)
    assert len(out["tokens"]) == 5
    engine.stop()       # the loop counts a step's tokens after it emits them
    assert telemetry.flight_recorder() == []
    assert telemetry._SPANS.value == before
    c = engine.metrics.stats()["counters"]
    assert c["decode_steps"] == 4 and c["tokens_generated"] == 4
    assert c["prefills"] == 1 and c["queue_wait_us"] > 0
    assert c["loop_offcpu_us"] >= 0
    assert engine.metrics.stats()["decode_step"]["count"] == 4


def test_decode_step_ms_runs_from_stage_to_the_end_of_emit(engine):
    engine.generate([1, 2, 3], max_new_tokens=8, timeout=30)
    engine.stop()
    hist = engine.metrics.stats()["decode_step"]
    assert hist["count"] == 7
    spans = [s for ss in generate_steps().values() for s in ss]
    staged = {s["step"] for s in spans if s["phase"] == "stage"}
    assert len(staged) == 7         # the eighth loop step is the drain
    inside = sum(s["dur_us"] for s in spans if s["step"] in staged
                 and s["phase"] in PHASES[:4]) / 1e3
    envelopes = sum(s["dur_us"] for s in spans if s["phase"] == "step") / 1e3
    # one sample a loop step that dispatches, from its stage to the end of
    # its emit (of the decode step before): no less than the four phases it
    # spans, no more than the envelopes
    assert inside <= hist["mean_ms"] * hist["count"] * 1.001
    assert hist["mean_ms"] * hist["count"] <= envelopes


@pytest.mark.parametrize("where", ["compile", "stage"])
def test_a_failure_before_the_dispatch_fails_the_riders_only(engine, where,
                                                             monkeypatch):
    """The lazy decode compile and the ``stage`` phase run inside the
    step's ``try``: the riders get the error and the loop keeps serving."""
    calls = []
    if where == "compile":
        real = engine._compile_decode

        def flaky():
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("lowering failed")
            return real()
        monkeypatch.setattr(engine, "_compile_decode", flaky)
    else:
        engine.precompile()
        real = engine._read_params

        def flaky():        # call 1 is the prefill's, call 2 the stage's
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("parameters unreadable")
            return real()
        monkeypatch.setattr(engine, "_read_params", flaky)
    with pytest.raises(RuntimeError):
        engine.generate([1, 2, 3], max_new_tokens=4, timeout=30)
    assert engine._thread.is_alive()
    assert len(engine._free) == 4
    out = engine.generate([1, 2, 3], max_new_tokens=4, timeout=30)
    assert len(out["tokens"]) == 4
    counters = engine.metrics.stats()["counters"]
    assert counters["errors"] == 1 and counters["completed"] == 1


def test_memory_sampler_runs_once_a_generate_step(engine):
    if not memory._census_active:
        pytest.skip("MXNET_MEMORY is off")
    n0 = memory._nsamples[0]
    engine.generate([1, 2, 3], max_new_tokens=10, timeout=30)
    engine.stop()
    steps = generate_steps()
    spans = sum(len(ss) for ss in steps.values())
    assert spans >= 4 * len(steps)
    assert memory._nsamples[0] - n0 == len(steps)
    assert {s["phase"] for s in memory.samples()[-len(steps):]} == {"step"}


def test_phases_of_other_steps_are_still_sampled():
    if not memory._census_active:
        pytest.skip("MXNET_MEMORY is off")
    telemetry.reset()
    n0 = memory._nsamples[0]
    with telemetry.step_span("serve"):
        with telemetry.phase("stage"):
            pass
        with telemetry.phase("execute"):
            pass
    assert memory._nsamples[0] - n0 == 3


# -- the profiler --------------------------------------------------------------
def test_generate_phases_land_in_the_profilers_host_plane(engine, tmp_path):
    def run():      # the loop thread closes its last step before the stop
        engine.generate([1, 2, 3], max_new_tokens=6, timeout=30)
        engine.stop()
    events = traced(tmp_path, run)
    names = {"mx:generate." + p for p in PHASES + ("admit", "step")}
    assert names <= set(events)
    ring = generate_steps()
    # the same steps under the same ids, on the profiler's clock
    for name in names:
        ids = {int(dict(e.stats)["step"]) for e in events[name]}
        assert ids <= set(ring) and ids
    # five loop steps dispatch a decode step; a sixth drains the last one
    assert len(events["mx:generate.stage"]) == 5
    assert len(events["mx:generate.dispatch"]) == 5
    assert len(events["mx:generate.readback"]) == 6
    assert len(events["mx:generate.emit"]) == 6
    assert len(events["mx:generate.release"]) == 6
    env = {int(dict(e.stats)["step"]): e for e in events["mx:generate.step"]}
    for e in events["mx:generate.readback"]:
        parent = env[int(dict(e.stats)["step"])]
        assert parent.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= \
            parent.start_ns + parent.duration_ns
    # the programs have names of their own in the trace
    assert any("pure_decode" in n for n in events)
    assert any("pure_prefill_L8" in n for n in events)


def test_trainer_and_serve_phases_get_the_same_bridge(tmp_path):
    telemetry.reset()

    def steps():
        telemetry.step_boundary("train")
        with telemetry.phase("stage"):
            pass
        with telemetry.phase("dispatch"):
            pass
        telemetry.end_step()
        with telemetry.step_span("serve"):
            with telemetry.phase("execute", bucket=4):
                pass
        with telemetry.phase("compile"):        # outside any step
            pass
    events = traced(tmp_path, steps)
    assert {"mx:train.step", "mx:train.stage", "mx:train.dispatch",
            "mx:serve.step", "mx:serve.execute", "mx:compile"} <= set(events)
    ring = {(s["kind"], s["phase"]): s["step"]
            for s in telemetry.flight_recorder()}
    assert int(dict(events["mx:train.stage"][0].stats)["step"]) == \
        ring[("train", "stage")]
    assert int(dict(events["mx:serve.execute"][0].stats)["step"]) == \
        ring[("serve", "execute")]
    assert not hasattr(telemetry, "current_step")


# -- the wire's counters -------------------------------------------------------
def test_a_streamed_request_moves_the_wire_and_queue_counters(lm):
    predict = serving.InferenceEngine(lambda x: (onp.asarray(x) * 2.0,),
                                      batch_buckets=(1, 2))
    gen = GenerationEngine(lm, slots=4, max_len=64, prefill_buckets=(8, 16))
    telemetry.set_trace_sample(1.0)
    try:
        with serving.ModelServer(predict, port=0, generator=gen) as srv:
            client = serving.ServingClient(srv.url, pool=False)
            before = gen.metrics.stats()["counters"]
            it = client.generate_stream([9, 4, 7], max_new_tokens=6)
            toks = []
            while True:
                try:
                    toks.append(next(it))
                except StopIteration as stop:
                    final = stop.value
                    break
            assert len(toks) == 6 and final["tokens"] == toks
            # the writer adds its sums once a wake, before the handler
            # wakes to write the final line
            import time
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and gen.metrics.stats()[
                    "counters"]["stream_tokens_written"] == \
                    before["stream_tokens_written"]:
                time.sleep(0.01)
            after = gen.metrics.stats()["counters"]
            d = {k: after[k] - before[k] for k in after}
            assert d["stream_tokens_written"] == 6
            assert d["prefills"] == 1 and d["queue_wait_us"] > 0
            # written inside the wait: emit -> flushed covers the write
            assert 0 < d["stream_write_us"] <= d["emit_to_wire_us"]
            # a request that is not streamed writes no token line
            client.generate([9, 4, 7], max_new_tokens=3)
            again = gen.metrics.stats()["counters"]
            assert again["stream_tokens_written"] == \
                after["stream_tokens_written"]
            assert again["prefills"] == after["prefills"] + 1
            # the sampled request's own trace has the queue wait beside
            # the prefill
            names = [s["phase"] for s in final["trace"]["spans"]]
            assert "generate_queue" in names and "generate_prefill" in names
            snap = telemetry.snapshot()["counters"]
            assert snap["generate/stream_tokens_written"] >= 6
            assert snap["generate/queue_wait_us"] > 0
    finally:
        telemetry.set_trace_sample(None)


def test_handler_threads_adding_their_sums_lose_no_update():
    import threading
    from mxnet_tpu.serving.generate import GenerationMetrics
    m = GenerationMetrics()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def handler():
            for _ in range(200):    # 200 streams ending on this thread
                m.add(emit_to_wire_us=7, stream_write_us=3,
                      stream_tokens_written=5)
                m.record_decode_step(2, 2, 1.0, 11, True)
        threads = [threading.Thread(target=handler) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    c = m.stats()["counters"]
    n = 32 * 200
    assert (c["emit_to_wire_us"], c["stream_write_us"],
            c["stream_tokens_written"]) == (7 * n, 3 * n, 5 * n)
    assert (c["decode_steps"], c["decode_steps_overlapped"],
            c["tokens_generated"], c["loop_offcpu_us"]) == \
        (n, n, 2 * n, 11 * n)
    assert m.stats()["decode_step"]["count"] == n


# -- chipbench's readers -------------------------------------------------------
MS = 1_000_000


def hand_trace():
    """Five decode runs of 10 ms every 40 ms (the first and the last count
    as cut, which leaves three whole runs and two periods, 40 to 120 ms),
    one 5 ms prefill, and the loop's spans on one thread line with a
    handler's on another."""
    mods = [["jit_pure_decode(1)", t * MS, 10 * MS] for t in
            (0, 40, 80, 120, 160)] + [["jit_pure_prefill_L32(2)",
                                       65 * MS, 5 * MS]]
    ops = [["fusion f32[8]", m[1], m[2], "fusion", 0] for m in mods]
    loop = []
    for t in (30, 70, 110, 150):        # a step's phases lead its run
        loop += [["mx:generate.step", t * MS, 39 * MS],
                 ["mx:generate.stage", t * MS, 2 * MS],
                 ["mx:generate.dispatch", (t + 2) * MS, 8 * MS],
                 ["mx:generate.readback", (t + 10) * MS, 10 * MS],
                 ["mx:generate.emit", (t + 20) * MS, 6 * MS],
                 ["mx:generate.release", (t + 26) * MS, 3 * MS]]
    loop.append(["mx:generate.admit", 60 * MS, 9 * MS])
    return {"devices": [{"name": "/device:TPU:0", "modules": mods,
                         "ops": ops}],
            "host": {"generate-engine": loop,
                     "Thread-9": [["mx:generate.emit", 200 * MS, 1 * MS],
                                  ["other", 50 * MS, 60 * MS]]}}


ROLES = {"decode": {"prefix": "jit_pure", "pick": "most_frequent"},
         "prefill": {"prefix": "jit_pure", "pick": "rest"}}


@pytest.mark.parametrize("metric, want", [
    ("loop_stage_ms.decode", 2.0),      # starts at 70 and 110: 4 ms / 2
    ("loop_dispatch_ms.decode", 8.0),
    ("loop_readback_ms.decode", 10.0),
    ("loop_emit_ms.decode", 6.0),       # the one at 200 ms is outside
    ("loop_release_ms.decode", 3.0),
    ("loop_admit_ms.decode", 4.5),      # one admission in two periods
    # idle in [40, 120] ms: 50-65, 70-80, 90-120 = 55 ms; inside a phase
    # span: 50-59 (emit, release), 60-65 (admit), 70-80 (stage, dispatch),
    # 90-99 (emit, release), 110-120 (stage, dispatch) = 43; the envelope
    # and the other thread's span name nothing: 12 ms over two periods
    ("idle_unnamed_ms.decode", 6.0),
])
def test_host_span_reads_the_reckoned_numbers(metric, want):
    from chipbench import common
    from chipbench.readers import host_span
    spec = dict(common.load("metrics", metric), name=metric)
    assert spec["reader"] == "host_span"
    obs = {"trace": hand_trace(), "readings": {"roles": ROLES}}
    assert host_span.read(spec, obs) == pytest.approx(want)
    # a program from before the spans, or a run with no device plane,
    # reports nothing under the metric's name
    bare = hand_trace()
    bare["host"] = {"main": [["PjitFunction(jit(pure))", 30 * MS, 8 * MS]]}
    assert host_span.read(spec, dict(obs, trace=bare)) is None
    assert host_span.read(spec, dict(obs, trace=None)) is None
    assert host_span.read(spec, dict(obs, trace={"devices": [],
                                                 "host": {}})) is None


def test_the_phases_add_up_on_the_hand_trace():
    from chipbench import common, trace_reduce
    from chipbench.readers import host_span
    trace = hand_trace()
    obs = {"trace": trace, "readings": {"roles": ROLES}}
    total = sum(host_span.read(dict(common.load("metrics", m), name=m), obs)
                for m in ("loop_stage_ms.decode", "loop_dispatch_ms.decode",
                          "loop_readback_ms.decode", "loop_emit_ms.decode",
                          "loop_release_ms.decode", "loop_admit_ms.decode"))
    decode = trace_reduce.modules_by_role(trace["devices"][0],
                                          ROLES)["decode"]
    assert trace_reduce.mean_period_ms(decode) == 40.0
    assert total == pytest.approx(33.5)
    # and the breakdown names the program's span where one covers half a gap
    gaps = dict(trace_reduce.top_idle_gaps(trace))
    assert any("mx:generate." in k for k in gaps)


@pytest.mark.parametrize("metric, counters, want", [
    ("loop_offcpu_us.decode", {"loop_offcpu_us": 900, "decode_steps": 3},
     300.0),
    ("loop_offcpu_us.decode", {"decode_steps": 3}, None),   # the parent's
    ("emit_to_wire_us", {"emit_to_wire_us": 5000,
                         "stream_tokens_written": 10}, 500.0),
    ("emit_to_wire_us", {"tokens_generated": 10}, None),
    ("wire_write_us", {"stream_write_us": 700,
                       "stream_tokens_written": 10}, 70.0),
    ("queue_wait_us", {"queue_wait_us": 1200, "prefills": 4}, 300.0),
    ("overlap_share.decode", {"decode_steps_overlapped": 9,
                              "decode_steps": 10}, 0.9),
    ("overlap_share.decode", {"decode_steps": 10}, None),   # the parent's
    ("overlap_share.dsv32", {"decode_steps_overlapped": 10,
                             "decode_steps": 10}, 1.0),
    ("writer_batch_tokens.decode", {"stream_tokens_written": 1270,
                                    "stream_writer_wakes": 10}, 127.0),
    # the parent counts the token lines and has no writer to count wakes
    ("writer_batch_tokens.decode", {"stream_tokens_written": 1270}, None),
    ("writer_batch_tokens.dsv32", {"stream_tokens_written": 640,
                                   "stream_writer_wakes": 10}, 64.0),
])
def test_counter_metrics_read_their_counters(metric, counters, want):
    from chipbench import common
    spec = dict(common.load("metrics", metric), name=metric)
    got = common.plugin("readers", spec["reader"]).read(
        spec, {"readings": {"counters": counters}})
    assert got == want


def test_new_metrics_are_entries_of_the_benchmark_except_queue_wait():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    new = ["loop_stage_ms.decode", "loop_dispatch_ms.decode",
           "loop_readback_ms.decode", "loop_emit_ms.decode",
           "loop_admit_ms.decode", "idle_unnamed_ms.decode",
           "loop_offcpu_us.decode", "emit_to_wire_us", "wire_write_us",
           "loop_release_ms.decode"]
    # appended in this order by PR 25; later PRs append after them
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(new[0])
    assert names[at:at + len(new)] == new
    for name in new:
        assert entries[name]["workloads"] == ["gpt1.decode_full"]
    # like wire_ttft_ms: a file, and no entry until a cell judges TTFT
    assert "queue_wait_us" not in entries
    assert os.path.isfile(os.path.join(REPO, "chipbench", "metrics",
                                       "queue_wait_us.json"))


@pytest.mark.parametrize("stem, layer, unit", [
    ("overlap_share", "host loop, serving", "share"),           # PR 29
    ("writer_batch_tokens", "entry point, wire", "tokens"),     # PR 32
])
def test_a_pair_of_counter_metrics_is_an_entry_a_serving_cell(stem, layer,
                                                              unit):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # appended as a pair, after everything that was there
    at = names.index(stem + ".decode")
    pair = bench["per_layer"][at:at + 2]
    assert [(m["name"], m["workloads"]) for m in pair] == [
        (stem + ".decode", ["gpt1.decode_full"]),
        (stem + ".dsv32", ["deepseek_v32.decode_long"])]
    for m in pair:
        assert (m["layer"], m["moves"], m["unit"], m["better"]) == (
            layer, "serve_tokens_per_s", unit, "higher")


# the sets of metric files a model_config PR copies for its job kind
# (PERF.md section 7.7): suffix -> (job, cell, the entry they all follow,
# what the set has of its own beside the 19 serving namesakes, what
# DeepSeek's set has that it lacks)
METRIC_SETS = {
    "lfm2": ("serve_lfm2", "lfm2_24b.decode_rollout",         # PR 33
             "writer_batch_tokens.dsv32",
             {"experts_touched", "expert_load_max", "kv_context_mean",
              "decode_step_roofline", "expert_rows_computed",       # PR 36
              "kv_rows_read"},                                      # PR 38
             {"index_selected_share", "routed_held_share",
              "latent_rows_read"}),
    "keye": ("serve_keye", "keye_vl2.decode_doc",             # PR 35
             "decode_step_roofline.lfm2",
             {"experts_touched", "expert_load_max", "kv_context_mean",
              "decode_step_roofline", "index_selected_share",
              "kv_rows_read", "expert_rows_computed"},              # PR 36
             {"routed_held_share", "latent_rows_read"}),
}


def _set_metric_files(suffix):
    return sorted(f[:-5] for f in os.listdir(os.path.join(
        REPO, "chipbench", "metrics")) if f.endswith(f".{suffix}.json"))


@pytest.mark.parametrize("name", [
    name for suffix in METRIC_SETS for name in _set_metric_files(suffix)])
def test_a_sets_metric_file_is_an_entry_of_its_cell(name):
    """Every ``*.lfm2.json`` and ``*.keye.json`` has its ``BENCHMARK.json``
    entry, which says what the file says and lists the one cell; a
    namesake of the serving set reads as its ``dsv32`` / ``lfm2`` file
    does."""
    from chipbench import common
    suffix = name.rsplit(".", 1)[1]
    job, cell, after, _own, _lacks = METRIC_SETS[suffix]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = common.load("metrics", name)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [cell]
    assert spec["jobs"] == [job]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    # appended behind everything that was there
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) > names.index(after)
    stem = name[:-len(suffix)]
    twins = [stem + other for other in ("dsv32", "lfm2")
             if other != suffix and stem + other in common.names("metrics")]
    if twins:
        other = common.load("metrics", twins[0])
        assert {k: v for k, v in spec.items() if k != "jobs"} \
            == {k: v for k, v in other.items() if k != "jobs"}
    else:
        assert name in ("kv_context_mean.lfm2", "kv_rows_read.keye",
                        "kv_rows_read.lfm2")


@pytest.mark.parametrize("suffix", sorted(METRIC_SETS))
def test_a_set_has_the_serving_namesakes_and_those_of_its_own(suffix):
    _job, _cell, _after, own, lacks = METRIC_SETS[suffix]
    stems = {n[:-len("." + suffix)] for n in _set_metric_files(suffix)}
    assert own <= stems
    assert len(stems) == 19 + len(own)
    from chipbench import common
    served = {n[:-len(".dsv32")] for n in common.names("metrics")
              if n.endswith(".dsv32")}
    # what DeepSeek's cell reads of its own mechanisms and this set does not
    assert served - stems == lacks


def test_rehearsal_of_the_serving_cell_reads_the_counter_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--rehearse", "--workload", "gpt1.decode_full", "--seed",
         "2147483999", "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    readings = next(x["rehearsal_readings"] for x in lines
                    if "rehearsal_readings" in x)
    for name in ("loop_offcpu_us.decode", "emit_to_wire_us", "wire_write_us"):
        assert readings[name]["unit"] == "us"
        assert readings[name]["value"] >= 0
    # the clients keep every slot taken: the loop never drains in the window
    assert readings["overlap_share.decode"]["value"] > 0.9
    # one wake takes a step's tokens or, behind on a CPU's steps, several
    assert readings["writer_batch_tokens.decode"]["value"] >= 1
    assert readings["wire_write_us"]["value"] <= \
        readings["emit_to_wire_us"]["value"]
    # the span metrics need a device plane: a CPU prints none of them, and
    # never a time under a device metric's name
    assert not any(k.startswith(("loop_stage", "idle_unnamed"))
                   for k in readings)
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert all(m["value"] is None for m in result["metrics"].values())
