"""mxnet_tpu.memory: live-array census lifecycle (weakref-only, retired
accumulators, origin tags across adopt_pending/zero_grad/hot-swap), the
per-program memory ledger vs the census referee, phase-correlated
sampling, OOM forensics (resource classification, the injected ``oom``
fault kind, crash-report memory section + tools/memory_report.py), the
leak-detection mode, the remat temp-bytes ordering, and the
check_keep_in_sync lint (docs/OBSERVABILITY.md, docs/RESILIENCE.md)."""
import gc
import importlib.util
import json
import os
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, faults, memory, nd, telemetry
from mxnet_tpu.gluon import Trainer, loss as gloss, nn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture(autouse=True)
def _clean():
    memory.reset()
    telemetry.enable(None)
    engine.set_engine_type("ThreadedEngine")
    faults.reset()
    yield
    memory.reset()
    telemetry.enable(None)
    engine.set_engine_type("ThreadedEngine")
    faults.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mlp(units=16, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize()
    return net


def _train_steps(net, tr, steps=3, batch=8, units=16, lazy=True):
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    if lazy:
        engine.set_engine_type("LazyEngine")
    x = nd.array(onp.random.RandomState(0).randn(batch, units)
                 .astype("float32"))
    y = nd.zeros((batch,))
    L = None
    for _ in range(steps):
        with autograd.record():
            L = lossfn(net(x), y).mean()
        L.backward()
        tr.step(batch)
    float(L.astype("float32").asnumpy())
    return L


# ---------------------------------------------------------------------------
# census lifecycle
# ---------------------------------------------------------------------------
def test_census_register_and_gc_no_leak():
    base_live = memory.census_bytes_total()
    base_retired = memory.retired_bytes()
    arrs = [nd.zeros((64, 64)) for _ in range(5)]
    nbytes = 64 * 64 * 4
    assert memory.census_bytes_total() >= base_live + 5 * nbytes
    assert memory.live_bytes()["activation"] >= 5 * nbytes
    del arrs
    gc.collect()
    # weakref-only: every entry retired, bytes fold monotonically
    assert memory.census_bytes_total() <= base_live + nbytes
    assert memory.retired_bytes() >= base_retired + 5 * nbytes
    # retired never decreases
    r1 = memory.retired_bytes()
    a = nd.zeros((8, 8))
    del a
    gc.collect()
    assert memory.retired_bytes() >= r1
    # allocated is monotonic and >= retired
    assert memory.allocated_bytes() >= memory.retired_bytes()


def test_census_tracks_raw_jax_arrays():
    # raw jax.Arrays (stager placements, SPMD optimizer states) register
    # too — and they are UNHASHABLE, so the registry must never hash the
    # referent (regression: the entry set once delegated hash to it)
    import jax.numpy as jnp
    raw = jnp.zeros((32, 32))
    memory.tag(raw, "prefetch_staged")
    assert memory.origin_of(raw) == "prefetch_staged"
    assert memory.live_bytes()["prefetch_staged"] >= 32 * 32 * 4
    r0 = memory.retired_bytes()
    del raw
    gc.collect()
    assert memory.live_bytes()["prefetch_staged"] == 0
    assert memory.retired_bytes() >= r0 + 32 * 32 * 4


def test_census_disabled_registers_nothing():
    memory.enable(False)
    base = memory.census_bytes_total()
    a = nd.zeros((128, 128))
    assert memory.census_bytes_total() == base
    assert memory.origin_of(a) is None
    memory.enable(None)


def test_census_skips_tracers():
    import jax

    seen = []

    def f(x):
        wrapped = nd.NDArray(x)          # wraps a tracer under the trace
        seen.append(memory.origin_of(wrapped))
        return x * 2

    jax.jit(f)(onp.ones((4,), "float32"))
    assert seen == [None]


def test_parameter_gradient_state_origins():
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.01, "momentum": 0.9})
    _train_steps(net, tr, steps=2)
    for p in net.collect_params().values():
        assert memory.origin_of(p._nd) == "parameter"
        assert memory.origin_of(p._nd._grad) == "gradient"
    lb = memory.live_bytes()
    assert lb["parameter"] > 0 and lb["gradient"] > 0
    # sgd+momentum has one state array per param (captured path holds
    # them as NDArrays, materializing paths as raw jax arrays)
    assert lb["optimizer_state"] > 0


def test_pending_origin_and_materialize_retag():
    x = nd.zeros((32, 32))
    pend0 = memory.live_bytes()["pending"]
    with engine.bulk(64):
        y = x + 1.0
        assert y._pending is not None
        # deferred slots are accounted at the segment level (no weakref
        # entry per placeholder — the mem_overhead_always_on bar), so
        # the placeholder itself is not yet in the registry...
        assert memory.origin_of(y) is None
        # ...but the pending origin carries its bytes
        assert memory.live_bytes()["pending"] >= pend0 + 32 * 32 * 4
        assert memory.census()["by_origin"]["pending"]["bytes"] \
            >= 32 * 32 * 4
    # bulk exit flushed the segment: the slot materialized, entered the
    # census as an activation, and the deferred accounting released
    assert y._pending is None and y._data is not None
    assert memory.origin_of(y) == "activation"
    assert memory.live_bytes()["pending"] == pend0


def test_origins_across_adopt_zero_grad_hotswap():
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.01, "momentum": 0.9})
    _train_steps(net, tr, steps=2)       # captured: params adopt_pending'd
    p = list(net.collect_params().values())[0]
    # adopt_pending rebinds the param NDArray onto a pending slot every
    # captured step — the origin must survive (flush retags ONLY pending)
    assert memory.origin_of(p._nd) == "parameter"
    # zero_grad rebinds the grad buffer in place: still a gradient
    p.zero_grad()
    assert memory.origin_of(p._nd._grad) == "gradient"
    # hot-swap (serving weight swap path): set_data keeps the tag
    p.set_data(nd.ones(p.shape))
    assert memory.origin_of(p._nd) == "parameter"


def test_adopt_and_tag_discount_pending_accounting():
    # a slot whose output lands in an already-registered array must NOT
    # also count under "pending" (review finding: census double-counted
    # the whole param/grad/state footprint while a segment was open)
    dst = nd.zeros((64, 64))
    memory.tag(dst, "parameter")
    nbytes = 64 * 64 * 4
    pend0 = memory.live_bytes()["pending"]
    with engine.bulk(64):
        src = dst + 1.0
        assert memory.live_bytes()["pending"] >= pend0 + nbytes
        engine.adopt_pending(dst, src)
        # adopted: the slot's bytes moved out of the deferred accounting
        assert memory.live_bytes()["pending"] <= pend0
    assert dst._data is not None
    assert memory.origin_of(dst) == "parameter"
    # same for registering a still-pending NDArray under an origin
    x = nd.zeros((32, 32))
    pend1 = memory.live_bytes()["pending"]
    with engine.bulk(64):
        y = x * 2.0
        assert memory.live_bytes()["pending"] >= pend1 + 32 * 32 * 4
        memory.tag(y, "optimizer_state")
        assert memory.live_bytes()["pending"] <= pend1
        assert memory.origin_of(y) == "optimizer_state"


def test_census_dedups_aliasing_wrappers():
    a = nd.zeros((64, 64))
    b = a.detach()                        # second wrapper, same buffer
    assert b._data is a._data
    c = memory.census()
    nbytes = 64 * 64 * 4
    total_64s = sum(g["bytes"] for g in c["groups"]
                    if g["origin"] == "activation" and g["bytes"] >= nbytes)
    # incremental gauges double-count the alias; the census walk must not
    assert memory.live_bytes()["activation"] >= 2 * nbytes
    assert c["by_origin"]["activation"]["bytes"] < 2 * nbytes \
        or total_64s < 2 * nbytes
    del b


# ---------------------------------------------------------------------------
# per-program ledger + census referee
# ---------------------------------------------------------------------------
def test_census_vs_memory_analysis_referee(tmp_path, monkeypatch):
    """The census estimate and XLA's buffer assignment agree within 10%
    on a referee program: a fused lazy segment whose every slot stays
    live, so ledger output+temp bytes == the bytes the census gains."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    engine.reset_op_cache()
    memory.reset()
    x = nd.zeros((128, 256))
    outs = []
    gc.collect()
    before = memory.live_bytes()["activation"]
    with engine.bulk(64):
        cur = x
        for i in range(8):
            cur = cur + float(i + 1)
            outs.append(cur)
    nd.waitall()
    after = memory.live_bytes()["activation"]
    census_delta = after - before
    entries = [e for e in memory.ledger() if e["kind"] == "lazy_segment"]
    assert entries, "segment compile did not land in the ledger"
    e = entries[-1]
    ledger_bytes = e["output_bytes"] + e["temp_bytes"]
    expect = 8 * 128 * 256 * 4
    assert census_delta >= expect
    assert abs(census_delta - ledger_bytes) <= 0.1 * max(census_delta,
                                                         ledger_bytes)
    # the ledger entry carries the full byte breakdown and a key
    assert e["argument_bytes"] >= 128 * 256 * 4
    assert e["peak_bytes"] >= ledger_bytes
    assert e["key"]


def test_ledger_and_flush_span_bytes(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    engine.reset_op_cache()
    memory.reset()
    telemetry.reset()
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    _train_steps(net, tr, steps=2)
    led = memory.ledger()
    assert led and all("peak_bytes" in e for e in led)
    assert memory.ledger_peak(led[0]["key"]) == led[0]["peak_bytes"]
    # pc:<key12> label resolution (the serving execute-span handle)
    assert memory.ledger_peak("pc:" + led[0]["key"][:12]) \
        == led[0]["peak_bytes"]
    # the step_flush span carries the bytes column
    flush_spans = [s for s in telemetry.flight_recorder()
                   if s["phase"] == "step_flush"]
    assert flush_spans
    with_bytes = [s for s in flush_spans
                  if (s.get("args") or {}).get("bytes")]
    assert with_bytes, "no step_flush span carried ledger bytes"
    # and trace_report folds it into the peak_bytes column
    tr_mod = _load_tool("trace_report")
    rep = tr_mod.fold(tr_mod.load_spans(
        telemetry.flight_recorder_payload()))
    assert rep["aggregate"]["max_peak_bytes"] > 0
    table = tr_mod.format_table(rep)
    assert "peak_mb" in table


def test_sampling_phase_peaks_and_metrics():
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    _train_steps(net, tr, steps=3)
    peaks = memory.phase_peaks()
    assert "forward" in peaks and "optimizer_update" in peaks
    assert all(p["peak_bytes"] >= 0 and "step" in p
               for p in peaks.values())
    assert memory.samples() and memory.samples()[-1]["origins"]
    assert memory.device_bytes_in_use() >= 0
    snap = telemetry.snapshot()
    assert snap["gauges"]["memory/live_bytes_parameter"] > 0
    assert snap["counters"]["memory/allocated_bytes_total"] > 0
    assert snap["counters"]["memory/samples"] > 0
    assert "mxnet_memory_live_bytes_total" in telemetry.prometheus_text()
    # CPU exposes no memory_stats(): samples must say census
    assert memory.samples()[-1]["source"] == "census"


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
def test_classify_resource():
    assert faults.classify(faults.ResourceExhausted("x")) == faults.RESOURCE
    assert faults.classify(MemoryError()) == faults.RESOURCE

    class XlaRuntimeError(RuntimeError):
        pass

    assert faults.classify(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1234 bytes")) \
        == faults.RESOURCE
    assert faults.classify(XlaRuntimeError("INTERNAL: fabric wedged")) \
        == faults.TRANSIENT
    # user marks still win
    faults.mark_transient(XlaRuntimeError)
    try:
        assert faults.classify(XlaRuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory")) == faults.TRANSIENT
    finally:
        faults._transient_marks.remove(XlaRuntimeError)


def test_oom_fault_kind_single_purge_retry(tmp_path):
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x, y = nd.zeros((4, 16)), nd.zeros((4,))
    rs = faults.ResilientStep(tr, skip_nonfinite=False,
                              crash_report_dir=str(tmp_path))
    purges_before = engine.engine_stats()["cache_purges"]
    with faults.inject("trainer.step@2:oom"):
        for _ in range(3):
            with autograd.record():
                L = lossfn(net(x), y).mean()
            L.backward()
            rs.step(4, loss=L)
    # recovered: exactly one purge+gc retry, no crash report
    assert faults.counters()["oom_recoveries"] == 1
    assert engine.engine_stats()["cache_purges"] == purges_before + 1
    assert not list(tmp_path.glob("crash_report_*.json"))


def test_oom_acceptance_crash_report_and_memory_report(tmp_path,
                                                       monkeypatch):
    """Acceptance proof: an injected ``oom`` fault under ResilientStep
    produces a crash report whose memory section names the top origin
    classes and the peak-owning ProgramCache key, and
    tools/memory_report.py renders a per-phase peak table from it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "pc"))
    engine.reset_op_cache()
    memory.reset()
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.01, "momentum": 0.9})
    _train_steps(net, tr, steps=2)       # warm: ledger + census populated
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x, y = nd.zeros((8, 16)), nd.zeros((8,))
    rs = faults.ResilientStep(tr, skip_nonfinite=False,
                              crash_report_dir=str(tmp_path))
    with faults.inject("trainer.step@1:oomx2"):
        with pytest.raises(faults.ResourceExhausted):
            with autograd.record():
                L = lossfn(net(x), y).mean()
            L.backward()
            rs.step(8, loss=L)
    # the single purge retry happened, then it raised
    assert faults.counters()["oom_recoveries"] == 1
    reports = sorted(tmp_path.glob("crash_report_*.json"))
    assert reports
    payload = json.load(open(reports[-1]))
    assert payload["schema"] == 7
    mem = payload["memory"]
    assert mem["schema"] == 1
    # names the top origin classes...
    tops = [r["origin"] for r in mem["census"]["top"]]
    assert "parameter" in tops and "gradient" in tops
    # ...and the peak-owning ProgramCache key
    hottest = mem["ledger"]["hottest"]
    assert hottest and hottest[0]["key"] \
        and hottest[0]["peak_bytes"] >= hottest[-1]["peak_bytes"]
    assert mem["peaks"]["by_phase"]
    # the tool renders the per-phase peak table from the report file
    mr = _load_tool("memory_report")
    out = mr.render(mr.load_payload(payload))
    assert "phase peaks" in out and "forward" in out
    assert "census" in out and "parameter" in out
    assert hottest[0]["key"][:16] in out


def test_leak_detection_flags_leaked_activations():
    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x, y = nd.zeros((8, 16)), nd.zeros((8,))
    leaked = []
    for _ in range(12):
        telemetry.step_boundary("train")
        with autograd.record():
            L = lossfn(net(x), y).mean()
        L.backward()
        tr.step(8)
        leaked.append(nd.zeros((64, 64)))     # the deliberate leak
        float(L.astype("float32").asnumpy())
    telemetry.end_step()
    mr = _load_tool("memory_report")
    # threshold: a few leaked arrays' worth — the window's first step
    # already carries part of the accumulation, so growth over the
    # window is smaller than 12 full leaks
    rep = mr.leak_report(memory.crash_report_payload(), window=10,
                         min_growth_bytes=3 * 64 * 64 * 4)
    flagged = [r["origin"] for r in rep["origins"] if r["flagged"]]
    assert flagged == ["activation"], rep["origins"][:3]
    assert "LEAK?" in mr.format_leaks(rep)


def test_elastic_run_purges_on_resource(tmp_path):
    from mxnet_tpu import checkpoint

    mgr = checkpoint.CheckpointManager(str(tmp_path / "ckpt"))
    calls = []
    purges_before = engine.engine_stats()["cache_purges"]

    def train_fn(start):
        calls.append(start)
        if len(calls) == 1:
            raise faults.ResourceExhausted(
                "RESOURCE_EXHAUSTED: out of memory")

    restarts = checkpoint.elastic_run(train_fn, mgr, max_restarts=3,
                                      backoff_s=0.0)
    assert restarts == 1 and len(calls) == 2
    # the restart was preceded by a cache purge + gc (docs/RESILIENCE.md)
    assert engine.engine_stats()["cache_purges"] == purges_before + 1
    assert faults.counters()["oom_recoveries"] == 1


def test_release_cached_memory_reports_what_it_freed():
    x = nd.zeros((4, 4))
    (x + 1).asnumpy()                    # populate the op cache
    freed = memory.release_cached_memory()
    assert freed["engine_executables"] is not None
    assert freed["gc_collected"] >= 0
    # training still works after a purge (everything recompiles)
    (x + 2).asnumpy()


# ---------------------------------------------------------------------------
# satellites: remat ordering + keep-in-sync lint
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_remat_temp_bytes_ordering():
    """examples/remat_memory.py through the ledger API: remat trades
    activation residency for recompute, so the remat-on program's temp
    bytes must be strictly below remat-off on the same stack."""
    spec = importlib.util.spec_from_file_location(
        "remat_memory", os.path.join(_REPO, "examples",
                                     "remat_memory.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    off = m.measure(False, layers=2, batch=4, seq=64, units=64, heads=4)
    on = m.measure(True, layers=2, batch=4, seq=64, units=64, heads=4)
    assert on is not None and off is not None
    assert on["temp_bytes"] < off["temp_bytes"], (on["temp_bytes"],
                                                  off["temp_bytes"])
    # both landed in the ledger under their example labels
    labels = {e["label"] for e in memory.ledger()}
    assert "remat_memory:remat=0" in labels \
        and "remat_memory:remat=1" in labels


def test_check_keep_in_sync_lint_clean():
    sys.path.insert(0, _TOOLS)
    try:
        import check_keep_in_sync
        violations = check_keep_in_sync.check(_REPO)
        assert violations == [], "\n".join(violations)
    finally:
        sys.path.remove(_TOOLS)
        sys.modules.pop("check_keep_in_sync", None)


def test_check_keep_in_sync_detects_divergence(tmp_path):
    sys.path.insert(0, _TOOLS)
    try:
        import check_keep_in_sync as lint
        for sub in ("mxnet_tpu", "tools"):
            os.makedirs(tmp_path / sub, exist_ok=True)
        (tmp_path / "mxnet_tpu" / "a.py").write_text(
            "# >>> KEEP-IN-SYNC(blk) note\nx = 1\n"
            "# <<< KEEP-IN-SYNC(blk)\n")
        (tmp_path / "tools" / "b.py").write_text(
            "# >>> KEEP-IN-SYNC(blk) note\nx = 2\n"
            "# <<< KEEP-IN-SYNC(blk)\n"
            "# >>> KEEP-IN-SYNC(orphan)\ny = 1\n"
            "# <<< KEEP-IN-SYNC(orphan)\n"
            "# >>> KEEP-IN-SYNC(unclosed)\n")
        vs = lint.check(str(tmp_path))
        assert any("diverged" in v for v in vs)
        assert any("only one file" in v for v in vs)
        assert any("never closed" in v for v in vs)
        # identical copies pass
        (tmp_path / "tools" / "b.py").write_text(
            "# >>> KEEP-IN-SYNC(blk) note\nx = 1\n"
            "# <<< KEEP-IN-SYNC(blk)\n")
        assert lint.check(str(tmp_path)) == []
    finally:
        sys.path.remove(_TOOLS)
        sys.modules.pop("check_keep_in_sync", None)
