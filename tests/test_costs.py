"""mxnet_tpu.costs: per-program cost ledger across all three capture
sites (fresh compile / AOT / warm load, warm flagged + upgraded), MFU
accounting on step_flush and serving execute spans, block-level
attribution of captured segments (sum-vs-cost_analysis referee, VJP
CSE correction, block scopes), the ledger-vs-analytic MFU referee on
Dense/Conv, crash-report schema v4 and tools/cost_report.py
(docs/OBSERVABILITY.md "Compute-cost observability")."""
import importlib.util
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, costs, engine, faults, memory, nd, telemetry
from mxnet_tpu.gluon import Trainer, loss as gloss, nn
from mxnet_tpu.base import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture(autouse=True)
def _clean():
    costs.reset()
    memory.reset()
    telemetry.enable(None)
    engine.set_engine_type("ThreadedEngine")
    yield
    costs.reset()
    memory.reset()
    telemetry.enable(None)
    engine.set_engine_type("ThreadedEngine")
    # precompile() wires jax's persistent compilation cache; detach it or
    # executables serialized later in the suite fail to re-load ("Symbols
    # not found") and poison warm-start tests — the same cleanup
    # test_compile_cache.py's fixture does
    from mxnet_tpu import compile as mxcompile
    mxcompile.disable_persistent_cache()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _compiled_tanh_matmul(m=8, k=16, n=32):
    import jax
    import jax.numpy as jnp

    def f(x, w):
        return jnp.tanh(x @ w)

    sds = (jax.ShapeDtypeStruct((m, k), jnp.float32),
           jax.ShapeDtypeStruct((k, n), jnp.float32))
    return jax.jit(f).lower(*sds).compile(), (m, k, n)


# ---------------------------------------------------------------------------
# ledger basics + capture sites
# ---------------------------------------------------------------------------
def test_record_program_matches_xla_cost_model():
    compiled, (m, k, n) = _compiled_tanh_matmul()
    e = costs.record_program(compiled, key="k" * 64, label="t", kind="op")
    assert e["flops"] == 2 * m * k * n          # the dot, 2xMACs
    assert e["transcendentals"] == m * n        # the tanh
    assert e["bytes_accessed"] > 0
    assert e["analysis"] == "fresh"
    assert costs.ledger_flops("k" * 64) == e["flops"]
    # pc:<key12> label resolution (the serving execute-span handle)
    assert costs.ledger_flops("pc:" + "k" * 12) == e["flops"]
    assert costs.ledger_entry("k" * 12)["key"] == "k" * 64


def test_warm_entry_flagged_and_upgraded_with_metric():
    compiled, _dims = _compiled_tanh_matmul()
    key = "w" * 64
    e = costs.record_program(compiled, key=key, warm=True)
    assert e["analysis"] == "warm"
    snap0 = telemetry.snapshot()["counters"]["costs/ledger_upgrades"]
    e2 = costs.record_program(compiled, key=key)   # fresh compile lands
    assert e2["analysis"] == "fresh" and e2["compiles"] == 2
    assert costs.ledger_upgrades() == 1
    assert telemetry.snapshot()["counters"]["costs/ledger_upgrades"] \
        == snap0 + 1
    # a warm re-load never downgrades a fresh entry
    e3 = costs.record_program(compiled, key=key, warm=True)
    assert e3["analysis"] == "fresh"
    assert costs.ledger_upgrades() == 1


def test_memory_ledger_upgrade_counted():
    """Satellite: the memory ledger's warm->fresh upgrade is explicit and
    counted by memory/ledger_upgrades."""
    compiled, _dims = _compiled_tanh_matmul()
    key = "m" * 64
    e = memory.record_program(compiled, key=key, warm=True)
    assert e["analysis"] == "warm"
    assert memory.ledger_upgrades() == 0
    e2 = memory.record_program(compiled, key=key)
    assert e2["analysis"] == "fresh"
    assert memory.ledger_upgrades() == 1
    assert telemetry.snapshot()["counters"]["memory/ledger_upgrades"] == 1


def test_ledger_captures_all_three_sites(tmp_path, monkeypatch):
    """fresh compile / warm load (deserialized, flagged) / AOT — keyed by
    the same ProgramCache keys as the memory ledger."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import compile as mxcompile

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def f(x):
        return jnp.tanh(x @ x.T).sum()

    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    # AOT site (fresh): aot_compile_lowered records under the fingerprint
    _compiled, info = mxcompile.aot_compile_lowered(lowered, label="t3")
    assert not info["cache_hit"]
    e = costs.ledger_entry(info["key"])
    assert e and e["analysis"] == "fresh" and e["flops"] > 0
    fresh_flops = e["flops"]
    # warm-load site: second AOT of the same program deserializes
    costs.reset()
    _compiled2, info2 = mxcompile.aot_compile_lowered(lowered, label="t3")
    assert info2["cache_hit"] and info2["key"] == info["key"]
    e2 = costs.ledger_entry(info2["key"])
    assert e2 and e2["analysis"] == "warm"
    # the warm cost_analysis quirk referee: where the backend DOES return
    # an analysis for a loaded executable it matches the fresh one (the
    # flag is the caveat, the numbers are still usable on this backend)
    assert e2["flops"] == pytest.approx(fresh_flops, rel=0.01)


def test_segment_compile_site_and_flush_span_mfu(tmp_path, monkeypatch):
    """The engine's segment-compile site: a fused lazy segment lands in
    the cost ledger under its ProgramCache key, the step_flush/lazy_flush
    span carries flops= and mfu=, and executions are accounted."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e11")  # the CPU has no peak
    engine.reset_op_cache()
    costs.reset()
    telemetry.reset()
    x = nd.zeros((64, 64))
    for _ in range(2):          # second flush is the cache HIT (see below)
        with engine.bulk(32):
            y = x
            for _ in range(4):
                y = (y @ x) + 1.0
        y.wait_to_read()
    entries = [e for e in costs.ledger() if e["kind"] == "lazy_segment"]
    assert entries and entries[-1]["flops"] >= 4 * 2 * 64 ** 3
    spans = [s for s in telemetry.flight_recorder()
             if s["phase"] == "lazy_flush"]
    assert len(spans) >= 2
    # the cache-MISS flush paid the compile inside its wall: flops only
    miss_args = spans[0].get("args") or {}
    assert miss_args.get("flops") == int(entries[-1]["flops"])
    assert "mfu" not in miss_args
    # the cache-HIT flush is a pure execution: flops + mfu + accounting
    args = spans[-1].get("args") or {}
    assert args.get("flops") == int(entries[-1]["flops"])
    assert args.get("mfu", 0) > 0       # peak resolves: env override
    assert costs.last_execution()["key"] == entries[-1]["key"]
    snap = telemetry.snapshot()
    assert snap["counters"]["costs/executions"] >= 1
    assert snap["counters"]["costs/flops_executed_total"] >= \
        entries[-1]["flops"]
    assert "mxnet_costs_ledger_programs" in telemetry.prometheus_text()


# ---------------------------------------------------------------------------
# block attribution
# ---------------------------------------------------------------------------
def _captured_steps(layers=4, units=128, batch=16, steps=2):
    mx.random.seed(0)
    engine.set_engine_type("LazyEngine")
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(nn.Dense(units, activation="relu"))
    net.add(nn.Dense(8))
    net.initialize()
    L = gloss.SoftmaxCrossEntropyLoss()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.01, "momentum": 0.9})
    x = nd.array(onp.random.RandomState(0).randn(batch, units)
                 .astype("float32"))
    y = nd.zeros((batch,))
    last = None
    for _ in range(steps):
        with autograd.record():
            last = L(net(x), y).mean()
        last.backward()
        tr.step(batch)
    float(last.astype("float32").asnumpy())
    return net


def test_block_attribution_sums_to_program_total(tmp_path, monkeypatch):
    """Acceptance referee: per-block flops of the ONE captured step sum
    to within 10% of the program's own cost_analysis() total, and every
    dense layer is attributed to its own block path (forward + backward
    folded together via the VJP CSE correction)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    engine.reset_op_cache()
    costs.reset()
    _captured_steps(layers=4, units=128, batch=16)
    tables = [t for t in costs.attributions()
              if t["kind"] == "step_segment"]
    assert tables, "captured step produced no attribution table"
    t = max(tables, key=lambda t: t["attributed_flops"])
    assert t["total_flops"] and t["total_flops"] > 0
    assert t["coverage"] == pytest.approx(1.0, abs=0.10)
    blocks = {b["block"]: b for b in t["blocks"]}
    dense_blocks = [b for b in blocks if "/dense" in b]
    assert len(dense_blocks) == 5
    # the four hidden layers dominate and carry fwd + bwd ops
    hidden = sorted(blocks.items(), key=lambda kv: -kv[1]["flops"])[0]
    assert "/dense" in hidden[0] and hidden[1]["ops"] >= 3
    # the trainer's fused update attributes to its op, outside any block
    assert any(b.startswith("(trainer") for b in blocks)
    rows = t["rows"]
    assert any(r["direction"] == "backward" and "/dense" in r["block"]
               for r in rows)
    # attribution is retrievable by the program key the span names
    assert costs.attribution(t["key"])["key"] == t["key"]


def test_block_scope_helpers_and_tags():
    assert engine.current_block() is None
    engine.push_block("a0")
    engine.push_block("b1")
    assert engine.current_block() == "a0/b1"
    engine.pop_block()
    with engine.block_scope("saved/path"):
        assert engine.current_block() == "saved/path"
    assert engine.current_block() == "a0"
    engine.pop_block()
    assert engine.current_block() is None
    # per-instance tags are stable and unique per class
    a, b = nn.Dense(4), nn.Dense(4)
    ta, tb = a._cost_tag(), b._cost_tag()
    assert ta != tb and ta.startswith("dense") and ta == a._cost_tag()


def test_attribution_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_COST_ATTRIBUTION", "0")
    engine.reset_op_cache()
    costs.reset()
    _captured_steps(layers=2, units=16, batch=4)
    assert costs.attributions() == []
    # the ledger itself still captured (attribution is gated separately)
    assert any(e["kind"] == "step_segment" for e in costs.ledger())


# ---------------------------------------------------------------------------
# MFU referee: ledger flops vs analytic 2xMACs
# ---------------------------------------------------------------------------
def test_mfu_referee_dense_ledger_vs_analytic(tmp_path, monkeypatch):
    """MFU referee: the fused SPMD step's cost_analysis()
    flops agree with the analytic 2xMACs convention within 10% on a
    dense stack (fwd + dgrad + wgrad = 3x forward)."""
    import jax
    from mxnet_tpu import parallel
    from mxnet_tpu import optimizer as opt

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    B, U, LAYERS = 32, 256, 4
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(LAYERS):
        net.add(nn.Dense(U, activation="relu"))
    net.initialize()
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    L = gloss.SoftmaxCrossEntropyLoss()
    trainer = parallel.SPMDTrainer(
        net, lambda out, y: L(out, y).mean(),
        opt.SGD(learning_rate=0.01), mesh)
    x = nd.array(onp.random.RandomState(0).randn(B, U).astype("float32"))
    y = nd.zeros((B,))
    info = trainer.precompile(x, y)
    assert info["key"] and info["flops"]
    analytic = LAYERS * 3 * 2 * B * U * U
    assert info["flops"] == pytest.approx(analytic, rel=0.10)
    assert costs.ledger_entry(info["key"])["kind"] == "spmd_step"


def test_mfu_referee_conv_ledger_vs_analytic():
    """Conv referee: cost_analysis flops vs analytic 2xMACs within 10%
    on a conv fwd+bwd program (and the jaxpr estimator agrees too)."""
    import jax
    import jax.numpy as jnp

    B, CIN, COUT, H, W, KH = 4, 8, 16, 16, 16, 3

    def loss(x, w):
        out = jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return (out * out).sum()

    def train(x, w):
        return jax.grad(loss, argnums=(0, 1))(x, w)

    sds = (jax.ShapeDtypeStruct((B, CIN, H, W), jnp.float32),
           jax.ShapeDtypeStruct((COUT, CIN, KH, KH), jnp.float32))
    compiled = jax.jit(train).lower(*sds).compile()
    e = costs.record_program(compiled, key="c" * 64, kind="bench")
    ho = wo = H - KH + 1
    fwd = 2 * B * COUT * ho * wo * CIN * KH * KH
    # fwd (recomputed inside grad) + dgrad + wgrad ~= 3x forward MACs
    assert e["flops"] == pytest.approx(3 * fwd, rel=0.10)
    # the jaxpr estimator counts every output x kernel tap, including the
    # padding-region taps of the full-padded dgrad conv that XLA's cost
    # model excludes — a bounded over-count ((16/14)^2 on this shape), so
    # the estimator referee gets a slightly wider band than the ledger
    est, _tr = costs.estimate_fun_cost(train, {}, sds)
    assert est == pytest.approx(e["flops"], rel=0.15)


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "123e12")
    costs.reset()
    assert costs.peak_flops() == 123e12
    assert "env" in costs.peak_info()["source"]
    compiled, (m, k, n) = _compiled_tanh_matmul()
    costs.record_program(compiled, key="p" * 64)
    out = costs.record_execution("p" * 64, 1000.0)
    expect = (2 * m * k * n) / 1e-3 / 123e12
    assert out["mfu"] == pytest.approx(expect, abs=1e-4)


def test_cpu_has_no_peak_and_unknown_kind_is_an_error(monkeypatch):
    """One table keyed by device_kind: the CPU has no utilization (no
    made-up peak keeps MFU 'finite'), and an accelerator kind that is not
    in the table is an error, not v5e's figures."""
    import jax
    costs.reset()
    jax.devices()                       # backend live: the peak can resolve
    assert costs.peak_info() is None and costs.peak_flops() is None
    compiled, _ = _compiled_tanh_matmul()
    costs.record_program(compiled, key="q" * 64)
    assert "mfu" not in costs.record_execution("q" * 64, 1000.0)
    assert costs.PEAKS["TPU v5 lite"] == (197e12, 819e9)

    class FakeDev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [FakeDev()])
    costs.reset()
    with pytest.raises(MXNetError, match="TPU v99"):
        costs.peak_flops()
    FakeDev.device_kind = "TPU v5 lite"
    assert costs.peak_info() == {"flops": 197e12, "bytes_per_s": 819e9,
                                 "source": "table:TPU v5 lite"}
    costs.reset()


# ---------------------------------------------------------------------------
# serving execute span
# ---------------------------------------------------------------------------
def test_serving_execute_span_carries_flops_and_mfu(tmp_path, monkeypatch):
    from mxnet_tpu import serving

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e11")  # the CPU has no peak
    telemetry.reset()
    costs.reset()
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"))
    net.add(nn.Dense(3, in_units=16))
    net.initialize()
    eng = serving.InferenceEngine(net, batch_buckets=(4,))
    eng.precompile(onp.zeros(8, dtype="float32"))
    xs = onp.random.RandomState(0).randn(3, 8).astype("float32")
    eng.run_batch([xs])
    spans = [s for s in telemetry.flight_recorder()
             if s["phase"] == "execute"]
    assert spans
    args = spans[-1].get("args") or {}
    assert args.get("flops", 0) > 0
    # mfu is present (a toy program's figure rounds to 0.0 at 4 decimals)
    assert "mfu" in args and args["mfu"] >= 0
    # the execution was accounted against the precompiled pc:* entry
    last = costs.last_execution()
    assert last is not None and last["flops"] == args["flops"]


# ---------------------------------------------------------------------------
# crash report schema v4 + cost_report tool
# ---------------------------------------------------------------------------
def test_crash_report_costs_section_and_cost_report_render(tmp_path,
                                                           monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # the roofline needs a peak and the CPU has none: supply one
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e11")
    monkeypatch.setenv("MXNET_PEAK_BYTES_PER_S", "50e9")
    engine.reset_op_cache()
    costs.reset()
    _captured_steps(layers=2, units=32, batch=4)
    payload = faults.crash_report_payload()
    assert payload["schema"] == 7
    sec = payload["costs"]
    assert sec["schema"] == 1 and sec["enabled"]
    assert sec["ledger"]["programs"] >= 1
    assert sec["ledger"]["hottest"][0]["flops"] > 0
    assert sec["executions"]["count"] >= 1
    assert sec["executions"]["last"]["key"]
    # the stdlib-only renderer folds both the crash section and the full
    # report_payload (with attribution tables) into tables
    cr = _load_tool("cost_report")
    text = cr.render(cr.load_payload(payload))
    assert "== programs ==" in text and "== roofline ==" in text
    full = costs.report_payload()
    path = tmp_path / "costs.json"
    path.write_text(json.dumps(full))
    loaded = cr.load_payload(json.loads(path.read_text()))
    text = cr.render(loaded)
    assert "step_segment" in text
    assert "/dense" in text             # the per-block table rendered
    assert "bound" in text              # roofline verdict printed
    rep = cr.roofline(loaded)
    assert rep["programs"] and rep["programs"][0]["verdict"] in (
        "compute-bound", "byte-bound")


def test_costs_disabled_env(monkeypatch):
    monkeypatch.setenv("MXNET_COSTS", "0")
    costs.reset()
    compiled, _dims = _compiled_tanh_matmul()
    assert costs.record_program(compiled, key="d" * 64) is None
    assert costs.ledger() == []
    assert costs.record_execution("d" * 64, 100.0) is None
    payload = costs.crash_report_payload()
    assert payload["enabled"] is False


# ---------------------------------------------------------------------------
# trace_report mfu columns
# ---------------------------------------------------------------------------
def test_trace_report_mfu_columns():
    tr = _load_tool("trace_report")
    # one 10 ms step whose flush span (2 ms) carried mfu=0.5: the
    # per-step figure rescales to the step wall -> 0.1
    spans = [
        {"step": 1, "phase": "step", "ts_us": 0, "dur_us": 10000,
         "tid": 1, "args": {}},
        {"step": 1, "phase": "step_flush", "ts_us": 100, "dur_us": 2000,
         "tid": 1, "args": {"flops": 1000000, "mfu": 0.5,
                            "bytes": 1 << 20}},
    ]
    rep = tr.fold(spans)
    s = rep["steps"][0]
    assert s["flops"] == 1000000
    assert s["mfu"] == pytest.approx(0.1, abs=1e-6)
    assert rep["aggregate"]["mean_mfu"] == pytest.approx(0.1, abs=1e-6)
    assert rep["aggregate"]["max_flops"] == 1000000
    table = tr.format_table(rep)
    assert "mfu" in table and "gflops" in table


# ---------------------------------------------------------------------------
# lint: metric names
# ---------------------------------------------------------------------------
def test_check_metric_names_requires_costs_family():
    cm = _load_tool("check_metric_names")
    assert "costs" in cm._REQUIRED_SUBSYSTEMS
    assert cm.check() == []
