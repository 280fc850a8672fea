"""The graph-rewrite pass layer (``mxnet_tpu.compile.passes``) and its
first paying customer, int8-resident inference (docs/COMPILE_PASSES.md).

Covers, all on CPU:

* CapturedProgram capture/replay parity and the pytree contract;
* the empty-pipeline identity (bit-identical by construction) and the
  ``MXNET_COMPILE_PASSES`` env knob / unknown-name resolution errors;
* the dce pass (bit-exact referee) and the int8_residency pass
  (structure via ``eqn_summary`` — inter-layer dequantize markers gone —
  plus numerics against the unrewritten quantized net);
* the validation referee: a deliberately-broken pass's rewrite is
  DISCARDED (program serves unrewritten) and counted;
* the costs pass ledger and ``compile/passes_*`` telemetry;
* ProgramCache key stability (ISSUE-17 satellite): rewritten vs
  unrewritten twins get distinct keys, stable per pipeline, including
  across pickled ``ReplicaSpec`` warm starts;
* ``tools/cost_report.py``'s ``rewrite_candidates`` section as a fixture
  feeding ``passes.candidate_specs``;
* the serving integration: ``InferenceEngine(compile_passes=...)``
  parity + ``serving/int8_*`` counters, non-block models degrade with a
  warning;
* lint coverage: the new env knob and metric names are seen by
  ``check_env_vars`` / ``check_metric_names`` in both directions.

Heavyweight R50/BERT-geometry drift parities are ``@pytest.mark.slow``
(tier-1 margin rule, ROADMAP).
"""
import os
import pickle
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.compile import passes as P
from mxnet_tpu.contrib import quantization as Q
from mxnet_tpu.gluon import nn

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _quantized_mlp(in_units=16, hidden=32, classes=8, seed=0, calib_b=8):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, in_units=in_units, activation="relu"),
            nn.Dense(hidden, in_units=hidden, activation="relu"),
            nn.Dense(classes, in_units=hidden))
    net.initialize()
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.randn(calib_b, in_units).astype("float32"))
    _ = net(x)
    return net, Q.quantize_net(net, calib_data=[x]), x


def _capture_quantized(qnet, batch=4, in_units=16):
    import jax
    pure_fn, read_params = qnet.inference_fn()
    raws = read_params()
    sds = [jax.ShapeDtypeStruct((batch, in_units), onp.float32)]
    prog = P.CapturedProgram.capture(pure_fn, (raws, *sds), label="t")
    return prog, raws, sds


# ---------------------------------------------------------------------------
# capture / replay
# ---------------------------------------------------------------------------
def test_capture_replay_parity():
    import jax.numpy as jnp

    def f(params, x):
        return (jnp.tanh(x @ params["w"]) + params["b"],)

    rng = onp.random.RandomState(0)
    params = {"w": rng.randn(4, 3).astype("float32"),
              "b": rng.randn(3).astype("float32")}
    x = rng.randn(2, 4).astype("float32")
    prog = P.CapturedProgram.capture(f, (params, x))
    (ref,) = f(params, x)
    (got,) = prog.as_callable()(params, x)
    assert onp.array_equal(onp.asarray(ref), onp.asarray(got))
    est = prog.cost_estimate()
    assert est["flops"] > 0 and est["bytes"] > 0
    assert "dot_general" in prog.eqn_summary()
    # the replay callable enforces the captured pytree structure
    with pytest.raises(MXNetError):
        prog.as_callable()([params["w"], params["b"]], x)


def test_empty_pipeline_is_none_and_env_knob(monkeypatch):
    assert P.resolve_pipeline("") is None
    monkeypatch.delenv("MXNET_COMPILE_PASSES", raising=False)
    assert P.resolve_pipeline(None) is None
    monkeypatch.setenv("MXNET_COMPILE_PASSES", "dce")
    pipe = P.resolve_pipeline(None)
    assert pipe is not None and pipe.spec == "dce"
    # a PassPipeline passes through untouched (per-model override path)
    assert P.resolve_pipeline(pipe) is pipe
    with pytest.raises(MXNetError, match="unknown compile pass"):
        P.resolve_pipeline("dce,no_such_pass")


def test_dce_pass_bit_exact():
    import jax.numpy as jnp

    def f(x):
        dead = jnp.exp(x) * 3.0          # feeds nothing
        dead2 = dead.sum()               # noqa: F841 — transitively dead
        return (jnp.tanh(x).sum(),)

    x = onp.random.RandomState(1).randn(8, 8).astype("float32")
    prog = P.CapturedProgram.capture(f, (x,))
    pipe = P.resolve_pipeline("dce")
    new, reports = pipe.run(prog, example_args=(x,), label="dce:t")
    assert reports[0]["changed"] and reports[0]["validated"]
    assert len(new.closed.jaxpr.eqns) < len(prog.closed.jaxpr.eqns)
    assert onp.array_equal(onp.asarray(f(x)[0]),
                           onp.asarray(new.as_callable()(x)[0]))


# ---------------------------------------------------------------------------
# int8 residency
# ---------------------------------------------------------------------------
def test_int8_residency_structure_and_numerics():
    from mxnet_tpu import costs
    net, qnet, calib = _quantized_mlp()
    prog, raws, sds = _capture_quantized(qnet)
    before = prog.eqn_summary()
    # the PTQ epilogue round-trips through float between every layer
    assert before.count("jit:" + P.DEQUANTIZE_MARKER) == 3
    pipe = P.resolve_pipeline("int8_residency")
    new, reports = pipe.run(prog, example_args=(raws, *sds), label="int8:t")
    assert reports[0]["changed"] and reports[0]["validated"]
    after = new.eqn_summary()
    # inter-layer dequantize markers folded: only the graph output
    # dequantizes, so layer-to-layer activations stay int8-resident
    assert after.count("jit:" + P.DEQUANTIZE_MARKER) == 1
    assert reports[0]["bytes_after"] < reports[0]["bytes_before"]
    # numerics: rewritten program vs the unrewritten quantized forward
    x = onp.random.RandomState(2).randn(4, 16).astype("float32")
    (got,) = new.as_callable()(raws, x)
    want = qnet(nd.array(x)).asnumpy()
    err = onp.max(onp.abs(onp.asarray(got) - want)) \
        / max(onp.max(onp.abs(want)), 1e-9)
    assert err <= 5e-2
    # the run landed in the costs pass ledger
    rows = [r for r in costs.pass_ledger()
            if r["pass"] == "int8_residency" and r["label"] == "int8:t"]
    assert rows and rows[-1]["validated"] \
        and rows[-1]["bytes_after"] < rows[-1]["bytes_before"]


def test_validation_referee_discards_broken_pass():
    import jax.numpy as jnp

    @P.register_pass
    class _BrokenPass(P.GraphPass):
        name = "_test_broken"
        tolerance = 0.0

        def run(self, prog):
            def wrong(*args):
                outs = prog.eval_flat(
                    __import__("jax").tree_util.tree_flatten(args)[0])
                return tuple(o + 1.0 for o in outs)
            return P.CapturedProgram.capture(
                wrong, tuple(prog.closed.in_avals), label=prog.label)

    try:
        def f(x):
            return (jnp.tanh(x),)

        x = onp.random.RandomState(0).randn(4).astype("float32")
        prog = P.CapturedProgram.capture(f, (x,))
        P.reset_stats()
        new, reports = pipe_run = P.resolve_pipeline("_test_broken").run(
            prog, example_args=(x,), label="broken:t")
        assert reports[0]["changed"] and reports[0]["validated"] is False
        # rewrite discarded: the returned program IS the original
        assert new is prog
        assert P.telemetry_stats()["compile/passes_validation_failures"] == 1
        assert P.telemetry_stats()["compile/passes_rewrites"] == 0
    finally:
        P._REGISTRY.pop("_test_broken", None)


def test_pass_errors_are_swallowed():
    import jax.numpy as jnp

    @P.register_pass
    class _RaisingPass(P.GraphPass):
        name = "_test_raises"

        def run(self, prog):
            raise RuntimeError("boom")

    try:
        def f(x):
            return (jnp.tanh(x),)

        x = onp.zeros(3, onp.float32)
        prog = P.CapturedProgram.capture(f, (x,))
        P.reset_stats()
        new, reports = P.resolve_pipeline("_test_raises").run(
            prog, example_args=(x,))
        assert new is prog and "error" in reports[0]
        assert P.telemetry_stats()["compile/passes_errors"] == 1
    finally:
        P._REGISTRY.pop("_test_raises", None)


# ---------------------------------------------------------------------------
# cache-key stability (satellite: no stale hits across pipeline changes)
# ---------------------------------------------------------------------------
def test_fingerprints_distinct_and_stable():
    fp = {s: P.resolve_pipeline(s).fingerprint()
          for s in ("dce", "int8_residency", "dce,int8_residency")}
    assert len(set(fp.values())) == 3
    for s, f in fp.items():
        assert f.startswith("passes:")
        assert P.resolve_pipeline(s).fingerprint() == f    # deterministic
    # a version bump (behavioural change) must miss stale programs
    old = P.DCEPass.version
    try:
        P.DCEPass.version = old + 1
        assert P.resolve_pipeline("dce").fingerprint() != fp["dce"]
    finally:
        P.DCEPass.version = old


def test_program_cache_key_distinct_with_passes(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import compile as mxcompile

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def f(x):
        return jnp.tanh(x @ x.T).sum()

    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    fp = P.resolve_pipeline("int8_residency").fingerprint()
    _c0, plain = mxcompile.aot_compile_lowered(lowered, label="kt")
    _c1, branded = mxcompile.aot_compile_lowered(lowered, label="kt",
                                                 extra_key=fp)
    # same StableHLO, different pipeline => different ProgramCache key —
    # toggling MXNET_COMPILE_PASSES can never warm-load the other mode
    assert plain["key"] != branded["key"]
    assert not branded["cache_hit"]
    _c2, again = mxcompile.aot_compile_lowered(lowered, label="kt",
                                               extra_key=fp)
    assert again["cache_hit"] and again["key"] == branded["key"]
    _c3, other = mxcompile.aot_compile_lowered(
        lowered, label="kt", extra_key=P.resolve_pipeline("dce")
        .fingerprint())
    assert other["key"] not in (plain["key"], branded["key"])


def test_replica_spec_pickle_carries_compile_passes():
    from mxnet_tpu.serving.fleet import ReplicaSpec

    spec = ReplicaSpec(_quantized_mlp, batch_buckets=(1, 2),
                       compile_passes="int8_residency")
    clone = pickle.loads(pickle.dumps(spec))
    assert clone.compile_passes == "int8_residency"
    # pre-pass-layer pickles (no attribute) warm-start unrewritten: the
    # worker reads the field with getattr(..., None)
    state = pickle.loads(pickle.dumps(spec)).__dict__
    state.pop("compile_passes")
    old = ReplicaSpec.__new__(ReplicaSpec)
    old.__dict__.update(state)
    assert getattr(old, "compile_passes", None) is None


# ---------------------------------------------------------------------------
# cost_report rewrite_candidates (satellite: fixture contract)
# ---------------------------------------------------------------------------
def _cost_report():
    sys.path.insert(0, _TOOLS)
    try:
        import cost_report
    finally:
        sys.path.remove(_TOOLS)
    return cost_report


def test_rewrite_candidates_schema_and_candidate_specs():
    cr = _cost_report()
    payload = {
        "peak": {"flops": 100e12, "bytes_per_s": 1e12, "source": "t"},
        "ledger": {"programs": 3, "upgrades": 0, "hottest": [
            {"key": "aaa1", "kind": "block", "label": "serve:b16",
             "flops": 1e9, "bytes_accessed": 1e9},      # 1 fl/B: byte-bound
            {"key": "bbb2", "kind": "step", "label": "train",
             "flops": 4e12, "bytes_accessed": 1e9},     # compute-bound
            {"key": "ccc3", "kind": "step", "label": "glue",
             "flops": 2e9, "bytes_accessed": 1e9},      # byte-bound
        ]},
    }
    rc = cr.rewrite_candidates(payload)
    assert rc["schema"] == 1 and rc["ridge_flops_per_byte"] == 100.0
    keys = [c["key"] for c in rc["candidates"]]
    assert keys == ["aaa1", "ccc3"]          # compute-bound excluded
    by_key = {c["key"]: c for c in rc["candidates"]}
    assert by_key["aaa1"]["suggested_passes"] == ["dce", "int8_residency"]
    assert by_key["ccc3"]["suggested_passes"] == ["dce"]
    for c in rc["candidates"]:
        assert c["verdict"] == "byte-bound"
    # the fixture feeds the pass layer: unknown suggestions filtered out
    rows = rc["candidates"] + [{"key": "ddd4",
                                "suggested_passes": ["not_a_pass"]}]
    specs = P.candidate_specs(rows)
    assert specs == {"aaa1": "dce,int8_residency", "ccc3": "dce"}
    for s in specs.values():
        assert P.resolve_pipeline(s) is not None
    # the rendered report and --json payload both carry the section
    assert "rewrite candidates" in cr.render(payload)
    assert "dce,int8_residency" in cr.format_rewrite_candidates(rc)


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------
def test_engine_int8_serving_mode_parity_and_counters():
    from mxnet_tpu.serving import InferenceEngine

    net, qnet, calib = _quantized_mlp()
    e8 = InferenceEngine(qnet, batch_buckets=(1, 2, 4),
                         compile_passes="int8_residency")
    e0 = InferenceEngine(qnet, batch_buckets=(1, 2, 4))
    x = onp.random.RandomState(3).randn(4, 16).astype("float32")
    (got8,) = e8.run_batch([x])
    (got0,) = e0.run_batch([x])
    want = qnet(nd.array(x)).asnumpy()
    assert onp.max(onp.abs(got0 - want)) == 0.0   # no pipeline: identity
    err = onp.max(onp.abs(got8 - want)) / max(onp.max(onp.abs(want)), 1e-9)
    assert err <= 5e-2
    info = e8.compile_passes_info()
    assert info["spec"] == "int8_residency" and info["int8_resident"]
    assert any(r["changed"] and r["validated"]
               for reps in info["programs"].values() for r in reps)
    c8 = e8.metrics.stats()["counters"]
    assert c8["int8_batches"] == 1 and c8["int8_requests"] == 4
    c0 = e0.metrics.stats()["counters"]
    assert c0["int8_batches"] == 0
    assert e0.compile_passes_info()["fingerprint"] is None


def test_engine_non_block_model_degrades_with_warning():
    from mxnet_tpu.serving import InferenceEngine

    def fn(x):
        return x * 2.0

    with pytest.warns(UserWarning, match="compile_passes"):
        eng = InferenceEngine(fn, batch_buckets=(1, 2),
                              compile_passes="dce")
    (out,) = eng.run_batch([onp.ones((2, 3), onp.float32)])
    assert onp.array_equal(out, onp.full((2, 3), 2.0, onp.float32))
    assert eng.compile_passes_info()["fingerprint"] is None


def test_generation_engine_prefill_pipeline(tmp_path, monkeypatch):
    from mxnet_tpu.models.lm import tiny_lm
    from mxnet_tpu.serving.generate import GenerationEngine

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mx.random.seed(7)
    net = tiny_lm(vocab_size=32, num_layers=1, units=16, hidden_size=32,
                  num_heads=2, max_length=64)
    net.initialize()
    net(nd.array(onp.zeros((1, 4), onp.int32)),
        nd.array(onp.asarray([4], onp.int32)))

    eng = GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,),
                           compile_passes="dce")
    toks = list(eng.submit([3, 5, 7], max_new_tokens=4))
    eng.stop()
    eng2 = GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,))
    toks2 = list(eng2.submit([3, 5, 7], max_new_tokens=4))
    eng2.stop()
    assert toks == toks2 and len(toks) == 4
    info = eng.compile_passes_info()
    assert info["spec"] == "dce" and "passes:generate:prefill:L8" \
        in info["programs"]


# ---------------------------------------------------------------------------
# lint coverage (satellite: the checkers see the new surface)
# ---------------------------------------------------------------------------
def test_lints_cover_new_knob_and_metrics():
    sys.path.insert(0, _TOOLS)
    try:
        import check_env_vars
        import check_metric_names
    finally:
        sys.path.remove(_TOOLS)
    root = os.path.dirname(_TOOLS)
    reads = check_env_vars.find_reads(root)
    assert "MXNET_COMPILE_PASSES" in reads
    exact, globs = check_env_vars.documented_vars(root)
    assert "MXNET_COMPILE_PASSES" in exact or any(
        "MXNET_COMPILE_PASSES".startswith(g) for g in globs)
    regs = check_metric_names.find_registrations(root)
    names = {r[0] for r in regs}
    for m in ("compile/passes_runs", "compile/passes_rewrites",
              "compile/passes_unchanged",
              "compile/passes_validation_failures",
              "compile/passes_errors", "compile/passes_bytes_saved",
              "serving/int8_batches", "serving/int8_requests"):
        assert m in names, m
    documented = check_metric_names.documented_names(root)
    for m in ("compile/passes_runs", "serving/int8_batches",
              "serving/int8_requests"):
        assert m in documented, m
    assert check_env_vars.check(root) == []
    assert check_metric_names.check(root) == []


# ---------------------------------------------------------------------------
# heavyweight drift parities (slow: tier-1 margin rule)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_int8_residency_drift_r50_eval_path():
    """R50 eval path: PTQ + int8_residency through the serving engine
    stays within the 0.5% top-1 drift ceiling vs the fp32 net."""
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    from mxnet_tpu.serving import InferenceEngine

    mx.random.seed(0)
    net = resnet18_v1(classes=10)
    net.initialize()
    rng = onp.random.RandomState(0)
    calib = nd.array(rng.randn(8, 3, 32, 32).astype("float32"))
    _ = net(calib)
    qnet = Q.quantize_net(net, calib_data=[calib])
    eng = InferenceEngine(qnet, batch_buckets=(8,),
                          compile_passes="int8_residency")
    xe = rng.randn(32, 3, 32, 32).astype("float32")
    ref = net(nd.array(xe)).asnumpy()
    got = onp.concatenate([eng.run_batch([xe[i:i + 8]])[0]
                           for i in range(0, 32, 8)])
    drift = 100.0 * float((got.argmax(1) != ref.argmax(1)).mean())
    assert drift <= 0.5
    # the pipeline actually ran and every adopted rewrite validated
    info = eng.compile_passes_info()
    assert info["programs"]
    for reps in info["programs"].values():
        for r in reps:
            assert r["validated"] is not False


@pytest.mark.slow
def test_int8_residency_drift_bert_ffn_eval_path():
    """BERT-base FFN geometry (768 -> 3072): top-1 drift vs fp32 within the 0.5% ceiling and the
    inter-layer fold actually engaged."""
    from mxnet_tpu.serving import InferenceEngine

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(3072, in_units=768, activation="relu"),
            nn.Dense(768, in_units=3072, activation="relu"),
            nn.Dense(10, in_units=768))
    net.initialize()
    rng = onp.random.RandomState(0)
    calib = nd.array(rng.randn(32, 768).astype("float32"))
    _ = net(calib)
    qnet = Q.quantize_net(net, calib_data=[calib])
    eng = InferenceEngine(qnet, batch_buckets=(16,),
                          compile_passes="int8_residency")
    xe = rng.randn(128, 768).astype("float32")
    ref = net(nd.array(xe)).asnumpy()
    got = onp.concatenate([eng.run_batch([xe[i:i + 16]])[0]
                           for i in range(0, 128, 16)])
    drift = 100.0 * float((got.argmax(1) != ref.argmax(1)).mean())
    assert drift <= 0.5
    info = eng.compile_passes_info()
    assert any(r["changed"] and r["validated"]
               for reps in info["programs"].values() for r in reps)
