"""mxnet_tpu.compile: persistent cache wiring, program-artifact index
robustness (corruption / eviction / version skew), AOT entry points
(HybridBlock.aot_compile, SPMDTrainer.precompile, InferenceEngine
precompile), and the multi-bucket StableHLO warmup manifest.

Runs entirely on the CPU backend (conftest pins JAX_PLATFORMS=cpu).
"""
import json
import os
import warnings

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving, stablehlo
from mxnet_tpu import compile as mxcompile
from mxnet_tpu.compile.cache import ProgramCache
from mxnet_tpu.gluon import nn


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Point the whole compile subsystem at a throwaway root."""
    d = str(tmp_path / "ccache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "1")
    yield d
    mxcompile.disable_persistent_cache()


def _mlp(seed=0, in_units=8):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=in_units, activation="relu"))
    net.add(nn.Dense(4, in_units=16))
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# ProgramCache robustness
# ---------------------------------------------------------------------------
def test_program_cache_roundtrip(tmp_path):
    pc = ProgramCache(str(tmp_path / "pc"))
    assert pc.get("k") is None
    assert pc.put("k", b"payload", meta={"label": "x"})
    assert pc.get("k") == b"payload"
    (e,) = pc.entries()
    assert e["key"] == "k" and e["bytes"] == 7
    assert e["meta"]["label"] == "x"
    # the persisted hit counter is coarse (touch skipped <60s); the
    # in-memory stats always count
    assert pc.stats["hits"] == 1


def test_program_cache_corrupt_blob_set_aside(tmp_path):
    pc = ProgramCache(str(tmp_path / "pc"))
    pc.put("k", b"0123456789")
    blob_path = os.path.join(pc.root, "k.bin")
    with open(blob_path, "wb") as f:
        f.write(b"0123")            # truncated on-disk entry
    assert pc.get("k") is None      # set-aside, not a crash
    assert os.path.exists(blob_path + ".corrupt")
    assert not os.path.exists(blob_path)
    assert pc.stats["corrupt"] == 1
    # the index entry is dropped too: a clean re-put works
    assert pc.put("k", b"fresh") and pc.get("k") == b"fresh"


def test_program_cache_corrupt_index_set_aside(tmp_path):
    pc = ProgramCache(str(tmp_path / "pc"))
    pc.put("k", b"payload")
    idx = os.path.join(pc.root, "index.json")
    with open(idx, "w") as f:
        f.write('{"format": 1, "entr')      # killed mid-write
    assert pc.get("k") is None              # index rebuilt empty
    assert os.path.exists(idx + ".corrupt")
    assert pc.put("k2", b"x") and pc.get("k2") == b"x"


def test_program_cache_size_cap_evicts_lru(tmp_path):
    pc = ProgramCache(str(tmp_path / "pc"), max_bytes=250)
    pc.put("a", b"x" * 100)
    pc.put("b", b"y" * 100)
    # age the records directly (the hit-path LRU touch is coarse — it only
    # persists when the entry is >60s stale): a recently used, b old
    idx_path = os.path.join(pc.root, "index.json")
    with open(idx_path) as f:
        idx = json.load(f)
    for e in idx["entries"]:
        e["last_used"] = 1e9 if e["key"] == "b" else 3e9
    with open(idx_path, "w") as f:
        json.dump(idx, f)
    pc.put("c", b"z" * 100)          # 300 bytes > 250: evict the LRU (b)
    keys = {e["key"] for e in pc.entries()}
    assert keys == {"a", "c"}
    assert pc.get("b") is None
    assert not os.path.exists(os.path.join(pc.root, "b.bin"))
    assert pc.stats["evictions"] == 1


def test_program_cache_version_mismatch_ignored(tmp_path):
    pc = ProgramCache(str(tmp_path / "pc"))
    pc.put("k", b"payload")
    idx_path = os.path.join(pc.root, "index.json")
    with open(idx_path) as f:
        idx = json.load(f)
    idx["entries"][0]["versions"]["jax"] = "0.0.older"
    with open(idx_path, "w") as f:
        json.dump(idx, f)
    assert pc.get("k") is None          # never deserialized
    assert pc.stats["version_skips"] == 1
    # blob untouched on disk (it ages out via LRU, not via set-aside)
    assert os.path.exists(os.path.join(pc.root, "k.bin"))


def test_cache_init_never_touches_backend(cache_dir, monkeypatch):
    """Cache setup must be pure config/filesystem work: no device
    contact."""
    import jax

    def boom(*a, **k):
        raise AssertionError("cache init touched the backend")

    monkeypatch.setattr(jax, "devices", boom)
    monkeypatch.setattr(jax, "local_devices", boom, raising=False)
    d = mxcompile.enable_persistent_cache()
    assert d == cache_dir and os.path.isdir(d)
    pc = mxcompile.default_program_cache()
    assert pc is not None and os.path.isdir(pc.root)
    info = mxcompile.cache_info()
    assert info["persistent_cache"]["enabled"]
    mxcompile.disable_persistent_cache()
    assert not jax.config.jax_enable_compilation_cache


_PLACED_SCRIPT = r"""
import json, os
import jax
calls = []
real_update = jax.config.update
def spy(name, value):
    calls.append(name)
    real_update(name, value)
jax.config.update = spy
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, compile as mxc
from mxnet_tpu import optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.models.lm import tiny_lm
from mxnet_tpu.serving.generate import GenerationEngine

net = nn.Dense(4, in_units=8)
net.initialize()
trainer = parallel.SPMDTrainer(
    net, lambda out, y: ((out - y) ** 2).mean(),
    opt.create("sgd", learning_rate=0.1),
    parallel.make_mesh({"data": 1}, devices=jax.devices()[:1]))
x = nd.array(onp.ones((2, 8), "float32"))
y = nd.array(onp.ones((2, 4), "float32"))
loss = float(trainer.step(x, y).asnumpy())

lm = tiny_lm(vocab_size=32, num_layers=1, units=16, hidden_size=32,
             num_heads=2, max_length=32)
lm.initialize()
lm(nd.array(onp.zeros((1, 4), onp.int32)),
   nd.array(onp.asarray([4], onp.int32)))
eng = GenerationEngine(lm, slots=2, max_len=16, prefill_buckets=(8,))
toks = eng.generate([3, 5, 7], max_new_tokens=3, timeout=120)["tokens"]
eng.stop()
print(json.dumps({
    "calls": calls, "dir": jax.config.jax_compilation_cache_dir,
    "root": mxc.cache_root(), "programs": mxc.default_program_cache().root,
    "xla_entries": len([f for f in os.listdir(mxc.cache_root())
                        if f != "programs"]),
    "program_entries": len(mxc.default_program_cache().entries()),
    "home_cache": os.path.exists(os.path.expanduser("~/.cache")),
    "loss": loss, "tokens": toks}))
"""


def test_cache_placed_from_outside_is_never_overridden(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set when the process starts, a
    trainer step and a generation engine (the normal entry points, no
    precompile call) leave XLA's cache in that directory and the
    ProgramCache under it, write nothing under ~/.cache, and never call
    ``jax.config.update("jax_compilation_cache_dir", ...)``."""
    import subprocess
    import sys
    root = str(tmp_path / "placed")
    home = tmp_path / "home"
    home.mkdir()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=root, HOME=str(home),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    r = subprocess.run([sys.executable, "-c", _PLACED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert "jax_compilation_cache_dir" not in out["calls"]
    assert out["dir"] == root and out["root"] == root
    assert out["programs"] == os.path.join(root, "programs")
    # the plain step() loop and the engine build both hit the cache
    assert out["xla_entries"] > 0 and out["program_entries"] > 0
    assert not out["home_cache"]
    assert len(out["tokens"]) == 3


def test_cache_defaults_to_fixed_checkout_path(monkeypatch):
    """Unset, XLA's cache and the ProgramCache resolve to ONE fixed,
    git-ignored directory inside the checkout (the path is part of XLA's
    cache key: a directory that moves never hits)."""
    import jax
    session_root = os.environ["JAX_COMPILATION_CACHE_DIR"]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".compile_cache")
    assert mxcompile.cache_root() == fixed
    assert mxcompile.program_cache_dir() == os.path.join(fixed, "programs")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".compile_cache/" in f.read().split()
    try:
        assert mxcompile.enable_persistent_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        monkeypatch.undo()
        mxcompile.disable_persistent_cache()
        mxcompile.enable_persistent_cache()
    assert jax.config.jax_compilation_cache_dir == session_root


def test_warm_load_runs_on_the_devices_it_was_lowered_for(cache_dir,
                                                          monkeypatch):
    """A one-device program compiled, stored and warm-loaded on the
    8-device host executes — through each of the three warm-load sites.
    jax's ``deserialize_and_load`` defaults to EVERY device of the
    backend, which turned such a program into an 8-shard executable that
    died at dispatch; and the ProgramCache key tells device sets apart."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import engine
    assert len(jax.devices()) == 8
    monkeypatch.setenv("MXNET_OP_CACHE_PERSIST_MIN_MS", "0")
    x = jnp.arange(8.0)
    want = onp.arange(8.0) * 2 + 1

    # site 1: compile.aot_compile_lowered (AOT entry points, serving)
    f = jax.jit(lambda a: a * 2 + 1)
    _cold, info = mxcompile.aot_compile_lowered(f.lower(x))
    warm, info2 = mxcompile.aot_compile_lowered(f.lower(x))
    assert not info["cache_hit"] and info2["cache_hit"]
    assert onp.array_equal(onp.asarray(warm(x)), want)

    # site 2: engine._aot_compile (lazy / captured-step segments)
    g = jax.jit(lambda a: a * 2 + 1 + 0 * a)
    hits = engine.engine_stats()["op_cache_persist_hits"]
    _exe, key = engine._aot_compile(g, (x,), "lazy_segment")
    exe2, key2 = engine._aot_compile(g, (x,), "lazy_segment")
    assert key2 == key
    assert engine.engine_stats()["op_cache_persist_hits"] == hits + 1
    assert onp.array_equal(onp.asarray(exe2(x)), want)

    # site 3: engine._pc_warm_load (per-op executable cache)
    exe3, _lowered, key3, _pc = engine._pc_warm_load(g, (x,))
    assert exe3 is not None and key3 == key
    assert onp.array_equal(onp.asarray(exe3(x)), want)

    # the same program lowered for another device is another cache entry,
    # and its warm load runs there
    dev3 = jax.devices()[3]
    x3 = jax.device_put(x, dev3)
    assert mxcompile.fingerprint_lowered(f.lower(x3)) != info["key"]
    mxcompile.aot_compile_lowered(f.lower(x3))
    warm3, info3 = mxcompile.aot_compile_lowered(f.lower(x3))
    assert info3["cache_hit"]
    out3 = warm3(x3)
    assert out3.devices() == {dev3}
    assert onp.array_equal(onp.asarray(out3), want)


def test_unwritable_cache_root_degrades_to_uncached(monkeypatch, tmp_path):
    """Read-only/unwritable cache root must mean 'run uncached', never an
    exception on the training/serving path."""
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where a directory must go")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "root"))
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "1")
    assert mxcompile.enable_persistent_cache() is None
    assert mxcompile.default_program_cache() is None
    net = _mlp(seed=11)
    info = net.aot_compile([((2, 8), "float32")])   # uncached compile
    assert info["cache_hit"] is False and info["key"] is None
    assert net(nd.zeros((2, 8))).shape == (2, 4)


def test_undeserializable_entry_invalidated(cache_dir):
    """A blob that hashes clean but will not deserialize is set aside and
    its index entry dropped (no doomed-load retry loop)."""
    net = _mlp(seed=12)
    info = net.aot_compile([((2, 8), "float32")])
    pc = mxcompile.default_program_cache()
    assert pc.put(info["key"], b"hash-clean but not a pickle")
    net2 = _mlp(seed=12)
    info2 = net2.aot_compile([((2, 8), "float32")])
    assert info2["cache_hit"] is False
    blob = os.path.join(pc.root, info["key"] + ".bin")
    assert os.path.exists(blob + ".corrupt")
    # the recompile re-put a good blob; a third instance warm-starts
    net3 = _mlp(seed=12)
    assert net3.aot_compile([((2, 8), "float32")])["cache_hit"] is True


def test_segment_arity_mismatch_invalidates_persisted_blob(cache_dir,
                                                           monkeypatch):
    """A warm-loaded fused-segment executable whose output count does not
    match the live slots must never reach the writeback: since the
    donation work the stale blob is caught by an arity PRE-check before
    it executes (a donating call would consume its inputs even when the
    outputs are garbage) — the flush surfaces a warning, poisons the
    persisted artifact, recompiles in place and still yields correct
    values; the re-persisted artifact is a good one."""
    import pickle

    import jax
    from jax.experimental import serialize_executable as se
    from mxnet_tpu import engine

    monkeypatch.setenv("MXNET_OP_CACHE_PERSIST_MIN_MS", "0")
    engine.reset_op_cache()
    engine.set_engine_type("LazyEngine")
    try:
        x = nd.array(onp.arange(6, dtype="float32").reshape(2, 3))

        def flush_chain():
            return ((x * 2.0) + 1.0).asnumpy()

        ref = flush_chain()                  # compiles + persists
        pc = mxcompile.default_program_cache()
        seg = [e for e in pc.entries()
               if e["meta"].get("kind") == "lazy_segment"]
        assert seg, pc.entries()
        key = seg[0]["key"]

        # poison: same key, a blob that DESERIALIZES fine but returns the
        # wrong number of outputs for the segment's live slots
        bad = jax.jit(lambda a, b, c: (a + 1, a + 2, a + 3))
        compiled = bad.lower(x.asnumpy(), 2.0, 1.0).compile()
        payload, in_tree, out_tree = se.serialize(compiled)
        assert pc.put(key, pickle.dumps((payload, in_tree, out_tree)),
                      meta=seg[0]["meta"])

        engine.reset_op_cache()              # drop in-memory entry only
        with pytest.warns(UserWarning, match="live slots"):
            out = flush_chain()     # warm-loads poison -> pre-check fires
        assert onp.array_equal(out, ref)
        # the poisoned blob is set aside AND the same flush recompiled +
        # re-persisted a good artifact under the key (pre-PR-11 the
        # mismatch was only caught after execution and the flush fell
        # back to an eager replay, leaving the key empty)
        blob = os.path.join(pc.root, key + ".bin")
        assert os.path.exists(blob + ".corrupt")
        assert pc.get(key) is not None

        # a later cold flush warm-loads the re-persisted artifact cleanly
        engine.reset_op_cache()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert onp.array_equal(flush_chain(), ref)
    finally:
        engine.set_engine_type("ThreadedEngine")


def test_segment_failing_warm_executable_invalidated(cache_dir,
                                                     monkeypatch):
    """A warm-loaded segment executable that RAISES at call time (not just
    wrong arity — e.g. a topology change at the same version stamp) must
    also poison the persisted artifact once the eager replay proves the
    recorded program itself is fine, so later processes recompile instead
    of warm-loading the same doomed blob forever."""
    import pickle

    import jax
    from jax.experimental import serialize_executable as se
    from mxnet_tpu import engine

    monkeypatch.setenv("MXNET_OP_CACHE_PERSIST_MIN_MS", "0")
    engine.reset_op_cache()
    engine.set_engine_type("LazyEngine")
    try:
        x = nd.array(onp.arange(6, dtype="float32").reshape(2, 3))

        def flush_chain():
            return ((x * 2.0) + 1.0).asnumpy()

        ref = flush_chain()
        pc = mxcompile.default_program_cache()
        seg = [e for e in pc.entries()
               if e["meta"].get("kind") == "lazy_segment"]
        assert seg, pc.entries()
        key = seg[0]["key"]

        # poison: deserializes fine, but was lowered for DIFFERENT input
        # shapes, so calling it with the segment's externals raises
        bad = jax.jit(lambda a, b, c: (a * 2 + 1,))
        compiled = bad.lower(onp.zeros((4, 5), "float32"), 2.0, 1.0)\
            .compile()
        payload, in_tree, out_tree = se.serialize(compiled)
        assert pc.put(key, pickle.dumps((payload, in_tree, out_tree)),
                      meta=seg[0]["meta"])

        engine.reset_op_cache()
        out = flush_chain()                  # poison raises -> replay
        assert onp.array_equal(out, ref)
        assert engine.engine_stats()["lazy_eager_replays"] >= 1
        assert pc.get(key) is None           # artifact set aside
        assert os.path.exists(os.path.join(pc.root, key + ".bin.corrupt"))

        engine.reset_op_cache()
        assert onp.array_equal(flush_chain(), ref)   # clean recompile
        assert pc.get(key) is not None
    finally:
        engine.set_engine_type("ThreadedEngine")


def test_cache_master_switch_off(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "off"))
    assert mxcompile.enable_persistent_cache() is None
    assert mxcompile.default_program_cache() is None
    assert not os.path.exists(str(tmp_path / "off"))


# ---------------------------------------------------------------------------
# HybridBlock.aot_compile
# ---------------------------------------------------------------------------
def test_block_aot_compile_matches_eager_and_warm_starts(cache_dir):
    net = _mlp(seed=1)
    x = nd.array(onp.random.RandomState(0).randn(2, 8).astype("float32"))
    ref = net(x).asnumpy()          # eager reference BEFORE aot
    info = net.aot_compile([((2, 8), "float32")])
    assert info["cache_hit"] is False
    out = net(x).asnumpy()          # runs the AOT executable
    onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    # an identical fresh net warm-starts from the program index
    net2 = _mlp(seed=1)
    info2 = net2.aot_compile([((2, 8), "float32")])
    assert info2["cache_hit"] is True and info2["key"] == info["key"]
    onp.testing.assert_allclose(net2(x).asnumpy(), ref,
                                rtol=1e-5, atol=1e-6)


def test_block_aot_compile_deferred_shapes(cache_dir):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))     # deferred in_units
    net.add(nn.Dense(4))
    net.initialize()
    net.aot_compile([((3, 8), "float32")])
    y = net(nd.zeros((3, 8)))
    assert y.shape == (3, 4)


def test_block_aot_gradients_still_flow(cache_dir):
    from mxnet_tpu import autograd
    net = _mlp(seed=2)
    net.aot_compile([((2, 8), "float32")])
    x = nd.ones((2, 8))
    x.attach_grad()
    with autograd.record():
        y = net(x).sum()
    y.backward()
    assert x.grad.shape == (2, 8)
    assert onp.isfinite(x.grad.asnumpy()).all()


def test_block_aot_corrupt_entry_recompiles_clean(cache_dir):
    """A truncated on-disk executable must degrade to a recompile, not a
    crash (the acceptance-criteria robustness path, end to end)."""
    net = _mlp(seed=3)
    info = net.aot_compile([((2, 8), "float32")])
    pc = mxcompile.default_program_cache()
    blob_path = os.path.join(pc.root, info["key"] + ".bin")
    with open(blob_path, "wb") as f:
        f.write(b"\x00garbage")
    net2 = _mlp(seed=3)
    info2 = net2.aot_compile([((2, 8), "float32")])
    assert info2["cache_hit"] is False        # set aside + recompiled
    assert os.path.exists(blob_path + ".corrupt")
    assert net2(nd.zeros((2, 8))).shape == (2, 4)


# ---------------------------------------------------------------------------
# SPMDTrainer.precompile
# ---------------------------------------------------------------------------
def test_trainer_precompile_then_step(cache_dir):
    import jax
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss

    net = _mlp(seed=4)
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    trainer = parallel.SPMDTrainer(
        net, lambda out, y: lossfn(out, y),
        opt.create("sgd", learning_rate=0.1), mesh)
    x = nd.array(onp.random.RandomState(1).randn(4, 8).astype("float32"))
    y = nd.array(onp.array([0, 1, 2, 3], dtype="float32"))
    info = trainer.precompile(x, y)
    assert info["compile_s"] >= 0 and info["lower_s"] > 0
    assert info["cache_dir"] == cache_dir
    loss = trainer.step(x, y)
    assert onp.isfinite(float(loss.astype("float32").asnumpy()))


# ---------------------------------------------------------------------------
# serving: engine precompile + warmup manifest
# ---------------------------------------------------------------------------
def test_engine_block_precompile_parallel_and_serve(cache_dir):
    net = _mlp(seed=5)
    eng = serving.InferenceEngine(net, batch_buckets=(1, 2, 4))
    res = eng.precompile(example_inputs=[onp.zeros(8, "float32")])
    assert set(res["buckets"]) == {1, 2, 4}
    stats = eng.metrics.stats()["counters"]
    assert stats["aot_compiles"] == 3 and stats["compiles"] == 3
    x = onp.random.RandomState(2).randn(3, 8).astype("float32")
    ref = net(nd.array(x)).asnumpy()
    out = eng.run_batch([x])
    onp.testing.assert_allclose(out[0], ref, rtol=1e-5, atol=1e-5)
    # precompiled buckets never trace on first traffic: compiles stays 3
    assert eng.metrics.stats()["counters"]["compiles"] == 3
    # weight hot-swap still picked up by the AOT path
    for p in net.collect_params().values():
        p.set_data(p.data() * 0)
    onp.testing.assert_allclose(eng.run_batch([x])[0], 0.0, atol=1e-6)


def _generation_engine(seed):
    from mxnet_tpu.models.lm import tiny_lm
    from mxnet_tpu.serving.generate import GenerationEngine
    mx.random.seed(seed)
    net = tiny_lm(vocab_size=32, num_layers=2, units=16, hidden_size=32,
                  num_heads=2, max_length=32)
    net.initialize()
    net(nd.array(onp.zeros((1, 4), onp.int32)),
        nd.array(onp.asarray([4], onp.int32)))
    return GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_donated_serving_program_warm_loads_and_consumes(cache_dir, program):
    """The generation engine's programs take the KV rings as donated
    arguments.  Read back from the ProgramCache they still consume them,
    and the donor attributes are in the key: the undonated lowering of the
    same function is another program."""
    import jax
    cold = _generation_engine(seed=7)
    try:
        want = cold.generate([3, 1, 4], max_new_tokens=4, timeout=120)
        c = cold.metrics.stats()["counters"]
        assert c[program + "_compiles"] == 1
    finally:
        cold.stop()
    warm = _generation_engine(seed=7)
    try:
        c = warm.metrics.stats()["counters"]
        assert c[program + "_cache_hits"] == 1
        assert c[program + "_compiles"] == 0
        rings = list(warm._cache_flat)
        got = warm.generate([3, 1, 4], max_new_tokens=1 if program ==
                            "prefill" else 4, timeout=120)
        assert got["tokens"] == want["tokens"][:len(got["tokens"])]
        assert all(r.is_deleted() for r in rings)
        c = warm.metrics.stats()["counters"]
        assert c["kv_inplace_dispatches"] == c["prefills"] + c["decode_steps"]
        # the same function and shapes, lowered with and without donors
        if program == "decode":
            fn, sds = warm._decode_pure(), warm._input_sds()
        else:
            fn, sds = warm._prefill_pure(8), warm._input_sds(8)
        donated = mxcompile.fingerprint_lowered(warm._lower(fn, sds))
        plain = mxcompile.fingerprint_lowered(
            jax.jit(fn).lower(warm._read_params(), *sds))
        pc = mxcompile.default_program_cache()
        keys = {e["key"] for e in pc.entries()}
        assert donated in keys and plain not in keys
    finally:
        warm.stop()


def test_engine_precompile_rejects_unknown_bucket(cache_dir):
    eng = serving.InferenceEngine(_mlp(seed=6), batch_buckets=(1, 2))
    with pytest.raises(mx.MXNetError):
        eng.precompile(example_inputs=[onp.zeros(8, "float32")],
                       buckets=(7,))
    with pytest.raises(mx.MXNetError):
        eng.precompile()            # block engine needs example specs


def test_multibucket_export_manifest_and_load_precompile(cache_dir,
                                                         tmp_path):
    net = _mlp(seed=7)
    x = nd.array(onp.random.RandomState(3).randn(4, 8).astype("float32"))
    ref = net(x).asnumpy()
    path = str(tmp_path / "m.shlo")
    stablehlo.export_model(net, path, x, batch_buckets=(1, 2, 4))
    model = stablehlo.import_model(path)
    assert model.buckets == (1, 2, 4)
    assert model.manifest == {"buckets": [1, 2, 4],
                              "signature": [[[8], "float32"]]}
    assert model.batch_size == 4
    # the engine ladder comes from the manifest; a bare precompile() warms
    # every exported bucket at load
    eng = serving.InferenceEngine(model, precompile=True)
    assert eng.batch_buckets == (1, 2, 4)
    c = eng.metrics.stats()["counters"]
    assert c["aot_compiles"] + c["aot_cache_hits"] == 3
    out = eng.run_batch([x.asnumpy()[:3]])      # pads 3 -> bucket 4
    onp.testing.assert_allclose(out[0], ref[:3], rtol=1e-5, atol=1e-5)
    # a restarted server deserializes instead of recompiling
    eng2 = serving.InferenceEngine(stablehlo.import_model(path),
                                   precompile=True)
    assert eng2.metrics.stats()["counters"]["aot_cache_hits"] == 3
    onp.testing.assert_allclose(eng2.run_batch([x.asnumpy()])[0], ref,
                                rtol=1e-5, atol=1e-5)


def test_servedmodel_exact_bucket_dispatch(tmp_path):
    net = _mlp(seed=8)
    x = onp.random.RandomState(4).randn(4, 8).astype("float32")
    path = str(tmp_path / "m.shlo")
    stablehlo.export_model(net, path, nd.array(x), batch_buckets=(2, 4))
    model = stablehlo.import_model(path)
    ref = net(nd.array(x)).asnumpy()
    onp.testing.assert_allclose(model(x[:2]).asnumpy(), ref[:2],
                                rtol=1e-5, atol=1e-5)
    with pytest.raises(mx.MXNetError):
        model.program(3)
    # a batch matching no bucket names the ladder instead of a raw
    # shape error from the largest program
    with pytest.raises(mx.MXNetError, match=r"buckets\s+are \(2, 4\)"):
        model(x[:3])


def test_stablehlo_v1_artifact_still_imports(tmp_path):
    """Pre-manifest artifacts (MXTPU-SHLO1) keep loading."""
    import jax
    from jax import export as jexport
    net = _mlp(seed=9)
    x = onp.random.RandomState(5).randn(2, 8).astype("float32")
    ref = net(nd.array(x)).asnumpy()
    pure_fn, read_params = net.inference_fn()
    raws = read_params()

    def frozen(a):
        return pure_fn(raws, a)[0]

    exp = jexport.export(jax.jit(frozen))(
        jax.ShapeDtypeStruct(x.shape, x.dtype))
    path = str(tmp_path / "v1.shlo")
    with open(path, "wb") as f:
        f.write(b"MXTPU-SHLO1\n")
        f.write(bytes(exp.serialize()))
    model = stablehlo.import_model(path)
    assert model.buckets == (2,) and model.batch_size == 2
    onp.testing.assert_allclose(model(x).asnumpy(), ref,
                                rtol=1e-5, atol=1e-5)


def test_stablehlo_truncated_v2_rejected(tmp_path):
    net = _mlp(seed=10)
    path = str(tmp_path / "t.shlo")
    stablehlo.export_model(net, path, nd.zeros((2, 8)),
                           batch_buckets=(1, 2))
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[:len(data) // 2])
    with pytest.raises(mx.MXNetError):
        stablehlo.import_model(path)


# ---------------------------------------------------------------------------
# satellites: io num_prefetch + bench-writer lint
# ---------------------------------------------------------------------------
def test_prefetching_iter_num_prefetch_exposed():
    from mxnet_tpu import io
    data = onp.arange(40, dtype="float32").reshape(10, 4)
    base = io.NDArrayIter(data, onp.zeros(10, "float32"), batch_size=2)
    it = io.PrefetchingIter(base, num_prefetch=4)
    assert it.num_prefetch == 4
    assert sum(1 for _ in it) == 5
    it.reset()
    assert sum(1 for _ in it) == 5
    with pytest.raises(mx.MXNetError):
        io.PrefetchingIter(base, num_prefetch=0)
