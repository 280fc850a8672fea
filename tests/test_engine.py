"""LazyEngine: fused lazy dispatch for the imperative NDArray path.

Covers the contract in docs/ENGINE.md: every materialization boundary
flushes, eager-vs-lazy numerics are identical, NaiveEngine overrides
deferral, errors from inside a deferred segment name the originating op,
and the sync-free lint holds on the hot dispatch-path modules.
"""
import os
import sys
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, nd, profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ndarray.ndarray import NDArray, apply_op


@pytest.fixture(autouse=True)
def _threaded_engine():
    """Every test starts and ends on the default async engine."""
    engine.set_engine_type("ThreadedEngine")
    yield
    engine.set_engine_type("ThreadedEngine")


def _arr(shape=(3, 4), seed=0, dtype="float32"):
    return nd.array(onp.random.RandomState(seed).randn(*shape).astype(dtype))


def _chain(x, b):
    return ((x * 2.0 + b).tanh() * (x + 1.0)).sigmoid()


# ---------------------------------------------------------------------------
# deferral basics
# ---------------------------------------------------------------------------
def test_bulk_defers_and_flushes_on_exit():
    a, b = _arr(), _arr(seed=1)
    with engine.bulk(32):
        y = _chain(a, b)
        assert y._data is None           # pending placeholder
        assert y.shape == (3, 4)         # aval metadata works un-flushed
        assert y.dtype == onp.dtype("float32")
        assert y.ndim == 2 and y.size == 12
    assert y._data is not None           # scope exit flushed


def test_lazy_engine_type_defers():
    engine.set_engine_type("LazyEngine")
    a = _arr()
    y = a + 1
    assert y._data is None
    assert engine.engine_type() == "LazyEngine"
    assert float(y.sum().asnumpy()) == pytest.approx(
        float((onp.asarray(a.asnumpy()) + 1).sum()), rel=1e-6)


def test_bulk_size_auto_flush():
    a = _arr()
    with engine.bulk(4):
        x = a
        for _ in range(4):
            x = x + 1
        assert x._data is not None       # 4th op hit the segment limit
        y = x + 1
        assert y._data is None           # new segment started


def test_env_bulk_size(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_BULK_SIZE", "2")
    a = _arr()
    with engine.bulk():                  # size<=0 -> env value
        x = a + 1
        y = x + 1
        assert y._data is not None       # flushed at 2 ops


# ---------------------------------------------------------------------------
# materialization boundaries (each must flush)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("boundary", [
    lambda y: y.asnumpy(),
    lambda y: y.sum().asscalar(),
    lambda y: y.sum().item(),
    lambda y: repr(y),
    lambda y: onp.asarray(y),            # __array__
    lambda y: bool(y.sum() > -1e9),      # __bool__
    lambda y: float(y.sum()),            # __float__
    lambda y: int(y.sum() * 0 + 3),      # __int__
    lambda y: y.wait_to_read(),
    lambda y: nd.waitall(),
    lambda y: engine.wait_for_var(y),
])
def test_materialization_boundary_flushes(boundary):
    a, b = _arr(), _arr(seed=1)
    with engine.bulk(64):
        y = _chain(a, b)
        assert y._data is None
        boundary(y)
        assert y._data is not None


def test_autograd_record_entry_continues_capture():
    """Whole-step capture (default on): entering record() inside a bulk
    scope CONTINUES the user's pending segment — pre-record staging ops
    fuse with the step instead of being force-flushed (the PR-3 behavior
    this replaces)."""
    a = _arr()
    with engine.bulk(64):
        y = a * 3
        assert y._data is None
        with autograd.record():
            assert y._data is None       # record() entry did NOT flush
        assert y._data is None
    assert onp.allclose(y.asnumpy(), a.asnumpy() * 3)


def test_autograd_record_entry_flushes_with_capture_off(monkeypatch):
    """Regression for the pre-capture contract: with MXNET_STEP_CAPTURE=0
    record() entry stays a materialization boundary."""
    monkeypatch.setenv("MXNET_STEP_CAPTURE", "0")
    a = _arr()
    with engine.bulk(64):
        y = a * 3
        assert y._data is None
        with autograd.record():
            assert y._data is not None   # record() entry is a boundary
            y.attach_grad()


def test_pending_input_mutation_flushes():
    a = _arr()
    with engine.bulk(64):
        y = a + 1
        assert y._data is None
        y += 1                           # mutation of a pending array
        assert y._data is not None
    assert onp.allclose(y.asnumpy(), a.asnumpy() + 2)


def test_pending_setitem_flushes():
    a = _arr()
    with engine.bulk(64):
        y = a + 1
        assert y._data is None
        y[0, 0] = 7.0
        assert y._data is not None
    assert y.asnumpy()[0, 0] == 7.0


def test_pending_copyto_target_flushes():
    a, b = _arr(), _arr(seed=1)
    with engine.bulk(64):
        y = a + 1
        assert y._data is None
        b.copyto(y)                      # overwrite a pending target
        assert y._data is not None
    assert onp.array_equal(y.asnumpy(), b.asnumpy())


def test_naive_engine_scope_flushes_and_disables():
    a = _arr()
    with engine.bulk(64):
        y = a + 1
        assert y._data is None
        with engine.naive_engine_scope():
            assert y._data is not None   # scope entry flushed
            z = a + 2
            assert z._data is not None   # and deferral is off inside
        w = a + 3
        assert w._data is None           # back on after the scope


def test_naive_engine_scope_inside_record_forces_sync():
    """Regression (PR-11 review): the capture flag is cached at record()
    entry for speed, but naive_engine_scope INSIDE an open record scope
    must still force synchronous execution — ops must not keep routing
    into the capture segment after lazy execution was force-disabled."""
    from mxnet_tpu import autograd as ag
    engine.set_engine_type("LazyEngine")
    try:
        a = _arr()
        a.attach_grad()
        with ag.record():
            y = a * 2
            assert y._data is None         # captured, as usual
            with engine.naive_engine_scope():
                z = a * 3
                assert z._data is not None  # forced synchronous
            w = a * 4
            assert w._data is None          # capture resumes after
        engine.flush_all()
    finally:
        engine.set_engine_type("ThreadedEngine")


def test_naive_engine_type_overrides_lazy(monkeypatch):
    engine.set_engine_type("NaiveEngine")
    assert engine.is_sync() and not engine.lazy_enabled()
    a = _arr()
    with engine.bulk(64):                # bulk cannot defeat NaiveEngine
        y = a + 1
        assert y._data is not None
    assert onp.allclose(y.asnumpy(), a.asnumpy() + 1)


def test_concurrent_flush_all_never_orphans_recordings():
    """A flush_all() racing a recording thread (autograd.record() entry on
    the main thread vs DataLoader prefetch workers — the exact failure the
    drive program caught) must never orphan placeholders or lose ops."""
    engine.set_engine_type("LazyEngine")
    a = _arr((4, 4))
    stop = threading.Event()
    errors = []

    def recorder():
        try:
            for i in range(200):
                y = ((a + float(i)) * 2).tanh()
                v = y.asnumpy()
                ref = onp.tanh((a.asnumpy() + float(i)) * 2)
                assert onp.allclose(v, ref)
        except Exception as e:            # pragma: no cover
            errors.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=recorder)
    t.start()
    while not stop.is_set():
        engine.flush_all()                # the racing boundary
    t.join()
    engine.set_engine_type("ThreadedEngine")
    assert not errors, errors[0]


def test_cross_segment_use_flushes_producer():
    """An array pending on another thread's segment is flushed when this
    thread consumes it."""
    a = _arr()
    box = {}

    def producer():
        with engine.bulk(64):
            box["y"] = a * 5
            box["pending"] = box["y"]._data is None
            ev.wait()                    # keep the scope open

    ev = threading.Event()
    t = threading.Thread(target=producer)
    t.start()
    while "y" not in box:
        pass
    assert box["pending"]
    z = box["y"] + 1                     # consumer on the main thread
    ev.set()
    t.join()
    assert onp.allclose(z.asnumpy(), a.asnumpy() * 5 + 1)


# ---------------------------------------------------------------------------
# numerics: eager and lazy must agree exactly
# ---------------------------------------------------------------------------
def test_parity_elementwise_chain_bit_identical():
    a, b = _arr((16, 16)), _arr((16, 16), seed=3)
    eager = _chain(a, b).asnumpy()
    with engine.bulk(64):
        lazy = _chain(a, b)
        out = lazy.asnumpy()
    assert onp.array_equal(eager, out)   # bit-identical


@pytest.mark.slow
def test_parity_model_zoo_forward():
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    mx.random.seed(0)
    net = get_model("vgg11_bn", classes=10)
    net.initialize()
    x = _arr((2, 3, 32, 32), seed=7)
    eager = net(x).asnumpy()
    engine.set_engine_type("LazyEngine")
    lazy = net(x).asnumpy()
    engine.set_engine_type("ThreadedEngine")
    assert eager.shape == (2, 10)
    assert onp.array_equal(eager, lazy)


def test_parity_reductions_and_indexing():
    a = _arr((8, 8), seed=11)
    eager = (a[2:6].sum(axis=1, keepdims=True) / a.max()).asnumpy()
    with engine.bulk(64):
        out = (a[2:6].sum(axis=1, keepdims=True) / a.max()).asnumpy()
    assert onp.array_equal(eager, out)


# ---------------------------------------------------------------------------
# error propagation
# ---------------------------------------------------------------------------
def test_deferred_error_names_originating_op():
    a = _arr()
    state = {"n": 0}

    def evil(x):
        # records clean (first abstract eval), then raises at flush time
        state["n"] += 1
        if state["n"] > 1:
            raise ValueError("boom")
        return x * 2

    with pytest.raises(MXNetError, match="evil_op"):
        with engine.bulk(64):
            y = apply_op(evil, a, op_name="evil_op")
            y.asnumpy()


def test_record_time_shape_error_raises_at_call_site():
    a, b = _arr((3, 4)), _arr((7, 7), seed=1)
    with pytest.raises(Exception):
        with engine.bulk(64):
            _ = a + b                    # incompatible broadcast


def test_autograd_unaffected_by_lazy():
    engine.set_engine_type("LazyEngine")
    a = _arr()
    a.attach_grad()
    with autograd.record():
        y = (a * a).sum()
    y.backward()
    engine.set_engine_type("ThreadedEngine")
    assert onp.allclose(a.grad.asnumpy(), 2 * a.asnumpy())


# ---------------------------------------------------------------------------
# tier-1 op-executable cache
# ---------------------------------------------------------------------------
def test_op_cache_hits_on_repeat_signatures():
    engine.reset_op_cache()
    a, b = _arr(), _arr(seed=1)
    for _ in range(3):
        (a + b).wait_to_read()
    s = engine.engine_stats()
    assert s["op_cache_hits"] >= 2
    assert s["op_cache_entries"] >= 1


def test_op_cache_scope_disables():
    engine.reset_op_cache()
    a, b = _arr(), _arr(seed=1)
    with engine.op_cache_scope(False):
        (a + b).wait_to_read()
        (a + b).wait_to_read()
    s = engine.engine_stats()
    assert s["op_cache_hits"] == 0 and s["op_cache_misses"] == 0


def test_op_cache_blacklists_jit_hostile_fun():
    engine.reset_op_cache()
    a = _arr()

    def hostile(x):
        # value-dependent control flow: fails under tracing, fine eagerly
        if float(onp.asarray(x).sum()) > -1e9:
            return x + 1
        return x

    r1 = apply_op(hostile, a, op_name="hostile")
    r2 = apply_op(hostile, a, op_name="hostile")
    assert onp.allclose(r1.asnumpy(), r2.asnumpy())
    assert engine.engine_stats()["op_cache_fallbacks"] >= 1


def test_invalid_call_does_not_blacklist_op():
    """A genuine user error (shape mismatch) must raise AND must not
    disable the executable cache for later valid calls of the same op."""
    engine.reset_op_cache()
    a, b = _arr((3, 4)), _arr((7, 7), seed=1)
    with pytest.raises(Exception):
        (a + b).wait_to_read()
    (a + _arr((3, 4), seed=2)).wait_to_read()
    (a + _arr((3, 4), seed=2)).wait_to_read()
    assert engine.engine_stats()["op_cache_hits"] >= 1   # still cached


def test_op_cache_persists_through_program_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_OP_CACHE_PERSIST_MIN_MS", "0")
    engine.reset_op_cache()
    a, b = _arr((32, 32)), _arr((32, 32), seed=1)
    (a + b).wait_to_read()               # compiles + persists (0ms gate)
    from mxnet_tpu import compile as mxc
    pc = mxc.default_program_cache()
    assert pc is not None and len(pc.entries()) >= 1
    engine.reset_op_cache()              # simulate a fresh process
    (a + b).wait_to_read()
    assert engine.engine_stats()["op_cache_persist_hits"] >= 1


def test_lazy_segment_cache_reuse():
    engine.reset_op_cache()
    a, b = _arr(), _arr(seed=1)
    for _ in range(3):
        with engine.bulk(64):
            out = _chain(a, b)
        out.wait_to_read()
    s = engine.engine_stats()
    assert s["lazy_flushes"] >= 3
    assert s["lazy_segment_cache_hits"] >= 1


def test_dead_placeholders_are_dropped_from_outputs():
    a = _arr()
    with engine.bulk(64):
        tmp = a + 1                      # dies before the flush
        out = tmp * 2
        del tmp
        v = out.asnumpy()
    assert onp.allclose(v, (a.asnumpy() + 1) * 2)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------
def test_profiler_records_flush_events(tmp_path):
    import json
    a, b = _arr(), _arr(seed=1)
    profiler.set_config(filename=str(tmp_path / "prof.json"))
    profiler.start()
    with engine.bulk(64):
        _chain(a, b).wait_to_read()
    profiler.stop()
    path = profiler.dump()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert any(e["name"].startswith("lazy_flush[") for e in events)
    assert any(e.get("cat") == "counter" and
               e["name"] == "engine/segment_ops" for e in events)


def test_engine_stats_shape():
    s = engine.engine_stats()
    for k in ("op_cache_hits", "op_cache_misses", "lazy_flushes",
              "lazy_segment_cache_hits", "op_cache_entries",
              "segment_cache_entries", "engine_type"):
        assert k in s


# ---------------------------------------------------------------------------
# lint: the hot dispatch path stays sync-free (fast test)
# ---------------------------------------------------------------------------
def test_sync_free_lint_repo_clean_and_catches_violation(tmp_path):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_sync_free", os.path.join(repo, "tools", "check_sync_free.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check(repo) == []
    # synthetic violation: asnumpy outside an allowlisted function
    bad = tmp_path / "mxnet_tpu" / "ndarray"
    bad.mkdir(parents=True)
    (bad / "ndarray.py").write_text(
        "def hot_path(x):\n    return x.asnumpy()\n")
    violations = mod.check(str(tmp_path))
    assert len(violations) == 1 and "asnumpy" in violations[0]
