"""Whole-step lazy capture through autograd (docs/ENGINE.md).

The tentpole contract: under the lazy engine, an eager gluon training step
(forward under ``record()``, ``backward()``, ``Trainer.step()``) flushes as
ONE fused, cached, ProgramCache-persisted executable — bit-identical to
op-by-op eager execution — with a safe eager fallback on capture-hostile
ops.
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, engine, autograd
from mxnet_tpu.gluon import nn, loss as gloss, Trainer


@pytest.fixture(autouse=True)
def _threaded_engine():
    engine.set_engine_type("ThreadedEngine")
    yield
    engine.set_engine_type("ThreadedEngine")


def _mlp(layers=3, units=32, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(nn.Dense(units, activation="relu"))
    net.add(nn.Dense(10))
    net.initialize()
    return net


def _train(mode, steps=4, optimizer="sgd", opt_kw=None, hybridize=False,
           read_grads=True, read_loss_every_step=True, grad_req=None,
           net_fn=_mlp, batch_shape=(8, 16)):
    """One training loop; returns (losses, grads-per-step, final params,
    engine stats)."""
    engine.reset_op_cache()
    engine.set_engine_type(mode)
    net = net_fn()
    if hybridize:
        net.hybridize()
    if grad_req:
        for p in net.collect_params().values():
            p.grad_req = grad_req
    L = gloss.SoftmaxCrossEntropyLoss()
    tr = Trainer(net.collect_params(), optimizer,
                 opt_kw or {"learning_rate": 0.05, "momentum": 0.9})
    rng = onp.random.RandomState(1)
    losses, grads = [], []
    l = None
    for i in range(steps):
        x = nd.array(rng.randn(*batch_shape).astype("float32"))
        y = nd.array(rng.randint(0, 10, (batch_shape[0],))
                     .astype("float32"))
        with autograd.record():
            l = L(net(x), y).mean()
        l.backward()
        if read_grads:
            grads.append([p.grad().asnumpy()
                          for p in net.collect_params().values()])
        tr.step(batch_shape[0])
        if read_loss_every_step:
            losses.append(l.asnumpy())
    if not read_loss_every_step:
        losses.append(l.asnumpy())
    params = [p.data().asnumpy() for p in net.collect_params().values()]
    stats = dict(engine.engine_stats())
    engine.set_engine_type("ThreadedEngine")
    return losses, grads, params, stats


def _assert_bit_identical(a, b, what):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        if isinstance(x, list):
            _assert_bit_identical(x, y, f"{what}[{i}]")
        else:
            assert onp.array_equal(x, y), f"{what}[{i}] diverged"


# ---------------------------------------------------------------------------
# bit-identical parity: the acceptance bar
# ---------------------------------------------------------------------------
def test_mlp_steps_bit_identical_eager_vs_captured():
    """Loss, per-step grads AND final params over N steps: captured
    whole-step == op-by-op eager, bitwise (sgd+momentum)."""
    cap = _train("LazyEngine")
    eag = _train("ThreadedEngine")
    _assert_bit_identical(cap[0], eag[0], "loss")
    _assert_bit_identical(cap[1], eag[1], "grads")
    _assert_bit_identical(cap[2], eag[2], "params")
    assert cap[3]["step_flushes"] >= 4          # one fused flush per step
    assert cap[3]["tape_ops_recorded"] > 0


def test_mlp_adam_bit_identical():
    cap = _train("LazyEngine", optimizer="adam",
                 opt_kw={"learning_rate": 1e-3})
    eag = _train("ThreadedEngine", optimizer="adam",
                 opt_kw={"learning_rate": 1e-3})
    _assert_bit_identical(cap[0], eag[0], "loss")
    _assert_bit_identical(cap[2], eag[2], "params")


@pytest.mark.slow
def test_model_zoo_convnet_step_parity():
    """A model-zoo conv net (BatchNorm aux updates are capture-hostile and
    must fall back per-op without breaking parity)."""
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    def convnet():
        mx.random.seed(0)
        net = get_model("resnet18_v1", classes=10)
        net.initialize()
        return net

    kw = dict(steps=2, net_fn=convnet, batch_shape=(2, 3, 32, 32),
              read_grads=False)
    cap = _train("LazyEngine", **kw)
    eag = _train("ThreadedEngine", **kw)
    _assert_bit_identical(cap[0], eag[0], "loss")
    _assert_bit_identical(cap[2], eag[2], "params")


def test_chained_steps_without_loss_read():
    """Never reading the loss until the end: step N's sealed segment
    flushes when step N+1 first touches the updated params (device work
    pipelines behind python dispatch) — values still bit-identical."""
    cap = _train("LazyEngine", read_grads=False,
                 read_loss_every_step=False)
    eag = _train("ThreadedEngine", read_grads=False,
                 read_loss_every_step=False)
    _assert_bit_identical(cap[0], eag[0], "final loss")
    _assert_bit_identical(cap[2], eag[2], "params")


def test_one_segment_per_step_and_cache_reuse():
    """Steady state: ONE fused flush per step, all hitting the same cached
    executable (compile once)."""
    _, _, _, stats = _train("LazyEngine", steps=5, read_grads=False)
    assert stats["step_flushes"] == 5
    assert stats["lazy_flushes"] == 5
    assert stats["lazy_segment_cache_misses"] == 1
    assert stats["lazy_segment_cache_hits"] == 4


def test_hybridized_block_joins_capture():
    """A hybridized (aux-free) block records as ONE CachedOp tape node
    inside the captured step — hybridize()/capture interop."""
    cap = _train("LazyEngine", hybridize=True, read_grads=False)
    eag = _train("ThreadedEngine", hybridize=True, read_grads=False)
    _assert_bit_identical(cap[0], eag[0], "loss")
    _assert_bit_identical(cap[2], eag[2], "params")
    # whole forward is one tape node, so forward+vjp+loss+update stays far
    # below the op-by-op run's count (~26 fwd + ~26 vjp + update)
    per_step = cap[3]["tape_ops_recorded"] / 4
    assert per_step < 20, f"hybrid forward did not collapse: {per_step}"


# ---------------------------------------------------------------------------
# capture-hostile ops: fallback, never wrong answers
# ---------------------------------------------------------------------------
def test_value_read_mid_record_falls_back_bit_identical():
    """Data-dependent python control flow (reading a value mid-tape) is a
    materialization boundary: the step fragments but stays correct."""
    def loop(mode):
        engine.reset_op_cache()
        engine.set_engine_type(mode)
        net = _mlp()
        L = gloss.SoftmaxCrossEntropyLoss()
        tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
        rng = onp.random.RandomState(3)
        for _ in range(2):
            x = nd.array(rng.randn(4, 16).astype("float32"))
            y = nd.array(rng.randint(0, 10, (4,)).astype("float32"))
            with autograd.record():
                h = net(x)
                # hostile: value read inside the tape
                scale = 2.0 if float(h.sum().asscalar()) > 0 else 1.0
                l = (L(h, y) * scale).mean()
            l.backward()
            tr.step(4)
        out = l.asnumpy()
        params = [p.data().asnumpy()
                  for p in net.collect_params().values()]
        engine.set_engine_type("ThreadedEngine")
        return out, params

    lc, pc = loop("LazyEngine")
    le, pe = loop("ThreadedEngine")
    assert onp.array_equal(lc, le)
    _assert_bit_identical(pc, pe, "params")


def test_inplace_mutation_on_recorded_array_raises():
    from mxnet_tpu.base import MXNetError
    engine.set_engine_type("LazyEngine")
    a = nd.array(onp.ones((3, 3), "float32"))
    a.attach_grad()
    with autograd.record():
        y = a * 2
        with pytest.raises(MXNetError, match="in-place"):
            y += 1


def test_mutation_of_untaped_pending_input_mid_capture():
    """Mutating a PENDING but non-recorded array mid-capture is a flush
    boundary (PR-3 rule), not an error, and stays correct."""
    engine.set_engine_type("LazyEngine")
    a = nd.array(onp.ones((3, 3), "float32"))
    b = a * 3                      # deferred, not on the tape
    a2 = nd.array(onp.full((3, 3), 2.0, "float32"))
    a2.attach_grad()
    with autograd.record():
        l = (a2 * a2).sum()
        b += 1                     # mutation boundary: b materializes
    l.backward()
    assert onp.allclose(b.asnumpy(), 4.0)
    assert onp.allclose(a2.grad.asnumpy(), 2 * a2.asnumpy())


def test_sparse_embedding_grad_falls_back():
    """Embedding(sparse_grad=True) builds a manual eager tape node; the
    trainer refuses to splice row-sparse grads and takes the
    materializing path — values match the default engine."""
    from mxnet_tpu.ndarray import ops as F
    from mxnet_tpu.ndarray.sparse import RowSparseGrad

    def loop(mode):
        engine.reset_op_cache()
        engine.set_engine_type(mode)
        mx.random.seed(0)
        w = nd.array(onp.random.RandomState(0)
                     .randn(20, 4).astype("float32"))
        w.attach_grad()
        idx = nd.array(onp.array([1, 3, 3, 7], "float32"))
        with autograd.record():
            emb = F.embedding(idx, w, sparse_grad=True)
            l = (emb * emb).sum()
        l.backward()
        g = w._grad
        assert isinstance(g, RowSparseGrad)
        engine.set_engine_type("ThreadedEngine")
        return g.asnumpy()

    assert onp.array_equal(loop("LazyEngine"), loop("ThreadedEngine"))


# ---------------------------------------------------------------------------
# tape semantics under capture
# ---------------------------------------------------------------------------
def test_retain_graph_second_backward():
    """retain_graph=True: a second backward() re-records the VJP (lazy
    nodes hold no residuals) and matches eager bitwise."""
    def run(mode):
        engine.set_engine_type(mode)
        a = nd.array(onp.random.RandomState(5)
                     .randn(4, 4).astype("float32"))
        a.attach_grad()
        with autograd.record():
            y = ((a * a).tanh()).sum()
        y.backward(retain_graph=True)
        g1 = a.grad.asnumpy().copy()
        y.backward()                 # second walk over the same tape
        g2 = a.grad.asnumpy()
        engine.set_engine_type("ThreadedEngine")
        return g1, g2

    c1, c2 = run("LazyEngine")
    e1, e2 = run("ThreadedEngine")
    assert onp.array_equal(c1, e1)
    assert onp.array_equal(c2, e2)
    assert onp.array_equal(c1, c2)   # grad_req='write' overwrites


def test_grad_req_add_accumulates_captured():
    def run(mode):
        engine.set_engine_type(mode)
        a = nd.array(onp.random.RandomState(6)
                     .randn(3, 3).astype("float32"))
        a.attach_grad(grad_req="add")
        for _ in range(3):
            with autograd.record():
                y = (a * a).sum()
            y.backward()
        g = a.grad.asnumpy()
        engine.set_engine_type("ThreadedEngine")
        return g

    assert onp.array_equal(run("LazyEngine"), run("ThreadedEngine"))


def test_zero_grad_on_pending_grad():
    """zero_grad() while the grad is still pending on a captured step must
    detach it from the segment — the deferred value must not clobber the
    zeros when the segment later flushes."""
    engine.set_engine_type("LazyEngine")
    a = nd.array(onp.random.RandomState(7).randn(3, 3).astype("float32"))
    a.attach_grad()
    with autograd.record():
        y = (a * a).sum()
    y.backward()
    assert a.grad._data is None          # pending on the capture segment
    a.zero_grad()
    nd.waitall()                          # flush the captured segment
    assert onp.array_equal(a.grad.asnumpy(), onp.zeros((3, 3), "float32"))


def test_zero_grad_then_second_backward_same_segment():
    """zero_grad() detaches the pending grad; a SECOND backward before any
    flush re-adopts the same .grad NDArray into a later slot of the SAME
    still-unflushed capture segment (record() is a continuation).  The
    flush must write the second gradient — not resurrect the stale first
    slot's value.  (Regression: the writeback guarded only on
    ``_pending is None``, so the stale slot clobbered the re-adopted
    binding and the newer gradient was silently dropped.)"""
    engine.set_engine_type("LazyEngine")
    a = nd.array(onp.random.RandomState(11).randn(3, 3).astype("float32"))
    a.attach_grad()
    with autograd.record():
        y = (a * a).sum()
    y.backward()                  # grad = 2a, pending on the segment
    assert a.grad._data is None
    a.zero_grad()                 # detach from the segment
    with autograd.record():
        y2 = (a * 3.0).sum()
    y2.backward()                 # grad = 3, re-adopted into a later slot
    nd.waitall()
    assert onp.array_equal(a.grad.asnumpy(),
                           onp.full((3, 3), 3.0, "float32"))


def test_autograd_grad_function_captured():
    def run(mode):
        engine.set_engine_type(mode)
        a = nd.array(onp.random.RandomState(8)
                     .randn(4,).astype("float32"))
        a.attach_grad()
        with autograd.record():
            y = (a.tanh() * a).sum()
        (g,) = autograd.grad([y], [a])
        out = g.asnumpy()
        engine.set_engine_type("ThreadedEngine")
        return out

    assert onp.array_equal(run("LazyEngine"), run("ThreadedEngine"))


def test_dropout_captures_with_key_as_external():
    """Dropout threads its PRNG key as a raw positional arg — a committed
    concrete external the capture records; the VJP re-trace replays the
    same mask, so grads (mask x 2) match the eager run bitwise.  The scalar
    ``y`` is a 256-term float32 sum that the fused segment and the per-op
    program fold in different orders (29.879974 against 29.879988 on
    XLA:CPU), so it is held to that sum's round-off, not to the bit."""
    from mxnet_tpu.ndarray import ops as F

    def run(mode):
        engine.reset_op_cache()
        engine.set_engine_type(mode)
        mx.random.seed(42)
        a = nd.array(onp.random.RandomState(9)
                     .randn(16, 16).astype("float32"))
        a.attach_grad()
        with autograd.record(), autograd.train_mode():
            y = F.dropout(a * 2.0, p=0.5).sum()
        y.backward()
        out = y.asnumpy(), a.grad.asnumpy()
        stats = dict(engine.engine_stats())
        engine.set_engine_type("ThreadedEngine")
        return out, stats

    (yc, gc), stats = run("LazyEngine")
    (ye, ge), _ = run("ThreadedEngine")
    assert onp.array_equal(gc, ge)
    assert set(onp.unique(gc)) == {0.0, 4.0}    # a mask, scaled 1/(1-p)
    onp.testing.assert_allclose(
        yc, ye, rtol=gc.size * onp.finfo(onp.float32).eps, atol=0)
    assert stats["tape_ops_recorded"] > 0   # dropout did capture


# ---------------------------------------------------------------------------
# persistence + resilience
# ---------------------------------------------------------------------------
_WARM_SCRIPT = r"""
import json, sys
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import nd, engine, autograd, compile as mxc
from mxnet_tpu.gluon import nn, loss as gloss, Trainer

mxc.enable_persistent_cache()
engine.set_engine_type("LazyEngine")
mx.random.seed(0)
net = nn.HybridSequential()
for _ in range(2):
    net.add(nn.Dense(48, activation="relu"))
net.add(nn.Dense(10))
net.initialize()
L = gloss.SoftmaxCrossEntropyLoss()
tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
rng = onp.random.RandomState(1)
x = nd.array(rng.randn(8, 16).astype("float32"))
y = nd.array(rng.randint(0, 10, (8,)).astype("float32"))
with autograd.record():
    l = L(net(x), y).mean()
l.backward()
tr.step(8)
loss = float(l.asnumpy())
s = engine.engine_stats()
print(json.dumps({"loss": loss,
                  "persist_hits": s["op_cache_persist_hits"],
                  "step_flushes": s["step_flushes"]}))
"""


def test_captured_step_program_cache_warm_restart(tmp_path, monkeypatch):
    """A second PROCESS warm-starts the captured whole-step executable
    from the ProgramCache instead of recompiling (and computes the same
    loss)."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    # force the capture compile over the persistence threshold gate
    env["MXNET_OP_CACHE_PERSIST_MIN_MS"] = "1"

    def run():
        r = subprocess.run([sys.executable, "-c", _WARM_SCRIPT],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run()
    warm = run()
    assert cold["step_flushes"] >= 1
    assert warm["loss"] == cold["loss"]
    # the warm process deserialized at least the whole-step executable
    assert warm["persist_hits"] >= 1, (cold, warm)


def test_resilient_step_retries_captured_step_bit_identical(monkeypatch):
    """A transient fault injected at the trainer.step fault point retries
    cleanly under capture (nothing was recorded/mutated before the point
    fired) and reaches the unfaulted run's exact loss and params."""
    from mxnet_tpu import faults

    def loop(plan):
        if plan:
            monkeypatch.setenv("MXNET_FAULT_PLAN", plan)
        else:
            monkeypatch.delenv("MXNET_FAULT_PLAN", raising=False)
        faults.reset()
        engine.reset_op_cache()
        engine.set_engine_type("LazyEngine")
        net = _mlp()
        L = gloss.SoftmaxCrossEntropyLoss()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.05, "momentum": 0.9})
        rs = faults.ResilientStep(tr, skip_nonfinite=False, backoff_ms=0.0)
        rng = onp.random.RandomState(1)
        for _ in range(3):
            x = nd.array(rng.randn(4, 16).astype("float32"))
            y = nd.array(rng.randint(0, 10, (4,)).astype("float32"))
            with autograd.record():
                l = L(net(x), y).mean()
            l.backward()
            rs.step(4, loss=l)
        out = l.asnumpy()
        params = [p.data().asnumpy()
                  for p in net.collect_params().values()]
        retried = rs.retried_steps
        rs.close()
        engine.set_engine_type("ThreadedEngine")
        monkeypatch.delenv("MXNET_FAULT_PLAN", raising=False)
        faults.reset()
        return out, params, retried

    faulted = loop("trainer.step@2:transient")
    clean = loop("")
    assert faulted[2] >= 1                    # the retry actually happened
    assert onp.array_equal(faulted[0], clean[0])
    _assert_bit_identical(faulted[1], clean[1], "params")


def test_injected_flush_fault_recovers_via_eager_replay(monkeypatch):
    """engine.flush fault inside the captured step: the eager replay
    recovery still materializes every pending output correctly."""
    from mxnet_tpu import faults
    monkeypatch.setenv("MXNET_FAULT_PLAN", "engine.flush@1:transient")
    faults.reset()
    engine.set_engine_type("LazyEngine")
    try:
        net = _mlp()
        L = gloss.SoftmaxCrossEntropyLoss()
        tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
        rng = onp.random.RandomState(1)
        x = nd.array(rng.randn(4, 16).astype("float32"))
        y = nd.array(rng.randint(0, 10, (4,)).astype("float32"))
        with autograd.record():
            l = L(net(x), y).mean()
        l.backward()
        tr.step(4)
        loss = float(l.asnumpy())             # flush hits the fault
        stats = engine.engine_stats()
        assert stats["lazy_eager_replays"] >= 1
        assert onp.isfinite(loss)
    finally:
        monkeypatch.delenv("MXNET_FAULT_PLAN", raising=False)
        faults.reset()
        engine.set_engine_type("ThreadedEngine")


def test_replacement_trainer_does_not_reuse_stale_update(monkeypatch):
    """A NEW Trainer over the same params (same avals, same graph) must
    not hit the previous trainer's cached captured-update executable —
    its hyperparameters are baked into the traced update.  (Regression:
    the update-op key once used id(closure), which CPython can reuse
    after the old trainer is collected.)"""
    import gc

    def steps_with(momentum, fresh_eager_ref=False):
        engine.set_engine_type(
            "ThreadedEngine" if fresh_eager_ref else "LazyEngine")
        net = _mlp()
        L = gloss.SoftmaxCrossEntropyLoss()
        rng = onp.random.RandomState(1)
        out = None
        for mom in ([momentum] if isinstance(momentum, float)
                    else momentum):
            tr = Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": mom})
            for _ in range(2):
                x = nd.array(rng.randn(4, 16).astype("float32"))
                y = nd.array(rng.randint(0, 10, (4,)).astype("float32"))
                with autograd.record():
                    l = L(net(x), y).mean()
                l.backward()
                tr.step(4)
            out = l.asnumpy()
            del tr
            gc.collect()      # free the old trainer's update closure
        params = [p.data().asnumpy()
                  for p in net.collect_params().values()]
        engine.set_engine_type("ThreadedEngine")
        return out, params

    engine.reset_op_cache()
    cap = steps_with([0.9, 0.1])              # trainer swap mid-training
    eag = steps_with([0.9, 0.1], fresh_eager_ref=True)
    _assert_bit_identical(cap[1], eag[1], "params")


def test_capture_disabled_env_means_eager_tape(monkeypatch):
    """MXNET_STEP_CAPTURE=0 restores the PR-3 behavior end to end: the
    tape records eager vjp nodes and the trainer takes the materializing
    path — same numbers, no step flushes.  Both runs disable capture: with
    it off the tape skips the bit-parity plain-program re-execution (one
    forward, outputs from the vjp primal), so the reference is the
    capture-off eager engine, not the capture-on default."""
    monkeypatch.setenv("MXNET_STEP_CAPTURE", "0")
    cap = _train("LazyEngine", read_grads=False)
    assert cap[3]["step_flushes"] == 0
    eag = _train("ThreadedEngine", read_grads=False)
    monkeypatch.delenv("MXNET_STEP_CAPTURE", raising=False)
    _assert_bit_identical(cap[0], eag[0], "loss")
    _assert_bit_identical(cap[2], eag[2], "params")
