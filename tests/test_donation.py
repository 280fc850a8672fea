"""Buffer donation into captured step executables (docs/ENGINE.md
"Memory-lean fused steps"): bit-identity with donation on/off, the
MXNET_STEP_DONATE policy switch shared with SPMDTrainer, ledger-visible
aliasing, stale warm-loaded executable invalidation, and the
donated-failure recovery paths (ResilientStep recover-and-retry +
elastic_run restart — docs/RESILIENCE.md)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint as ckpt, engine, faults, io, \
    memory, nd
from mxnet_tpu.gluon import Trainer, loss as gloss, nn


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    engine.set_engine_type("ThreadedEngine")
    engine.reset_op_cache()
    memory.reset()
    faults.reset()
    yield
    monkeypatch.undo()
    engine.set_engine_type("ThreadedEngine")
    engine.reset_op_cache()
    memory.reset()
    faults.reset()


def _build(seed=0, layers=4, units=32):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(nn.Dense(units, activation="relu", in_units=units))
    net.add(nn.Dense(10, in_units=units))
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.05, "momentum": 0.9})
    return net, tr


def _train(mode, steps=5, donate=None, monkeypatch=None, units=32):
    if donate is not None:
        assert monkeypatch is not None
        monkeypatch.setenv("MXNET_STEP_DONATE", "1" if donate else "0")
    engine.reset_op_cache()
    engine.set_engine_type(mode)
    net, tr = _build(units=units)
    L = gloss.SoftmaxCrossEntropyLoss()
    rng = onp.random.RandomState(3)
    losses = []
    for _ in range(steps):
        x = nd.array(rng.randn(8, units).astype("float32"))
        y = nd.array(rng.randint(0, 10, (8,)).astype("float32"))
        with autograd.record():
            l = L(net(x), y).mean()
        l.backward()
        tr.step(8)
        losses.append(float(l.asnumpy()))
    params = [p.data().asnumpy() for p in net.collect_params().values()]
    stats = dict(engine.engine_stats())
    engine.set_engine_type("ThreadedEngine")
    return losses, params, stats


# ---------------------------------------------------------------------------
# bit-identity + the policy switch
# ---------------------------------------------------------------------------
def test_donated_capture_bit_identical_to_eager(monkeypatch):
    """Donation must not change a single bit: eager == captured+donate
    == captured without donation, and the donated loop actually donated
    (every sealed step flush, not just some)."""
    eag = _train("ThreadedEngine")
    don = _train("LazyEngine", donate=True, monkeypatch=monkeypatch)
    nod = _train("LazyEngine", donate=False, monkeypatch=monkeypatch)
    assert don[0] == eag[0] == nod[0]
    for a, b, c in zip(don[1], eag[1], nod[1]):
        assert onp.array_equal(a, b)
        assert onp.array_equal(a, c)
    assert don[2]["donated_flushes"] >= 5
    assert don[2]["donated_flushes"] == don[2]["step_flushes"]
    assert nod[2]["donated_flushes"] == 0


def test_donation_aliases_in_ledger(monkeypatch, tmp_path):
    """The step-segment executable's ledger entry shows the donated
    param/state bytes as alias bytes, and its peak drops vs the
    non-donating program (the memory_report referee)."""
    # fresh ProgramCache root: a warm-loaded (deserialized) executable
    # reports memory_analysis WITHOUT the alias table — the ledger
    # flags it analysis="warm", but this referee needs fresh numbers
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "pc"))
    def seg_peak():
        segs = [e for e in memory.ledger() if e["kind"] == "step_segment"]
        assert segs, "no step_segment ledger entry"
        best = max(segs, key=lambda e: e["compiles"])
        return best

    # units sized up: XLA-CPU declines to alias very small buffers, so a
    # 32-wide net shows alias_bytes 0 even though donation is active
    memory.reset()
    _train("LazyEngine", donate=True, monkeypatch=monkeypatch, units=128)
    don = seg_peak()
    memory.reset()
    _train("LazyEngine", donate=False, monkeypatch=monkeypatch, units=128)
    nod = seg_peak()
    assert don["alias_bytes"] > 0
    assert nod["alias_bytes"] == 0
    assert don["peak_bytes"] < nod["peak_bytes"]


def test_old_param_buffers_freed_after_donated_flush(monkeypatch):
    """The point of donating: the pre-step weight buffers are actually
    invalidated (aliased into the updated outputs), not kept alive."""
    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    engine.reset_op_cache()
    engine.set_engine_type("LazyEngine")
    net, tr = _build()
    L = gloss.SoftmaxCrossEntropyLoss()
    x = nd.array(onp.random.RandomState(0).randn(8, 32).astype("float32"))
    y = nd.array(onp.random.RandomState(1).randint(0, 10, (8,))
                 .astype("float32"))
    # settle compile caches first
    with autograd.record():
        l = L(net(x), y).mean()
    l.backward()
    tr.step(8)
    float(l.asnumpy())
    olds = [p.data()._data for p in net.collect_params().values()]
    assert all(o is not None for o in olds)
    with autograd.record():
        l = L(net(x), y).mean()
    l.backward()
    tr.step(8)
    float(l.asnumpy())               # flush: the sealed step donates
    assert any(o.is_deleted() for o in olds)
    engine.set_engine_type("ThreadedEngine")


def test_spmd_policy_follows_env(monkeypatch):
    """SPMDTrainer(donate_params=None) resolves through the SAME policy
    switch as the captured gluon step; explicit bools override."""
    import jax
    from mxnet_tpu import parallel
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, _ = _build()

    def mk(**kw):
        return parallel.SPMDTrainer(
            net, lambda o, y: gloss.SoftmaxCrossEntropyLoss()(o, y).mean(),
            "sgd", mesh, **kw)

    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    assert mk()._donate is True
    monkeypatch.setenv("MXNET_STEP_DONATE", "0")
    assert mk()._donate is False
    assert engine.donation_enabled() is False
    assert mk(donate_params=True)._donate is True
    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    assert mk(donate_params=False)._donate is False


def test_capture_off_and_naive_engine_unaffected(monkeypatch):
    """MXNET_STEP_CAPTURE=0 (materializing update path) and NaiveEngine
    train bit-identically with the donation env on — the policy only
    engages through sealed capture segments."""
    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    eag = _train("ThreadedEngine")
    monkeypatch.setenv("MXNET_STEP_CAPTURE", "0")
    off = _train("LazyEngine")
    assert off[0] == eag[0]
    assert off[2]["donated_flushes"] == 0
    monkeypatch.delenv("MXNET_STEP_CAPTURE")
    naive = _train("NaiveEngine")
    assert naive[0] == eag[0]


# ---------------------------------------------------------------------------
# mid-step flush safety: donation only arms at seal
# ---------------------------------------------------------------------------
def test_unsealed_flush_never_donates(monkeypatch):
    """A capture segment flushed BEFORE the trainer seals it (value read
    mid-step) must execute WITHOUT donation — params are still live."""
    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    engine.reset_op_cache()
    engine.set_engine_type("LazyEngine")
    net, tr = _build()
    L = gloss.SoftmaxCrossEntropyLoss()
    x = nd.array(onp.random.RandomState(0).randn(8, 32).astype("float32"))
    y = nd.array(onp.random.RandomState(1).randint(0, 10, (8,))
                 .astype("float32"))
    olds = [p.data()._data for p in net.collect_params().values()]
    with autograd.record():
        l = L(net(x), y).mean()
    l.backward()
    # value read BEFORE trainer.step: flushes the unsealed segment
    float(l.asnumpy())
    assert all(not o.is_deleted() for o in olds)
    tr.step(8)
    engine.flush_all()
    stats = engine.engine_stats()
    engine.set_engine_type("ThreadedEngine")
    # params were re-recorded as concrete externals of the update-only
    # sealed segment — THAT flush donates
    assert stats["donated_flushes"] >= 1


# ---------------------------------------------------------------------------
# failure recovery
# ---------------------------------------------------------------------------
def _poison_donating_executable():
    """Replace the cached donating step executable with one that deletes
    its donated inputs then raises — the 'executable failed after
    consuming its buffers' case (a real one: device-side failure after
    the runtime took ownership)."""
    poisoned = []
    with engine._cache_lock:
        items = list(engine._segment_cache.items())
    for sig, fn in items:
        donate = sig[2] if len(sig) > 2 else ()
        if not donate:
            continue

        def explode(*ext, _donate=donate):
            for i in _donate:
                try:
                    ext[i].delete()
                except Exception:
                    pass
            raise faults.TransientFault("injected post-donation failure")

        with engine._cache_lock:
            engine._segment_cache[sig] = explode
        poisoned.append(sig)
    return poisoned


def test_donated_failure_without_checkpoint_raises_typed(monkeypatch):
    """No checkpoint manager: a post-donation failure surfaces as the
    typed DonatedBuffersLost (classified TRANSIENT for elastic_run), not
    as a replay over freed buffers."""
    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    engine.reset_op_cache()
    engine.set_engine_type("LazyEngine")
    net, tr = _build()
    L = gloss.SoftmaxCrossEntropyLoss()
    x = nd.array(onp.random.RandomState(0).randn(8, 32).astype("float32"))
    y = nd.array(onp.random.RandomState(1).randint(0, 10, (8,))
                 .astype("float32"))
    for _ in range(2):
        with autograd.record():
            l = L(net(x), y).mean()
        l.backward()
        tr.step(8)
        float(l.asnumpy())
    # step 3 seals a donating segment; poison its cached executable
    with autograd.record():
        l = L(net(x), y).mean()
    l.backward()
    tr.step(8)
    assert _poison_donating_executable()
    with pytest.raises(engine.DonatedBuffersLost):
        float(l.asnumpy())
    assert faults.classify(engine.DonatedBuffersLost("x")) == \
        faults.TRANSIENT
    engine.set_engine_type("ThreadedEngine")


def _train_resumable_donating(ckdir, steps=6, poison_at=None):
    """Captured+donating training over a shuffled resumable iterator,
    checkpointing every step, under elastic_run.  ``poison_at``: after
    that step's seal, poison the donating executable ONCE so its flush
    kills the donated buffers mid-run.  Returns (losses, final_weights)."""
    mx.random.seed(7)
    onp.random.seed(7)
    rng = onp.random.RandomState(5)
    data = rng.rand(24, 8).astype("float32")
    label = rng.rand(24, 3).astype("float32")
    engine.reset_op_cache()
    engine.set_engine_type("LazyEngine")
    mx.random.seed(11)
    net = nn.Dense(3, in_units=8)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05,
                                               "momentum": 0.9})
    it = io.NDArrayIter(data, label, batch_size=6, shuffle=True)
    mgr = ckpt.CheckpointManager(ckdir, max_to_keep=3)
    losses = {}
    armed = [poison_at]

    def train_fn(start):
        if start:
            faults.restore_resume_extra(mgr.last_extra, data_iter=it)
        for step in range(start, steps):
            try:
                batch = it.next()
            except StopIteration:
                it.reset()
                batch = it.next()
            with autograd.record():
                l = gloss.L2Loss()(net(batch.data[0]), batch.label[0])
            l.backward()
            tr.step(6)
            if armed[0] is not None and step == armed[0]:
                armed[0] = None
                assert _poison_donating_executable()
            # the loss read flushes the sealed donating step — with the
            # poisoned executable this is where DonatedBuffersLost fires
            losses[step] = float(l.mean().asnumpy())
            mgr.save(step, net=net, trainer=tr,
                     extra=faults.make_resume_extra(it))

    try:
        if poison_at is not None:
            restarts = ckpt.elastic_run(train_fn, mgr, net=net, trainer=tr,
                                        max_restarts=2, backoff_s=0.01)
            assert restarts == 1
        else:
            train_fn(0)
    finally:
        engine.set_engine_type("ThreadedEngine")
    return losses[steps - 1], net.weight.data().asnumpy().copy()


def test_donated_failure_recovers_from_checkpoint(tmp_path, monkeypatch):
    """THE donation-safety acceptance proof: a transient failure that
    consumes the donated buffers mid-run recovers by restore-from-
    checkpoint (elastic_run restart + resumable iterator/RNG state) to a
    BIT-identical final loss and weights vs the un-faulted run."""
    monkeypatch.setenv("MXNET_STEP_DONATE", "1")
    loss_ref, w_ref = _train_resumable_donating(str(tmp_path / "ref"))
    loss_f, w_f = _train_resumable_donating(str(tmp_path / "faulted"),
                                            poison_at=3)
    assert loss_f == loss_ref          # bit-identical, not allclose
    assert onp.array_equal(w_f, w_ref)


def test_spmd_donated_failure_recover_and_retry(tmp_path, monkeypatch):
    """ResilientStep recover-and-retry (SPMD): a dispatch failure that
    deleted donated param buffers restores the latest checkpoint and
    re-dispatches IN-PROCESS — final loss bit-identical to unfaulted."""
    import jax
    from mxnet_tpu import parallel

    def run(ckdir, fault_step=None):
        mx.random.seed(21)
        mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
        net = nn.Dense(3, in_units=8)
        net.initialize()
        L = gloss.L2Loss()
        tr = parallel.SPMDTrainer(net, lambda o, y: L(o, y).mean(),
                                  "sgd", mesh, donate_params=True)
        mgr = ckpt.CheckpointManager(ckdir, max_to_keep=2)
        rs = faults.ResilientStep(tr, skip_nonfinite=False, manager=mgr,
                                  net=net, backoff_ms=1,
                                  crash_report_dir=str(tmp_path))
        rng = onp.random.RandomState(2)
        xs = [rng.rand(6, 8).astype("float32") for _ in range(5)]
        ys = [rng.rand(6, 3).astype("float32") for _ in range(5)]
        losses = []
        for i, (xa, ya) in enumerate(zip(xs, ys)):
            if fault_step is not None and i == fault_step:
                real_fn = tr._step_fn
                calls = [0]

                def failing(*args, _real=real_fn, _tr=tr):
                    calls[0] += 1
                    if calls[0] == 1:
                        # simulate a post-donation dispatch death: the
                        # runtime consumed the param buffers
                        for p in _tr._params:
                            try:
                                p._nd._data.delete()
                            except Exception:
                                pass
                        raise faults.TransientFault(
                            "injected dispatch failure after donation")
                    return _real(*args)

                tr._step_fn = failing
            out = rs.step(nd.array(xa), nd.array(ya))
            losses.append(float(out.astype("float32").asnumpy()))
            mgr.save(i, net=net, trainer=tr,
                     extra=faults.make_resume_extra())
        return losses

    ref = run(str(tmp_path / "ref"))
    faulted = run(str(tmp_path / "faulted"), fault_step=3)
    assert faulted == ref
    assert faults.counters().get("donation_recoveries", 0) >= 1


# ---------------------------------------------------------------------------
# lint: every donation site names its recovery test
# ---------------------------------------------------------------------------
def test_check_donation_sites_lint_clean():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_donation_sites.py")
    spec = importlib.util.spec_from_file_location("check_donation_sites",
                                                  path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    assert m.check() == []
