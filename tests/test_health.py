"""mxnet_tpu.health: in-graph step diagnostics fused into the captured
gluon step / the eager update program / the SPMD fused step (training
bit-identical on/off on every path), the persistent run ledger (atomic
appends, resume rewind, elastic_run kill/restart contiguity), the
EWMA/z-score anomaly detectors (seeded spike/explosion/plateau/
nonfinite referees + clean-run false-positive referee), Monitor rewired
onto in-graph taps (one step_flush per monitored captured step),
crash-report schema v6 ``training`` section, and tools/run_report.py
(docs/OBSERVABILITY.md "Training-dynamics observability")."""
import importlib.util
import json
import math
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, faults, health, nd, telemetry
from mxnet_tpu.gluon import Trainer, loss as gloss, nn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture(autouse=True)
def _clean():
    health.reset()
    engine.reset_op_cache()
    engine.set_engine_type("ThreadedEngine")
    yield
    health.reset()
    engine.set_engine_type("ThreadedEngine")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_L = gloss.SoftmaxCrossEntropyLoss()
_RNG = onp.random.RandomState(0)
_X = _RNG.randn(8, 16).astype("float32")
_Y = _RNG.randint(0, 4, (8,)).astype("float32")


def _build_net(units=16, nout=4):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation="relu"))
    net.add(nn.Dense(nout))
    net.initialize()
    return net


def _train(mode, diag_on, steps=6, optimizer="sgd",
           opt_args=None):
    """One small run; returns (final loss, weights, consumed rows)."""
    engine.reset_op_cache()
    health.reset()
    health.enable(diag_on)
    engine.set_engine_type(
        "LazyEngine" if mode == "captured" else "ThreadedEngine")
    try:
        net = _build_net()
        tr = Trainer(net.collect_params(), optimizer,
                     opt_args or {"learning_rate": 0.05, "momentum": 0.9})
        x, y = nd.array(_X), nd.array(_Y)
        for _ in range(steps):
            with autograd.record():
                l = _L(net(x), y).mean()
            l.backward()
            tr.step(8)
            last = float(l.asnumpy())
        health.flush()
        rows = health.last_rows(64)
        w = {k: p.data().asnumpy().copy()
             for k, p in net._collect_params_with_prefix().items()}
        return last, w, rows
    finally:
        engine.set_engine_type("ThreadedEngine")


def _train_spmd(diag_on, steps=6):
    import jax
    from mxnet_tpu import optimizer as opt_mod, parallel
    engine.reset_op_cache()
    health.reset()
    health.enable(diag_on)
    net = _build_net()
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    tr = parallel.SPMDTrainer(
        net, lambda out, y: _L(out, y).mean(),
        opt_mod.create("sgd", learning_rate=0.05, momentum=0.9), mesh)
    x, y = nd.array(_X), nd.array(_Y)
    for _ in range(steps):
        last = float(tr.step(x, y).asnumpy())
    health.flush()
    rows = health.last_rows(64)
    w = {k: p.data().asnumpy().copy()
         for k, p in net._collect_params_with_prefix().items()}
    return last, w, rows


# ---------------------------------------------------------------------------
# bit-identity: diagnostics on vs off, all three paths
# ---------------------------------------------------------------------------
def test_captured_bit_identical_on_off():
    l_on, w_on, rows_on = _train("captured", True)
    l_off, w_off, rows_off = _train("captured", False)
    assert l_on == l_off
    for k in w_on:
        assert (w_on[k] == w_off[k]).all(), k
    assert [r["step"] for r in rows_on] == [1, 2, 3, 4, 5, 6]
    assert rows_off == []
    # the captured path stays ONE program per step with the tail in
    assert all(r["source"] == "gluon_captured" for r in rows_on)


def test_eager_bit_identical_and_matches_captured():
    l_cap, w_cap, rows_cap = _train("captured", True)
    l_e_on, w_e_on, rows_e = _train("eager", True)
    l_e_off, w_e_off, _ = _train("eager", False)
    assert l_e_on == l_e_off == l_cap
    for k in w_cap:
        assert (w_e_on[k] == w_e_off[k]).all(), k
        assert (w_e_on[k] == w_cap[k]).all(), k
    # diag values agree across the two gluon paths (same math, fp32
    # reductions fused into different programs — tolerance, not bits)
    assert len(rows_e) == len(rows_cap) == 6
    for ra, rb in zip(rows_cap, rows_e):
        assert abs(ra["loss"] - rb["loss"]) < 1e-6
        assert abs(ra["grad_norm"] - rb["grad_norm"]) \
            < 1e-5 * max(1.0, ra["grad_norm"])
        assert abs(ra["update_norm"] - rb["update_norm"]) \
            < 1e-5 * max(1.0, ra["update_norm"])


def test_spmd_disable_mid_run_stops_submitting():
    """A fused step built with diagnostics compiled in keeps returning
    the diag vector after health.enable(False); the trainer must stop
    SUBMITTING it (nothing polls anymore), or the queue grows without
    bound for the rest of the run."""
    import jax
    from mxnet_tpu import optimizer as opt_mod, parallel
    engine.reset_op_cache()
    health.reset()
    health.enable(True)
    net = _build_net()
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    tr = parallel.SPMDTrainer(
        net, lambda out, y: _L(out, y).mean(),
        opt_mod.create("sgd", learning_rate=0.05), mesh)
    x, y = nd.array(_X), nd.array(_Y)
    tr.step(x, y)
    assert tr._diag_spec is not None
    health.enable(False)
    for _ in range(5):
        tr.step(x, y)
    assert len(health._queue) <= 1      # only the pre-disable entry
    health.enable(True)
    tr.step(x, y)
    health.flush()
    # the pre-disable step and the re-enabled one both consumed; the
    # disabled window recorded nothing
    assert [r["step"] for r in health.last_rows()] == [1, 7]


def test_spmd_bit_identical_on_off():
    l_on, w_on, rows_on = _train_spmd(True)
    l_off, w_off, rows_off = _train_spmd(False)
    assert l_on == l_off
    for k in w_on:
        assert (w_on[k] == w_off[k]).all(), k
    assert len(rows_on) == 6 and rows_off == []
    assert all(r["source"] == "spmd" for r in rows_on)
    # per-block grouping by structural path
    assert rows_on[0]["blocks"], rows_on[0]


def test_diag_values_match_reference():
    """The fused reductions agree with a host-side recomputation from
    the actual grads/params of an identical run."""
    engine.reset_op_cache()
    health.reset()
    health.enable(True)
    net = _build_net()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
    x, y = nd.array(_X), nd.array(_Y)
    with autograd.record():
        l = _L(net(x), y).mean()
    l.backward()
    # reference values BEFORE the update mutates params
    gs = [p.grad().asnumpy().astype("float64") for p in tr._params]
    ws = [p.data().asnumpy().astype("float64") for p in tr._params]
    rescale = 1.0 / 8
    ref_grad = math.sqrt(sum(((g * rescale) ** 2).sum() for g in gs))
    ref_param = math.sqrt(sum((w ** 2).sum() for w in ws))
    tr.step(8)
    rows = health.flush()
    assert len(rows) == 1
    r = rows[0]
    assert abs(r["loss"] - float(l.asnumpy())) < 1e-6
    assert abs(r["grad_norm"] - ref_grad) < 1e-4 * max(1.0, ref_grad)
    assert abs(r["param_norm"] - ref_param) < 1e-4 * ref_param
    assert r["nonfinite"] == 0 and r["update_norm"] > 0
    # per-block triples fold up to the global sums
    blocks = r["blocks"]
    assert len(blocks) == 2
    bsum = math.sqrt(sum(b["grad_norm"] ** 2 for b in blocks.values()))
    assert abs(bsum - r["grad_norm"]) < 1e-4 * max(1.0, r["grad_norm"])


def test_captured_one_flush_per_step_with_diagnostics():
    engine.reset_op_cache()
    health.reset()
    health.enable(True)
    engine.set_engine_type("LazyEngine")
    try:
        net = _build_net()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.05})
        x, y = nd.array(_X), nd.array(_Y)
        for _ in range(4):
            with autograd.record():
                l = _L(net(x), y).mean()
            l.backward()
            tr.step(8)
            float(l.asnumpy())
        health.flush()
        stats = engine.engine_stats()
        assert stats["step_flushes"] == 4
        assert stats["step_capture_fallbacks"] == 0
    finally:
        engine.set_engine_type("ThreadedEngine")


def test_nonfinite_counted():
    engine.reset_op_cache()
    health.reset()
    health.enable(True)
    net = _build_net()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
    x, y = nd.array(_X), nd.array(_Y)
    with autograd.record():
        l = _L(net(x), y).mean()
    l.backward()
    # poison one gradient
    g = tr._params[0].grad()
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import unwrap
    tr._params[0]._nd._grad._data = unwrap(g) * jnp.float32("nan")
    tr.step(8)
    rows = health.flush()
    assert rows and rows[-1]["nonfinite"] > 0
    assert not math.isfinite(rows[-1]["grad_norm"])
    snap = telemetry.snapshot()
    assert snap["counters"]["health/nonfinite_steps"] >= 1


# ---------------------------------------------------------------------------
# run ledger
# ---------------------------------------------------------------------------
def test_ledger_rows_and_resume_rewind(tmp_path):
    from mxnet_tpu.health.ledger import RunLedger
    led = RunLedger(str(tmp_path), run_id="r1")
    for i in range(1, 6):
        led.append({"event": "step", "step": i, "loss": 1.0 / i})
    led.append({"event": "anomaly", "step": 4, "kind": "loss_spike"})
    assert led.resumes == 0
    # a restart restores step 2 and re-delivers 3..: the rewind must
    # drop rows >= 3 (including the anomaly at 4) before continuing
    led.append({"event": "step", "step": 3, "loss": 0.33})
    rows = led.rows()
    steps = [r["step"] for r in rows if r["event"] == "step"]
    assert steps == [1, 2, 3]
    assert not [r for r in rows if r["event"] == "anomaly"]
    assert led.resumes == 1
    # continuing appends normally
    led.append({"event": "step", "step": 4, "loss": 0.25})
    assert [r["step"] for r in led.rows()
            if r["event"] == "step"] == [1, 2, 3, 4]
    led.close()
    # reopening the same run id continues where the file left off
    led2 = RunLedger(str(tmp_path), run_id="r1")
    led2.append({"event": "step", "step": 5, "loss": 0.2})
    assert [r["step"] for r in led2.rows()
            if r["event"] == "step"] == [1, 2, 3, 4, 5]
    led2.close()


def test_ledger_torn_tail_skipped(tmp_path):
    from mxnet_tpu.health.ledger import RunLedger, read_ledger
    led = RunLedger(str(tmp_path), run_id="t")
    led.append({"event": "step", "step": 1, "loss": 1.0})
    led.close()
    with open(led.path, "a") as f:
        f.write('{"event": "step", "step": 2, "lo')   # torn tail
    rows = read_ledger(led.path)
    assert [r["step"] for r in rows] == [1]


def test_ledger_wired_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_RUN_LEDGER_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_RUN_ID", "envrun")
    health.reset()
    health.enable(True)
    try:
        _run_steps(2)
        health.flush()
        led = health.run_ledger()
        assert led is not None and led.run_id == "envrun"
        rows = led.rows()
        assert [r["step"] for r in rows if r["event"] == "step"] == [1, 2]
        assert rows[0]["run"] == "envrun"
    finally:
        health.reset()


def _run_steps(n, lr=0.05, net=None, tr=None):
    net = net or _build_net()
    tr = tr or Trainer(net.collect_params(), "sgd",
                       {"learning_rate": lr})
    x, y = nd.array(_X), nd.array(_Y)
    for _ in range(n):
        with autograd.record():
            l = _L(net(x), y).mean()
        l.backward()
        tr.step(8)
        float(l.asnumpy())
    return net, tr


# ---------------------------------------------------------------------------
# detectors
# ---------------------------------------------------------------------------
def _row(step, loss, grad=1.0, nonfinite=0):
    return {"event": "step", "step": step, "loss": loss,
            "grad_norm": grad, "nonfinite": nonfinite, "run": "u"}


def test_detector_loss_spike():
    bank = health.DetectorBank(warmup_steps=4)
    fired = []
    for i in range(1, 20):
        loss = 2.0 - 0.01 * i + (0.001 * (i % 2))
        if i == 15:
            loss = 50.0
        fired += bank.observe(_row(i, loss))
    kinds = [(a.kind, a.step) for a in fired]
    assert ("loss_spike", 15) in kinds, kinds
    assert all(k == "loss_spike" for k, _s in kinds)


def test_detector_grad_explosion():
    bank = health.DetectorBank(warmup_steps=4, grad_jump=10.0)
    fired = []
    for i in range(1, 20):
        grad = 1.0 + 0.02 * ((i % 3) - 1)
        if i == 12:
            grad = 500.0
        fired += bank.observe(_row(i, 2.0 - 0.01 * i, grad=grad))
    assert ("grad_explosion", 12) in [(a.kind, a.step) for a in fired]


def test_detector_plateau_and_rearm():
    bank = health.DetectorBank(warmup_steps=4, plateau_window=10,
                               plateau_rel_eps=1e-3)
    fired = []
    # decays to 1.0 by step 8, then dead flat: the loss EWMA needs ~50
    # more steps to settle within the window epsilon, then plateau must
    # fire exactly ONCE for the whole flat stretch (armed-once contract)
    for i in range(1, 140):
        loss = 1.0 if i > 8 else 2.0 - 0.1 * i
        fired += bank.observe(_row(i, loss))
    kinds = [a.kind for a in fired]
    assert kinds.count("plateau") == 1, kinds


def test_detector_nonfinite_streak():
    bank = health.DetectorBank(nonfinite_streak=3)
    fired = []
    for i in range(1, 12):
        nf = 1 if 5 <= i <= 8 else 0
        loss = float("nan") if nf else 1.5
        fired += bank.observe(_row(i, loss, nonfinite=nf))
    kinds = [(a.kind, a.step) for a in fired]
    assert ("nonfinite_streak", 7) in kinds
    assert len([k for k, _s in kinds if k == "nonfinite_streak"]) == 1


def test_detector_divergence():
    bank = health.DetectorBank(warmup_steps=4, divergence_patience=5,
                               divergence_factor=2.0)
    fired = []
    for i in range(1, 40):
        loss = 1.0 + 0.2 * max(0, i - 10)   # steady rise after step 10
        fired += bank.observe(_row(i, loss))
    assert "divergence" in [a.kind for a in fired]


def test_detectors_clean_lr_decay_run_flags_nothing():
    """The false-positive referee: a routine decaying-loss run with a
    decaying LR schedule must not trip any detector."""
    bank = health.DetectorBank()
    fired = []
    for i in range(1, 120):
        loss = 0.5 + 1.5 * (0.98 ** i) + 0.004 * ((i * 7) % 5 - 2)
        grad = 0.5 + 0.3 * (0.99 ** i) + 0.01 * ((i * 3) % 4 - 1.5)
        fired += bank.observe(_row(i, loss, grad=grad))
    assert fired == [], [(a.kind, a.step) for a in fired]


def test_anomalies_emitted_to_every_surface(tmp_path):
    health.reset()
    health.enable(True)
    health.set_run_ledger(str(tmp_path), run_id="a")
    seen = []
    health.on_anomaly(seen.append)
    bank = health.set_detector_bank(health.DetectorBank(warmup_steps=3))
    net, tr = _run_steps(6)
    # inject a loss spike through the real pipeline: a huge LR for one
    # step blows the next step's loss up
    tr.set_learning_rate(1000.0)
    _run_steps(1, net=net, tr=tr)
    tr.set_learning_rate(0.05)
    _run_steps(3, net=net, tr=tr)
    health.flush()
    led_rows = health.run_ledger().rows()
    anom_rows = [r for r in led_rows if r.get("event") == "anomaly"]
    assert anom_rows, "no anomaly reached the ledger"
    assert seen, "the opt-in callback never fired"
    snap = telemetry.snapshot()
    assert snap["counters"]["health/anomalies"] >= 1
    # flight recorder: the anomaly span rides the ring
    spans = [s for s in telemetry.flight_recorder()
             if s["phase"] == "anomaly"]
    assert spans and spans[0]["args"]["anomaly"] in (
        "loss_spike", "grad_explosion", "divergence")
    assert bank.open_anomalies()


# ---------------------------------------------------------------------------
# Monitor under the lazy engine (the paper-API satellite)
# ---------------------------------------------------------------------------
def _monitor_run(mode, steps=3):
    from mxnet_tpu.monitor import Monitor
    engine.reset_op_cache()
    engine.set_engine_type(
        "LazyEngine" if mode == "captured" else "ThreadedEngine")
    try:
        net = _build_net()
        mon = Monitor(1, pattern=".*", monitor_all=True).install(net)
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.05})
        x, y = nd.array(_X), nd.array(_Y)
        out = []
        for _ in range(steps):
            mon.tic()
            with autograd.record():
                l = _L(net(x), y).mean()
            l.backward()
            tr.step(8)
            out.append(mon.toc())
        stats = engine.engine_stats()
        return out, stats
    finally:
        engine.set_engine_type("ThreadedEngine")


def test_monitor_captured_step_integrity():
    """Monitor.install under the lazy engine must not fragment the
    one-program captured step: one step_flush per step, stats fused in
    as extra outputs — and the values must match eager mode."""
    cap_out, cap_stats = _monitor_run("captured")
    eager_out, _ = _monitor_run("eager")
    assert cap_stats["step_flushes"] == 3, cap_stats
    # every monitored tensor produced a stat, none failed
    for step_rows in cap_out:
        assert step_rows and not any("failed" in s for _i, _n, s in
                                     step_rows)
    # same tensor names, same values as reference eager semantics
    for cap_rows, eag_rows in zip(cap_out, eager_out):
        cd = dict((n, v) for _i, n, v in cap_rows)
        ed = dict((n, v) for _i, n, v in eag_rows)
        assert set(cd) == set(ed)
        for n in cd:
            assert abs(float(cd[n]) - float(ed[n])) \
                <= 1e-5 * max(1.0, abs(float(ed[n]))), (n, cd[n], ed[n])


# ---------------------------------------------------------------------------
# crash report + ResilientStep hook
# ---------------------------------------------------------------------------
def test_crash_report_training_section(tmp_path):
    health.reset()
    health.enable(True)
    _run_steps(3)
    health.flush()
    payload = faults.crash_report_payload()
    assert payload["schema"] == 7
    sec = payload["training"]
    assert sec["schema"] == 2 and sec["enabled"]
    assert [r["step"] for r in sec["last_rows"]] == [1, 2, 3]
    assert sec["detectors"]["steps"] == 3
    assert sec["counters"]["steps_recorded"] == 3
    assert sec["open_anomalies"] == []
    # RFC-8259-safe (the /statusz federation path re-serializes it)
    json.dumps(payload["training"], default=str)


def test_resilient_step_checkpoint_on_anomaly(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager
    health.reset()
    health.enable(True)
    health.set_detector_bank(health.DetectorBank(warmup_steps=3))
    net = _build_net()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
    manager = CheckpointManager(str(tmp_path / "ck"))
    rs = faults.ResilientStep(tr, skip_nonfinite=False, manager=manager,
                              net=net, checkpoint_on_anomaly=True)
    x, y = nd.array(_X), nd.array(_Y)

    def one(lr):
        tr.set_learning_rate(lr)
        with autograd.record():
            l = _L(net(x), y).mean()
        l.backward()
        rs.step(8)
        float(l.asnumpy())

    for _ in range(6):
        one(0.05)
    assert manager.steps() == []        # observe-only until it fires
    one(2000.0)                         # the spike lands next step
    for _ in range(3):
        one(0.05)
    health.flush()
    one(0.05)                           # the post-flush step saves
    assert manager.steps(), "anomaly checkpoint never saved"
    assert faults.counters().get("anomaly_saves", 0) >= 1
    rs.close()
    # the callback deregistered: no dangling observer after close
    one(0.05)


# ---------------------------------------------------------------------------
# elastic_run kill/restart ledger contiguity (the resume referee)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_elastic_run_ledger_contiguity(tmp_path):
    from mxnet_tpu import checkpoint
    engine.reset_op_cache()
    health.reset()
    health.enable(True)
    health.set_run_ledger(str(tmp_path / "led"), run_id="contig")
    engine.set_engine_type("LazyEngine")
    try:
        net = _build_net()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.05})
        x, y = nd.array(_X), nd.array(_Y)
        manager = checkpoint.CheckpointManager(str(tmp_path / "ck"),
                                               max_to_keep=2)
        steps = 12

        def train_fn(start):
            for i in range(start if start else 1, steps + 1):
                with autograd.record():
                    l = _L(net(x), y).mean()
                l.backward()
                tr.step(8)
                float(l.asnumpy())
                if i % 3 == 0:
                    manager.save(i, net=net, trainer=tr)
            health.flush()

        plan = faults.FaultPlan.parse("trainer.step@8:transient")
        with faults.inject(plan):
            restarts = checkpoint.elastic_run(train_fn, manager, net=net,
                                              trainer=tr, backoff_s=0.0)
        assert restarts == 1
        led = health.run_ledger()
        rows = [r for r in led.rows() if r.get("event") == "step"]
        assert [r["step"] for r in rows] == list(range(1, steps + 1))
        assert led.resumes >= 1      # the rewind actually exercised
    finally:
        engine.set_engine_type("ThreadedEngine")


# ---------------------------------------------------------------------------
# tools/run_report.py
# ---------------------------------------------------------------------------
def _write_ledger(path, run, losses, anomalies=()):
    with open(path, "w") as f:
        for i, l in enumerate(losses, 1):
            f.write(json.dumps(
                {"event": "step", "run": run, "step": i, "loss": l,
                 "grad_norm": 0.1, "param_norm": 5.0,
                 "update_ratio": 1e-3, "nonfinite": 0, "lr": 0.01,
                 "steps_per_s": 10.0, "mfu": 0.4,
                 "blocks": {"dense0": {"grad_norm": 0.1,
                                       "param_norm": 5.0,
                                       "update_ratio": 1e-3}}}) + "\n")
        for step, kind in anomalies:
            f.write(json.dumps(
                {"event": "anomaly", "run": run, "step": step,
                 "kind": kind, "value": 9.9, "threshold": 1.0,
                 "message": "m"}) + "\n")


def test_run_report_render_and_baseline(tmp_path, capsys):
    rr = _load_tool("run_report")
    base = [2.0 * (0.95 ** i) for i in range(40)]
    spiked = list(base)
    for i in range(20, 40):
        spiked[i] = base[i] + 5.0       # diverges at step 21
    a = str(tmp_path / "run_a.jsonl")
    b = str(tmp_path / "run_b.jsonl")
    _write_ledger(a, "a", spiked, anomalies=[(21, "loss_spike")])
    _write_ledger(b, "b", base)
    rc = rr.main([a, "--baseline", b, "--blocks"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "DIVERGED" in out and "first divergent step: 21" in out
    assert "loss_spike" in out and "dense0" in out
    # contiguity figures render
    assert "duplicated 0" in out and "missing 0" in out
    # a run against itself is consistent
    rc = rr.main([b, "--baseline", b, "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["comparison"]["verdict"] == "consistent"
    assert payload["summary"]["duplicated_steps"] == 0


def test_run_report_contiguity_detects_damage(tmp_path):
    rr = _load_tool("run_report")
    p = str(tmp_path / "run_d.jsonl")
    _write_ledger(p, "d", [1.0, 0.9, 0.8, 0.7])
    with open(p, "a") as f:
        f.write(json.dumps({"event": "step", "run": "d", "step": 2,
                            "loss": 0.95}) + "\n")  # duplicate
        f.write(json.dumps({"event": "step", "run": "d", "step": 7,
                            "loss": 0.5}) + "\n")   # gap 5-6
    steps, _ = rr.split_rows(rr.load_rows(p))
    dup, missing = rr.contiguity(steps)
    assert dup == 1 and missing == 2


# ---------------------------------------------------------------------------
# gates + metrics hygiene
# ---------------------------------------------------------------------------
def test_env_gate_off_records_nothing(monkeypatch):
    monkeypatch.setenv("MXNET_STEP_DIAGNOSTICS", "0")
    health.reset()      # drop the process override so the env decides
    assert not health.enabled()
    _run_steps(2)
    assert health.flush() == []
    assert health.last_rows() == []


def test_health_metrics_registered_and_snapshot():
    snap = telemetry.snapshot()
    for name in ("health/steps_recorded", "health/anomalies",
                 "health/ledger_rows"):
        assert name in snap["counters"], name
    for name in ("health/pending_diags", "health/open_anomalies",
                 "health/last_loss"):
        assert name in snap["gauges"], name
    health.enable(True)
    _run_steps(2)
    health.flush()
    snap = telemetry.snapshot()
    assert snap["counters"]["health/steps_recorded"] == 2
    assert snap["gauges"]["health/last_loss"] > 0
    # prometheus exposition stays parseable with the new family
    text = telemetry.prometheus_text()
    assert "mxnet_health_steps_recorded" in text
