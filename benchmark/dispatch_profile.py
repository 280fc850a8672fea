"""Host-side dispatch cost profiles.

Three instruments:

* **elementwise-chain dispatch** (default; ``--engine {eager,lazy}``) —
  wall time to issue a chain of eager elementwise ops, the unit the
  LazyEngine amortizes (docs/ENGINE.md).  ``eager`` measures the un-jitted
  per-op baseline (op-executable cache disabled), ``lazy`` records the
  chain into a bulk segment flushed as one fused jit program.  Results are
  appended to ``benchmark/BENCH_DETAILS.json`` through the atomic
  ``util.write_json_records`` writer (``--no-record`` to skip).

* **whole-step capture referee** (``--engine fused-step``) — one full
  eager gluon training step (forward under ``autograd.record()``,
  ``backward()``, ``Trainer.step()``, loss read) measured three ways on
  the same net/data/optimizer: op-by-op eager dispatch, LazyEngine
  whole-step capture (ONE fused executable per step — docs/ENGINE.md),
  and ``SPMDTrainer``'s hand-fused step as the ceiling.  The net is a
  dense chain sized by ``--model``: ``base`` matches BERT-base's hidden
  size (768) and per-step dense-op count (48); ``--fs-units/--fs-layers``
  override.  Asserts the captured loss is bit-identical to eager.

* **SPMDTrainer.step phase decomposition** (``--model base|large`` with
  the default engine) — the original instrument: BERT has ~390 parameter
  arrays; round 2 measured ~8.4 s/step wall against ~80 ms device time on
  this host.  Times each phase of ``step()`` to find where the host time
  goes.

Usage:
    python benchmark/dispatch_profile.py --engine lazy
    python benchmark/dispatch_profile.py --engine eager --chain-ops 60
    python benchmark/dispatch_profile.py --engine fused-step --model base
    python benchmark/dispatch_profile.py --model large --steps 5
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DETAILS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_DETAILS.json")



def _record_replace(records):
    """Append records to BENCH_DETAILS.json replacing by EXACT metric
    name (the serve_bench convention) — rerunning a mode must not stack
    duplicate records."""
    from mxnet_tpu import util
    names = {r["metric"] for r in records}
    util.write_json_records(
        _DETAILS_PATH, records, append=False,
        keep=lambda r: r.get("metric") not in names)


def bench_zero(level="sweep", steps=12, record=True):
    """The ZeRO-ladder referee (``--zero {1,2,3,sweep}``): run the
    BERT-tiny zero1/zero2/zero3 sweep on the pinned 8-device virtual
    mesh (``mxnet_tpu.parallel.dryrun.zero_sweep_guarded``) and record
    the ``parallel_zero*`` evidence chain — per-device param+grad+state
    bytes and paired step wall per level, the byte-shrink percentages
    vs zero1, the measured collective-overlap fraction, and the
    ``run_report --baseline`` convergence verdict (zero3 trajectory vs
    zero1).  A numeric ``level`` prints and records only that level's
    rows (the sweep still runs whole: the walls are paired and the
    shrink is relative to zero1 by construction).

    Gated by ``tools/perf_sentinel.py`` bars: shrink >= 40% (zero2) /
    >= 60% (zero3), overlap >= 5%, convergence ratio <= 1.0 — the
    referee chain docs/PARALLEL.md "Pod-scale training" cites.
    """
    import json as _json
    import tempfile

    from mxnet_tpu.parallel.dryrun import zero_sweep_guarded

    ledger_dir = tempfile.mkdtemp(prefix="zero_sweep_ledger_")
    out = zero_sweep_guarded(steps=steps, ledger_dir=ledger_dir)
    dp = out["dp"]

    rr = _load_tool("run_report")
    rows = {z: rr.load_rows(out["ledgers"][z]) for z in (1, 3)}
    sp = {z: rr.split_rows(rows[z]) for z in (1, 3)}
    conv = rr.compare(sp[3][0], sp[1][0], sp[3][1], sp[1][1])
    conv_ratio = conv["mean_abs_loss_delta"] / conv["bar"]

    want = (1, 2, 3) if level == "sweep" else (int(level),)
    recs = []
    for z in want:
        lv = out["levels"][z]
        print(f"zero{z}: per-device {lv['total_mb']:.3f} MB "
              f"(params {lv['param_mb']:.3f} + grads {lv['grad_mb']:.3f}"
              f" + state {lv['state_mb']:.3f}), "
              f"step wall {lv['wall_ms']:.2f} ms"
              + (f", overlap {lv['overlap_pct']:.1f}% of "
                 f"{lv['collective_ms']:.2f} ms collective"
                 if "overlap_pct" in lv else ""), flush=True)
        recs.append({
            "metric": f"parallel_zero{z}_per_device_mb",
            "value": round(lv["total_mb"], 4), "unit": "MB",
            "vs_baseline": None,
            "extra": {"param_mb": round(lv["param_mb"], 4),
                      "grad_mb": round(lv["grad_mb"], 4),
                      "state_mb": round(lv["state_mb"], 4),
                      "dp": dp, "basis": "none"},
            "basis_note": "per-device param+grad+optimizer-state bytes, "
                          "BERT-tiny SGD-momentum on the pinned "
                          "8-device virtual mesh; params/states from "
                          "addressable shards, grads analytic from the "
                          "pinned per-grad shardings",
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S")})
        recs.append({
            "metric": f"parallel_zero{z}_step_wall_ms",
            "value": round(lv["wall_ms"], 3), "unit": "ms_per_step",
            "vs_baseline": None,
            "extra": {"dp": dp, "steps": steps, "basis": "none"},
            "basis_note": "median wall of interleaved z1/z2/z3 step "
                          "triples (host drift cancels pairwise); "
                          "virtual CPU mesh, so absolute values are "
                          "host-speed-bound — sentinel band 75%",
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S")})
    if level == "sweep":
        for z in (2, 3):
            recs.append({
                "metric": f"parallel_zero{z}_bytes_shrink_pct",
                "value": round(out[f"zero{z}_shrink_pct"], 2),
                "unit": "pct", "vs_baseline": None,
                "extra": {"dp": dp,
                          "zero1_mb": round(out["levels"][1]["total_mb"],
                                            4),
                          "basis": "none"},
                "basis_note": "per-device (param+grad+state) bytes "
                              "shrink vs zero1 at dp=8; sentinel floor "
                              f"{'40' if z == 2 else '60'}%",
                "ts": time.strftime("%Y-%m-%dT%H:%M:%S")})
        lv2 = out["levels"][2]
        recs.append({
            "metric": "parallel_collective_overlap_pct",
            "value": round(out["overlap_pct"], 2), "unit": "pct",
            "vs_baseline": None,
            "extra": {"zero2_collective_ms":
                          round(lv2["collective_ms"], 3),
                      "zero2_hidden_ms": round(lv2["hidden_ms"], 3),
                      "zero3_overlap_pct":
                          round(out["levels"][3].get("overlap_pct", 0.0),
                                2),
                      "basis": "none"},
            "basis_note": "paired-program referee: hidden = clamp("
                          "W_zero1 + C - W_zero2, 0, C) per interleaved "
                          "step pair, C = serialized standalone wall of "
                          "the real reduce-scatter+all-gather volume "
                          "(shard_map psum_scatter/all_gather chain)",
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S")})
        recs.append({
            "metric": "parallel_zero3_convergence_ratio",
            "value": round(conv_ratio, 6), "unit": "ratio",
            "vs_baseline": None,
            "extra": {"verdict": conv["verdict"],
                      "mean_abs_loss_delta":
                          conv["mean_abs_loss_delta"],
                      "noise_bar": conv["bar"],
                      "common_steps": conv["common_steps"],
                      "basis": "none"},
            "basis_note": "run_report --baseline: zero3 ledger vs zero1 "
                          "ledger, mean |loss delta| over the noise-"
                          "aware bar (<1 = convergence unchanged)",
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S")})
    print(f"zero2 shrink {out['zero2_shrink_pct']:.2f}% "
          f"zero3 shrink {out['zero3_shrink_pct']:.2f}% "
          f"overlap {out['overlap_pct']:.1f}% "
          f"convergence {conv['verdict']} "
          f"(ratio {conv_ratio:.2e})", flush=True)
    if record:
        _record_replace(recs)
        print(f"recorded {len(recs)} parallel_zero* records -> "
              f"{_DETAILS_PATH}", flush=True)
    return out


def bench_chain(engine_mode, n_ops=60, side=64, reps=30, record=True):
    """Median wall time to issue (and flush, for lazy) an ``n_ops``-long
    eager elementwise chain — the host-dispatch unit the engine amortizes.
    The sync (``wait_to_read``) is outside the timed window in both modes;
    the lazy window includes the bulk-exit flush dispatch."""
    import numpy as onp
    from mxnet_tpu import nd, engine, util

    a = nd.array(onp.random.RandomState(0).randn(side, side)
                 .astype("float32"))
    b = nd.array(onp.random.RandomState(1).randn(side, side)
                 .astype("float32"))

    def chain(x):
        # mixed single-primitive and compound elementwise ops, 4 per round
        for _ in range(n_ops // 4):
            x = nd.gelu(x * 0.999 + b).tanh()
        return x

    def timed(run):
        run().wait_to_read()
        run().wait_to_read()          # second warmup stabilizes cache keys
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = run()
            ts.append(time.perf_counter() - t0)
            out.wait_to_read()
        return sorted(ts)[reps // 2]

    if engine_mode == "lazy":
        def run():
            with engine.bulk(n_ops + 8):
                return chain(a)
        wall = timed(run)
    else:
        with engine.op_cache_scope(False):
            wall = timed(lambda: chain(a))

    n = (n_ops // 4) * 4
    print(f"elementwise-chain dispatch [{engine_mode}]: {n} ops "
          f"({side}x{side}) -> {wall * 1e3:.3f} ms/chain, "
          f"{wall / n * 1e6:.1f} us/op", flush=True)
    if record:
        _record_replace([{
            "metric": f"dispatch_chain_{engine_mode}",
            "value": round(wall * 1e3, 4),
            "unit": "ms_per_chain",
            "vs_baseline": None,
            "extra": {"n_ops": n, "side": side, "reps": reps,
                      "us_per_op": round(wall / n * 1e6, 2),
                      "engine": engine_mode, "basis": "none"},
            "basis_note": "median wall time to issue one eager "
                          "elementwise chain; sync excluded; lazy "
                          "includes the bulk-exit flush dispatch",
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }])
        print(f"recorded dispatch_chain_{engine_mode} -> {_DETAILS_PATH}",
              flush=True)
    return wall


def _load_tool(name):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _print_trace_report(trace_file, steps):
    """Fold the just-dumped step-phase trace into the per-step table and
    print the wall-vs-phase-sum coverage the referee checks."""
    tr = _load_tool("trace_report")
    rep = tr.report_file(trace_file, last=steps)
    print(f"\nstep-phase trace -> {trace_file}")
    print(tr.format_table(rep))
    return rep


def bench_record_floor(n_ops=200, reps=15, record=True):
    """The python record floor: microseconds to RECORD one op into a lazy
    segment (the flush runs outside the timed window) — the per-op unit
    of the ~15-20 ms/step captured-step python cost the ROADMAP names.
    Median over ``reps`` chains of ``n_ops`` mixed elementwise ops."""
    import numpy as onp
    from mxnet_tpu import nd, engine, util

    a = nd.array(onp.random.RandomState(0).randn(64, 64).astype("float32"))
    b = nd.array(onp.random.RandomState(1).randn(64, 64).astype("float32"))

    def run_once():
        with engine.bulk(n_ops + 16):
            x = a
            t0 = time.perf_counter()
            for _ in range(n_ops // 4):
                x = nd.gelu(x * 0.999 + b).tanh()
            t1 = time.perf_counter()
        x.wait_to_read()
        return (t1 - t0) / ((n_ops // 4) * 4) * 1e6

    for _ in range(3):
        run_once()
    vals = sorted(run_once() for _ in range(reps))
    us = vals[len(vals) // 2]
    print(f"record floor: {us:.2f} us/op recorded "
          f"({(n_ops // 4) * 4} ops/chain, {reps} reps, flush excluded)",
          flush=True)
    if record:
        _record_replace([{
            "metric": "record_floor_us_per_op",
            "value": round(us, 2), "unit": "us_per_op",
            "vs_baseline": None,
            "extra": {"n_ops": (n_ops // 4) * 4, "reps": reps,
                      "basis": "none"},
            "basis_note": "median wall to RECORD one op into a lazy "
                          "segment, flush outside the timed window — the "
                          "per-op python record floor of captured steps "
                          "(docs/ENGINE.md)",
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }])
        print(f"recorded record_floor_us_per_op -> {_DETAILS_PATH}",
              flush=True)
    return us


def bench_fused_step(model="base", steps=20, batch=8, units=0, layers=0,
                     record=True, trace=None, overhead_check=False,
                     overhead_pairs=0, donate=True,
                     cost_overhead_check=False):
    """Referee: median wall per eager-gluon training step, op-by-op vs
    whole-step capture vs SPMDTrainer's fused step, on one shared
    net/data/optimizer.  Loss is read (synced) every step in every mode —
    the honest common pattern, and the captured mode's materialization
    boundary."""
    import tempfile
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, engine, util, autograd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import nn, loss as gloss, Trainer

    # a FRESH ProgramCache root for the referee: warm-loaded (deserialized)
    # executables report memory_analysis without the alias table, which
    # would misread a donating program's peak on the second run.
    # try/finally (not tail code): a mid-benchmark failure must not leave
    # the process pointed at the throwaway cache root, and the tempdir is
    # removed either way.
    import shutil
    saved_cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_tmp = tempfile.mkdtemp(prefix="mxnet-fused-step-bench-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_tmp
    # pin the health diagnostics tail OFF for the whole referee: the
    # committed fused_step_*/telemetry_overhead_*/cost_overhead_*
    # trajectory isolates dispatch amortization, and on this
    # bandwidth-bound batch-8 config the diag tail's param-pass
    # reductions would dominate the measured quantity (the diagnostics
    # have their own paired record — health_overhead_captured_base,
    # benchmark/health_bench.py)
    from mxnet_tpu import health as mxhealth
    mxhealth.enable(False)
    try:
        return _bench_fused_step_impl(
            model, steps, batch, units, layers, record, trace,
            overhead_check, overhead_pairs, donate, cost_overhead_check)
    finally:
        mxhealth.enable(None)
        if saved_cache_dir is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = saved_cache_dir
        shutil.rmtree(cache_tmp, ignore_errors=True)


def _bench_fused_step_impl(model, steps, batch, units, layers, record,
                           trace, overhead_check, overhead_pairs, donate,
                           cost_overhead_check=False):
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, engine, util, autograd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import nn, loss as gloss, Trainer

    # (layers, units): dense-op count and hidden size matched to the BERT
    # config — base: 12 encoder layers x 4 dense matmuls = 48 dense ops at
    # 768 hidden; large: 24 x 4 = 96 at 1024.  Attention/layernorm ops are
    # absent, so absolute ms is not a full BERT step, but the
    # dispatch-vs-device balance the referee judges is representative.
    dims = dict(base=(48, 768), large=(96, 1024))
    n_layers, n_units = dims[model]
    if layers:
        n_layers = layers
    if units:
        n_units = units

    rng = onp.random.RandomState(0)
    X = rng.randn(batch, n_units).astype("float32")
    Y = rng.randint(0, 10, (batch,)).astype("float32")

    def build():
        mx.random.seed(0)
        net = nn.HybridSequential()
        for _ in range(n_layers):
            net.add(nn.Dense(n_units, activation="relu"))
        net.add(nn.Dense(10))
        net.initialize()
        return net

    L = gloss.SoftmaxCrossEntropyLoss()

    from mxnet_tpu import costs as mxcosts
    from mxnet_tpu import memory as mxmem

    def _step_seg_peak():
        """Largest whole-step executable peak recorded in the per-program
        ledger during the loop (XLA buffer assignment: arg+out+temp-alias
        — donation shows up as alias bytes shrinking the peak)."""
        segs = [e for e in mxmem.ledger() if e["kind"] == "step_segment"]
        return max((e["peak_bytes"] for e in segs), default=None)

    def gluon_loop(mode, trace_file=None, donate_mode=None):
        saved_env = os.environ.get("MXNET_STEP_DONATE")
        if mode == "captured" and donate_mode is not None:
            os.environ["MXNET_STEP_DONATE"] = "1" if donate_mode else "0"
        try:
            return _gluon_loop_body(mode, trace_file)
        finally:
            # finally, not tail code: a failing flush mid-benchmark must
            # not leave the process with donation forced on/off
            if saved_env is None:
                os.environ.pop("MXNET_STEP_DONATE", None)
            else:
                os.environ["MXNET_STEP_DONATE"] = saved_env

    def _gluon_loop_body(mode, trace_file):
        engine.reset_op_cache()
        mxmem.reset()
        mxcosts.reset()
        engine.set_engine_type(
            "LazyEngine" if mode == "captured" else "ThreadedEngine")
        net = build()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.01, "momentum": 0.9})
        x, y = nd.array(X), nd.array(Y)

        def one_step():
            with autograd.record():
                l = L(net(x), y).mean()
            l.backward()
            tr.step(batch)
            return float(l.asnumpy())

        for _ in range(3):           # warmup: compiles + cache keys settle
            last = one_step()
        if trace_file:
            from mxnet_tpu import profiler
            profiler.set_config(filename=trace_file)
            profiler.start()
        ts = []
        for _ in range(steps):
            t0 = time.perf_counter()
            last = one_step()
            ts.append(time.perf_counter() - t0)
        if trace_file:
            from mxnet_tpu import profiler
            profiler.stop()
            profiler.dump()
        engine.set_engine_type("ThreadedEngine")
        peak = _step_seg_peak()
        return sorted(ts)[len(ts) // 2], last, peak

    def spmd_loop():
        engine.set_engine_type("ThreadedEngine")
        net = build()
        mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
        tr = parallel.SPMDTrainer(
            net, lambda out, y: L(out, y).mean(),
            opt.create("sgd", learning_rate=0.01, momentum=0.9), mesh)
        x, y = nd.array(X), nd.array(Y)
        for _ in range(3):
            last = float(tr.step(x, y).asnumpy())
        ts = []
        for _ in range(steps):
            t0 = time.perf_counter()
            last = float(tr.step(x, y).asnumpy())
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2], last

    eager_ms, eager_loss, _ = gluon_loop("eager")
    cap_ms, cap_loss, cap_peak = gluon_loop("captured", trace_file=trace,
                                            donate_mode=donate)
    # snapshot the captured loop's cost ledger + attribution tables NOW —
    # the later loops reset both (per-loop isolation)
    cost_payload = mxcosts.report_payload()
    nod_ms = nod_loss = nod_peak = None
    if donate:
        # the donation referee needs BOTH peaks: rerun captured with
        # donation off on the same net/data (ledger reset per loop)
        nod_ms, nod_loss, nod_peak = gluon_loop("captured",
                                                donate_mode=False)
    spmd_ms, spmd_loss = spmd_loop()

    bit_identical = eager_loss == cap_loss
    speedup = eager_ms / cap_ms
    vs_spmd = cap_ms / spmd_ms
    dense_layers = n_layers + 1   # hidden Dense chain + the output head
    print(f"fused-step referee [{model}: {n_layers}x Dense({n_units}), "
          f"batch {batch}, {steps} timed steps, loss synced every step, "
          f"donate={'on' if donate else 'off'}]")
    print(f"  eager gluon (op-by-op) : {eager_ms*1e3:9.2f} ms/step")
    print(f"  captured whole-step    : {cap_ms*1e3:9.2f} ms/step "
          f"({speedup:.2f}x over eager)")
    print(f"  SPMDTrainer fused step : {spmd_ms*1e3:9.2f} ms/step "
          f"(captured = {vs_spmd:.2f}x of fused)")
    print(f"  final loss eager={eager_loss!r} captured={cap_loss!r} "
          f"bit_identical={bit_identical} (spmd={spmd_loss!r})")
    if donate and cap_peak and nod_peak:
        drop = 100.0 * (1.0 - cap_peak / nod_peak)
        dms = 100.0 * (cap_ms / nod_ms - 1.0)
        print(f"  donation: step-program peak {nod_peak / 2**20:.2f} -> "
              f"{cap_peak / 2**20:.2f} MB ({drop:+.1f}% peak) at "
              f"{dms:+.1f}% step_ms (donated loss bit-identical: "
              f"{cap_loss == nod_loss})")

    # -- compute-cost observability (mxnet_tpu.costs): per-step MFU +
    # the per-block cost table of the ONE captured step program --------
    cr = _load_tool("cost_report")
    step_entries = [e for e in (cost_payload.get("ledger") or {})
                    .get("hottest", ()) if e.get("kind") == "step_segment"]
    step_entry = step_entries[0] if step_entries else None
    attr = None
    for t in cost_payload.get("attributions") or ():
        if t.get("kind") != "step_segment":
            continue
        if attr is None or (t.get("attributed_flops") or 0) > \
                (attr.get("attributed_flops") or 0):
            attr = t
    peak = cost_payload.get("peak") or {}
    step_mfu = None
    if step_entry and peak.get("flops") and cap_ms:
        # the honest per-step figure: program flops over the MEDIAN step
        # wall (the ledger's last/best_mfu divide by the flush/dispatch
        # wall — an upper bound on async backends)
        step_mfu = step_entry["flops"] / cap_ms / peak["flops"]
        print(f"  per-step MFU (captured) : {step_mfu:.4f} at the median "
              f"step wall ({step_entry['flops'] / 1e9:.3f} GFLOP/step vs "
              f"peak {peak['flops'] / 1e12:.1f} TFLOP/s "
              f"[{peak.get('source', 'unresolved')}], "
              f"flop_source=cost_analysis; flush-wall mfu last "
              f"{step_entry['last_mfu']})")
    print("\nper-block cost table (captured step):")
    print(cr.format_blocks(attr))
    cost_cov = (attr or {}).get("coverage")
    if cost_cov:
        print(f"block-flops sum = {100.0 * cost_cov:.1f}% of the "
              f"program's cost_analysis() total (referee: within 10%)")
    if record:
        base_note = ("median wall per full train step incl. per-step loss "
                     "sync; dense chain matching BERT-%s's hidden size and "
                     "per-step dense-op count (no attention/layernorm, so "
                     "not a full BERT step — the dispatch-vs-device "
                     "balance is the refereed quantity)" % model)
        ts = time.strftime("%Y-%m-%dT%H:%M:%S")
        _record_replace([
            {"metric": f"fused_step_eager_{model}",
             "value": round(eager_ms * 1e3, 3), "unit": "ms_per_step",
             "vs_baseline": None,
             "extra": {"layers": n_layers, "units": n_units, "batch": batch,
                       "steps": steps, "dense_layers": dense_layers,
                       "basis": "none"},
             "basis_note": base_note + "; eager baseline is the current "
                           "eager tape, which executes each op's plain "
                           "program in addition to the vjp primal for "
                           "capture bit-parity (docs/ENGINE.md) — the "
                           "pre-PR un-jitted Dense dispatch was slower "
                           "still", "ts": ts},
            {"metric": f"fused_step_captured_{model}",
             "value": round(cap_ms * 1e3, 3), "unit": "ms_per_step",
             "vs_baseline": round(speedup, 2),
             "extra": {"layers": n_layers, "units": n_units, "batch": batch,
                       "steps": steps,
                       "loss_bit_identical_vs_eager": bool(bit_identical),
                       "basis": f"fused_step_eager_{model}"},
             "basis_note": base_note, "ts": ts},
            {"metric": f"fused_step_spmd_{model}",
             "value": round(spmd_ms * 1e3, 3), "unit": "ms_per_step",
             "vs_baseline": round(vs_spmd, 2),
             "extra": {"layers": n_layers, "units": n_units, "batch": batch,
                       "steps": steps,
                       "captured_over_fused_ratio": round(vs_spmd, 3),
                       "basis": f"fused_step_captured_{model}"},
             "basis_note": "SPMDTrainer hand-fused step on the same "
                           "net/data/optimizer — the ceiling the captured "
                           "step is refereed against (~1.2x target; "
                           "observed 1.2-1.4x across runs on the shared "
                           "2-core CPU host; the remaining gap is python "
                           "record cost — a real accelerator's step time "
                           "dwarfs it)",
             "ts": ts},
        ])
        if donate and cap_peak and nod_peak:
            _record_replace([{
                "metric": f"fused_step_donated_{model}",
                "value": round(cap_ms * 1e3, 3), "unit": "ms_per_step",
                "vs_baseline": round(cap_ms / nod_ms, 3),
                "extra": {
                    "layers": n_layers, "units": n_units, "batch": batch,
                    "steps": steps,
                    "peak_mb_donated": round(cap_peak / 2**20, 2),
                    "peak_mb_nodonate": round(nod_peak / 2**20, 2),
                    "peak_drop_pct": round(
                        100.0 * (1.0 - cap_peak / nod_peak), 1),
                    "step_ms_nodonate": round(nod_ms * 1e3, 3),
                    "loss_bit_identical_vs_nodonate":
                        bool(cap_loss == nod_loss),
                    "loss_bit_identical_vs_eager": bool(bit_identical),
                    "basis": f"fused_step_captured_{model}"},
                "basis_note": "captured whole-step with param/optimizer-"
                              "state buffer donation (MXNET_STEP_DONATE, "
                              "default on) vs the same loop with donation "
                              "off: peak_mb_* is the step executable's "
                              "XLA buffer-assignment peak from the "
                              "per-program memory ledger "
                              "(memory.record_program; donation appears "
                              "as alias bytes), step ms is the median "
                              "wall — the acceptance bar is peak down "
                              ">=20% at equal step_ms (docs/ENGINE.md "
                              "'Memory-lean fused steps')",
                "ts": ts,
            }])
            print(f"recorded fused_step_donated_{model} -> "
                  f"{_DETAILS_PATH}", flush=True)
        if cost_cov and step_entry:
            _record_replace([{
                "metric": f"cost_attribution_coverage_{model}",
                "value": round(cost_cov, 4), "unit": "fraction_of_total",
                "vs_baseline": None,
                "extra": {
                    "layers": n_layers, "units": n_units, "batch": batch,
                    "attributed_gflops": round(
                        attr["attributed_flops"] / 1e9, 4),
                    "total_gflops": round(attr["total_flops"] / 1e9, 4),
                    "step_mfu_at_median_wall":
                        round(step_mfu, 4) if step_mfu else None,
                    "flush_wall_mfu_last": step_entry["last_mfu"],
                    "peak_flops": peak.get("flops"),
                    "peak_source": peak.get("source"),
                    "flop_source": "cost_analysis",
                    "top_blocks": [
                        [b["block"], round(b["flops"] / 1e9, 4)]
                        for b in (attr.get("blocks") or [])[:5]],
                    "basis": "none"},
                "basis_note": "per-block flop attribution of the ONE "
                              "captured step program (mxnet_tpu.costs "
                              "jaxpr-walk estimates, VJP ops "
                              "CSE-corrected) summed over blocks, as a "
                              "fraction of the program's own "
                              "cost_analysis() total — the acceptance "
                              "referee is within 10% of 1.0; "
                              "step_mfu_at_median_wall divides program "
                              "flops by the median step wall (the "
                              "honest figure), flush_wall_mfu_last by "
                              "the flush/dispatch wall (an upper bound "
                              "on async backends) "
                              "(docs/OBSERVABILITY.md 'Compute-cost "
                              "observability')",
                "ts": ts,
            }])
            print(f"recorded cost_attribution_coverage_{model} -> "
                  f"{_DETAILS_PATH}", flush=True)
        print(f"recorded fused_step_* -> {_DETAILS_PATH}", flush=True)

    out = {"eager_ms": eager_ms, "captured_ms": cap_ms, "spmd_ms": spmd_ms,
           "speedup": speedup, "vs_spmd": vs_spmd,
           "bit_identical": bit_identical,
           "peak_donated": cap_peak, "peak_nodonate": nod_peak,
           "cost_coverage": cost_cov,
           "step_mfu": step_mfu,
           "cost_payload": cost_payload}

    if trace:
        rep = _print_trace_report(trace, steps)
        cov = rep["aggregate"]["mean_coverage"]
        print(f"phase-sum coverage of measured wall: {100 * cov:.1f}% "
              f"(referee target: within 10%)")
        out["trace_coverage"] = cov

    if overhead_check:
        # Always-on proof: captured-step wall with span recording on vs
        # off (MXNET_TELEMETRY=0 equivalent).  The true per-step span
        # cost is microseconds, far below this host's cgroup-throttling
        # step-time swings (±20% within one run; whole separate on/off
        # runs measured ±7% in BOTH directions — pure drift).  So the
        # modes are interleaved at STEP granularity inside ONE loop:
        # same compiled executable, same allocator state, adjacent
        # steps — drift cancels pairwise, and the paired median of
        # (on - off) per adjacent step pair is the recorded overhead.
        from mxnet_tpu import telemetry
        engine.reset_op_cache()
        engine.set_engine_type("LazyEngine")
        net_o = build()
        tr_o = Trainer(net_o.collect_params(), "sgd",
                       {"learning_rate": 0.01, "momentum": 0.9})
        xo, yo = nd.array(X), nd.array(Y)

        def oh_step():
            with autograd.record():
                l = L(net_o(xo), yo).mean()
            l.backward()
            tr_o.step(batch)
            return float(l.asnumpy())

        # Randomized paired design: the loop itself shows a ±5% even/odd
        # step-time periodicity (measured with telemetry ON for every
        # step — allocator/GC phase, not telemetry), so within each
        # adjacent pair the on/off ORDER is drawn from a seeded RNG;
        # any periodic artifact then flips sign randomly across pairs
        # and cancels in the median of (on - off) deltas.
        import numpy as _onp
        # SE of the trimmed mean scales 1/sqrt(pairs): per-pair deltas on
        # this host have sigma ~10-15% of a step, so ~150 pairs resolves
        # only to ~+/-1-2% while the true signal is ~40us/step (measured
        # below) — default high enough to resolve the 2% bar with margin
        pairs = overhead_pairs or max(10 * steps, 1000)
        order_rng = _onp.random.RandomState(0)
        on_ts, off_ts = [], []
        try:
            for _ in range(3):
                oh_step()               # warmup: compile + cache keys
            for _i in range(pairs):
                first_on = bool(order_rng.randint(2))
                for mode_on in ((True, False) if first_on
                                else (False, True)):
                    telemetry.enable(mode_on)
                    t0 = time.perf_counter()
                    oh_step()
                    dt = time.perf_counter() - t0
                    (on_ts if mode_on else off_ts).append(dt)
        finally:
            telemetry.enable(None)
            engine.set_engine_type("ThreadedEngine")

        # Noise-free corroboration: time the exact telemetry call
        # sequence one captured step emits (boundary + 3 phase scopes +
        # flush span + sync span), on vs off, isolated from the step's
        # compute — this pins the TRUE absolute cost the paired estimate
        # above measures through ~10-15% per-step host noise.
        def span_seq():
            telemetry.step_boundary("train")
            with telemetry.phase("forward"):
                pass
            with telemetry.phase("backward"):
                pass
            with telemetry.phase("optimizer_update"):
                pass
            telemetry.add_span("step_flush", 0, 100.0, ops=64,
                               cache_hit=True, program="microbench")
            telemetry.add_span("sync", 0, 100.0)

        def span_cost_us():
            for _ in range(1000):
                span_seq()
            n = 20000
            t0 = time.perf_counter_ns()
            for _ in range(n):
                span_seq()
            return (time.perf_counter_ns() - t0) / n / 1000.0

        try:
            telemetry.enable(True)
            call_on_us = span_cost_us()
            telemetry.enable(False)
            call_off_us = span_cost_us()
        finally:
            telemetry.enable(None)
        telemetry.reset()       # drop the synthetic spans from the ring
        # 20%-trimmed mean of paired deltas: randomization makes the
        # host's periodic/throttle noise zero-mean across pairs, and the
        # trim discards the heavy throttle tails that make a plain
        # median/mean estimator swing several percent run-to-run
        diffs = sorted(a - b for a, b in zip(on_ts, off_ts))
        trim = len(diffs) // 5
        core = diffs[trim:len(diffs) - trim] or diffs
        delta_s = sum(core) / len(core)
        on_ms = sorted(on_ts)[len(on_ts) // 2]
        off_ms = sorted(off_ts)[len(off_ts) // 2]
        pct = delta_s / off_ms * 100.0
        spread = (diffs[len(diffs) // 4] / off_ms * 100.0,
                  diffs[3 * len(diffs) // 4] / off_ms * 100.0)
        print(f"telemetry overhead [captured {model}]: on "
              f"{on_ms * 1e3:.2f} ms/step vs off {off_ms * 1e3:.2f} "
              f"ms/step, paired trimmed-mean delta = {pct:+.2f}% "
              f"(target: within 2%; {pairs} randomized-order adjacent "
              f"on/off step pairs in one loop, per-pair delta IQR "
              f"[{spread[0]:+.1f}%, {spread[1]:+.1f}%])")
        print(f"  span-call microbench: {call_on_us:.1f} us/step on vs "
              f"{call_off_us:.2f} us/step off = "
              f"{(call_on_us - call_off_us) / (off_ms * 1e3) / 10:.3f}% "
              f"of the step")
        if record:
            _record_replace([{
                "metric": f"telemetry_overhead_captured_{model}",
                "value": round(pct, 2), "unit": "pct",
                "vs_baseline": None,
                "extra": {"telemetry_on_ms": round(on_ms * 1e3, 3),
                          "telemetry_off_ms": round(off_ms * 1e3, 3),
                          "paired_samples": len(on_ts),
                          "pair_delta_iqr_pct": [round(spread[0], 2),
                                                 round(spread[1], 2)],
                          "span_call_us_on": round(call_on_us, 2),
                          "span_call_us_off": round(call_off_us, 3),
                          "span_call_pct_of_step": round(
                              (call_on_us - call_off_us)
                              / (off_ms * 1e4), 4),
                          "layers": n_layers, "units": n_units,
                          "batch": batch, "steps": steps, "basis": "none"},
                "basis_note": "captured-step wall with telemetry span "
                              "recording on (default) vs off "
                              "(MXNET_TELEMETRY=0), interleaved at step "
                              "granularity in ONE loop with the on/off "
                              "order randomized within each adjacent "
                              "pair (seeded): 20%-trimmed mean of "
                              "paired (on - off) deltas over the off "
                              "median — separate-runs comparisons "
                              "measured ±7% pure host drift in both "
                              "directions and fixed-order pairing "
                              "aliased a ±5% even/odd loop "
                              "periodicity, both far above the "
                              "microsecond true span cost; the "
                              "randomized paired trimmed design "
                              "cancels both (per-pair delta IQR in "
                              "extra shows the raw noise floor) and "
                              "span_call_us_* pin the noise-free "
                              "absolute cost of one step's telemetry "
                              "call sequence measured in isolation; "
                              "the always-on overhead proof "
                              "(docs/OBSERVABILITY.md)",
                "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            }])
            print(f"recorded telemetry_overhead_captured_{model} -> "
                  f"{_DETAILS_PATH}", flush=True)
        out["telemetry_overhead_pct"] = pct

    if cost_overhead_check:
        # Always-on proof for the COST side: capture is compile-time-only
        # and execution accounting is one dict lookup + four float ops
        # per flush, so the paired delta must sit within the standing 2%
        # bar.  Same randomized-order adjacent-pair methodology as the
        # PR-7 telemetry proof (same rationale: ±7% whole-run drift and
        # the ±5% even/odd loop periodicity both dwarf the true cost).
        import numpy as _onp
        engine.reset_op_cache()
        engine.set_engine_type("LazyEngine")
        net_c = build()
        tr_c = Trainer(net_c.collect_params(), "sgd",
                       {"learning_rate": 0.01, "momentum": 0.9})
        xc, yc = nd.array(X), nd.array(Y)

        def co_step():
            with autograd.record():
                l = L(net_c(xc), yc).mean()
            l.backward()
            tr_c.step(batch)
            return float(l.asnumpy())

        pairs = overhead_pairs or max(10 * steps, 1000)
        order_rng = _onp.random.RandomState(1)
        on_ts, off_ts = [], []
        try:
            for _ in range(3):
                co_step()           # warmup: compile with costs ON
            for _i in range(pairs):
                first_on = bool(order_rng.randint(2))
                for mode_on in ((True, False) if first_on
                                else (False, True)):
                    mxcosts.enable(mode_on)
                    t0 = time.perf_counter()
                    co_step()
                    dt = time.perf_counter() - t0
                    (on_ts if mode_on else off_ts).append(dt)
        finally:
            mxcosts.enable(None)
            engine.set_engine_type("ThreadedEngine")
        diffs = sorted(a - b for a, b in zip(on_ts, off_ts))
        trim = len(diffs) // 5
        core = diffs[trim:len(diffs) - trim] or diffs
        delta_s = sum(core) / len(core)
        on_ms = sorted(on_ts)[len(on_ts) // 2]
        off_ms = sorted(off_ts)[len(off_ts) // 2]
        pct_c = delta_s / off_ms * 100.0
        spread_c = (diffs[len(diffs) // 4] / off_ms * 100.0,
                    diffs[3 * len(diffs) // 4] / off_ms * 100.0)
        print(f"cost-capture overhead [captured {model}]: on "
              f"{on_ms * 1e3:.2f} vs off {off_ms * 1e3:.2f} ms/step, "
              f"paired trimmed-mean delta = {pct_c:+.2f}% (target: "
              f"within 2%; {pairs} randomized-order pairs, IQR "
              f"[{spread_c[0]:+.1f}%, {spread_c[1]:+.1f}%])")
        if record:
            _record_replace([{
                "metric": f"cost_overhead_captured_{model}",
                "value": round(pct_c, 2), "unit": "pct",
                "vs_baseline": None,
                "extra": {"costs_on_ms": round(on_ms * 1e3, 3),
                          "costs_off_ms": round(off_ms * 1e3, 3),
                          "paired_samples": len(on_ts),
                          "pair_delta_iqr_pct": [round(spread_c[0], 2),
                                                 round(spread_c[1], 2)],
                          "layers": n_layers, "units": n_units,
                          "batch": batch, "basis": "none"},
                "basis_note": "captured-step wall with mxnet_tpu.costs "
                              "on (default) vs off (MXNET_COSTS=0), "
                              "randomized-order adjacent on/off step "
                              "pairs in ONE loop, 20%-trimmed mean of "
                              "paired deltas over the off median (the "
                              "PR-7 pairing methodology) — cost capture "
                              "is compile-time-only and execution "
                              "accounting is a dict lookup per flush, "
                              "so the true cost is sub-microsecond; "
                              "the always-on proof for the 2% bar "
                              "(docs/OBSERVABILITY.md 'Compute-cost "
                              "observability')",
                "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            }])
            print(f"recorded cost_overhead_captured_{model} -> "
                  f"{_DETAILS_PATH}", flush=True)
        out["cost_overhead_pct"] = pct_c
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="none", choices=["none", "base",
                                                        "large"],
                    help="run the SPMDTrainer.step phase profile on this "
                         "BERT config (heavy: pays a full trace+compile); "
                         "'none' runs only the chain benchmark")
    ap.add_argument("--engine", default="eager",
                    choices=["eager", "lazy", "fused-step"],
                    help="dispatch mode for the elementwise-chain "
                         "benchmark (and engine type for the step "
                         "profile); 'fused-step' runs the whole-step "
                         "capture referee instead")
    ap.add_argument("--donate", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fused-step mode: donate param/optimizer-state "
                         "buffers into the captured step executable "
                         "(MXNET_STEP_DONATE policy); --donate also "
                         "records the fused_step_donated_* comparison "
                         "(peak_mb donated vs not, via the memory ledger)")
    ap.add_argument("--record-floor", action="store_true",
                    help="measure the python record floor (us per op "
                         "recorded into a lazy segment, flush excluded) "
                         "and record record_floor_us_per_op")
    ap.add_argument("--chain-ops", type=int, default=60)
    ap.add_argument("--chain-side", type=int, default=64)
    ap.add_argument("--fs-steps", type=int, default=20,
                    help="fused-step referee: timed steps per mode")
    ap.add_argument("--fs-batch", type=int, default=8)
    ap.add_argument("--fs-units", type=int, default=0,
                    help="override the dense-chain width (0 = per --model)")
    ap.add_argument("--fs-layers", type=int, default=0,
                    help="override the dense-chain depth (0 = per --model)")
    ap.add_argument("--record", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="append chain results to BENCH_DETAILS.json")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="fused-step mode: dump a step-phase chrome trace "
                         "of the captured loop to FILE and print the "
                         "tools/trace_report.py per-step phase table")
    ap.add_argument("--telemetry-overhead", action="store_true",
                    help="fused-step mode: rerun the captured loop with "
                         "MXNET_TELEMETRY off and record the always-on "
                         "overhead (telemetry_overhead_* record)")
    ap.add_argument("--cost-overhead", action="store_true",
                    help="fused-step mode: paired captured loop with "
                         "mxnet_tpu.costs on vs off — the always-on "
                         "proof for cost capture (cost_overhead_* "
                         "record, 2% bar)")
    ap.add_argument("--oh-pairs", type=int, default=0,
                    help="overhead check: randomized on/off step pairs "
                         "(0 = max(10*--fs-steps, 1000); the trimmed-mean "
                         "SE shrinks as 1/sqrt(pairs))")
    ap.add_argument("--zero", default=None,
                    choices=["1", "2", "3", "sweep"],
                    help="run the ZeRO-ladder referee (BERT-tiny "
                         "zero1/2/3 sweep on the pinned 8-device "
                         "virtual mesh) and record the parallel_zero* "
                         "evidence chain; a numeric level records only "
                         "that level's rows")
    ap.add_argument("--zero-steps", type=int, default=12,
                    help="timed steps per level for --zero")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    # BooleanOptionalAction so --no-remat can actually disable it
    # (store_true with default=True was impossible to turn off)
    ap.add_argument("--remat", action=argparse.BooleanOptionalAction,
                    default=True)
    args = ap.parse_args()

    if args.zero:
        bench_zero(args.zero, steps=args.zero_steps, record=args.record)
        return

    if args.record_floor:
        bench_record_floor(record=args.record)
        # with everything else at its default, --record-floor alone means
        # "just the floor"; any explicit mode (--engine lazy/fused-step,
        # --model ...) still runs afterwards
        if args.engine == "eager" and args.model == "none":
            return

    if args.engine == "fused-step":
        bench_fused_step(args.model if args.model != "none" else "base",
                         steps=args.fs_steps, batch=args.fs_batch,
                         units=args.fs_units, layers=args.fs_layers,
                         record=args.record, trace=args.trace,
                         overhead_check=args.telemetry_overhead,
                         overhead_pairs=args.oh_pairs, donate=args.donate,
                         cost_overhead_check=args.cost_overhead)
        return

    bench_chain(args.engine, n_ops=args.chain_ops, side=args.chain_side,
                record=args.record)
    if args.model == "none":
        return

    if args.engine == "lazy":
        from mxnet_tpu import engine as _eng
        _eng.set_engine_type("LazyEngine")

    import jax
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import random as _random
    from mxnet_tpu.models import BERTModel, BERTPretrainingLoss
    from mxnet_tpu.ndarray.ndarray import NDArray, unwrap

    VOCAB = 30522
    dims = dict(base=(12, 768, 3072, 12), large=(24, 1024, 4096, 16))
    layers, units, hidden, heads = dims[args.model]
    mx.random.seed(0)
    net = BERTModel(vocab_size=VOCAB, num_layers=layers, units=units,
                    hidden_size=hidden, num_heads=heads, max_length=512,
                    dropout=0.1, remat=args.remat)
    net.initialize()
    mx.amp.convert_hybrid_block(net, "bfloat16")
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    loss_core = BERTPretrainingLoss()

    def loss_fn(outputs, labels):
        _, _, nsp_logits, mlm_logits = outputs
        mlab, mw, nsp = labels
        return loss_core(mlm_logits.astype("float32"),
                         nsp_logits.astype("float32"), mlab, mw, nsp)

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.create("lamb", learning_rate=1e-4, wd=0.01), mesh)

    rng = onp.random.RandomState(0)
    B, L, M = args.batch, 512, 80
    data = (nd.array(rng.randint(0, VOCAB, (B, L)).astype("int32")),
            nd.array(onp.zeros((B, L), dtype="int32")),
            nd.array(onp.full((B,), L, dtype="float32")),
            nd.array(rng.randint(0, L, (B, M)).astype("int32")))
    labels = (nd.array(rng.randint(0, VOCAB, (B, M)).astype("int32")),
              nd.array(onp.ones((B, M), dtype="float32")),
              nd.array(rng.randint(0, 2, (B,)).astype("int32")))

    print(f"params: {len(trainer._params)}")
    t0 = time.perf_counter()
    loss = trainer.step(data, labels)
    float(loss.astype("float32").asnumpy())
    print(f"first step (compile): {time.perf_counter()-t0:.1f}s")

    # phase-timed steps (mirror of SPMDTrainer.step)
    for it in range(args.steps):
        t = {}
        t0 = time.perf_counter()
        x = trainer._unwrap_tree(data)
        y = trainer._unwrap_tree(labels)
        t["unwrap_batch"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        trainer._num_update += 1
        tt = trainer._num_update
        o = trainer._optimizer
        lr = o.lr_scheduler(tt) if o.lr_scheduler else o.lr
        batch_sh = trainer._batch_sh
        x = jax.tree_util.tree_map(
            lambda r: parallel.global_put(r, batch_sh), x)
        y = jax.tree_util.tree_map(
            lambda r: parallel.global_put(r, batch_sh), y)
        t["batch_put"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        key = _random.next_key()
        t["rng"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        praws = [unwrap(p.data()) for p in trainer._params]
        t["param_list"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        # the fused step returns an extra diagnostics vector when the
        # health tail compiled in (MXNET_STEP_DIAGNOSTICS, default on)
        outs = trainer._step_fn(
            praws, trainer._states, x, y, key,
            jnp.asarray(lr, "float32"), tt,
            jnp.asarray(o.rescale_grad, "float32"))
        loss, new_params, new_states, aux, _finite = outs[:5]
        t["step_fn_dispatch"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        trainer._states = new_states
        for pp, w in zip(trainer._params, new_params):
            pp._nd._data = w
        if aux and trainer._aux_box and trainer._aux_box[0]:
            for pp, raw in zip(trainer._aux_box[0], aux):
                pp._nd._data = raw
        t["writeback"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        float(NDArray(loss).astype("float32").asnumpy())
        t["sync"] = time.perf_counter() - t0
        total = sum(t.values())
        print(f"step {it}: total {total*1e3:8.1f} ms | " +
              " ".join(f"{k}={v*1e3:.1f}" for k, v in t.items()))


if __name__ == "__main__":
    main()
