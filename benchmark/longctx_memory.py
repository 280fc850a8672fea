"""Long-context training under an HBM budget: the memory-lean fused step
demonstrator (donation + ledger-guided remat).

The question this answers: *does a long-context config that previously
blew the device budget now train?*  The referee is the per-program
memory ledger (``memory.record_program`` — XLA's own buffer assignment,
available at compile time on every backend), so the proof runs anywhere:

* the **fat** variant (``remat=False``, ``donate_params=False`` — the
  pre-PR configuration) is compiled AOT and its ledger peak checked
  against ``--budget-mb``.  Over budget -> the run is REFUSED before a
  single step executes — on a real accelerator this is the
  compile/alloc-OOM the budget models;
* the **lean** variant (``SPMDTrainer(remat='auto',
  remat_budget_bytes=budget)`` + buffer donation, the defaults this PR
  lands) must fit the same budget AND actually train ``--steps`` steps;
  its loss, step wall and ledger peak go into the committed
  ``longctx_*`` records.

Defaults are CPU-host-sized: a seq-1024 encoder stack on the
dense-score attention path (``use_flash=False`` — the O(L^2) fallback
long-context configs actually OOM on; flash is unavailable on CPU and
on >1-mesh custom-call boundaries), adam states so donation's aliasing
carries params + both moments.  NOTE the CPU caveat: XLA-CPU's buffer
assignment barely reuses buffers across per-layer remat recomputes, so
the remat share of the saving is UNDERSTATED here relative to a real
accelerator (``examples/remat_memory.py`` documents the v5e-scale
behavior); donation's alias bytes are modeled exactly.  On a v5e
substitute the real config, e.g.::

    python benchmark/longctx_memory.py --layers 24 --units 1024 \\
        --hidden 4096 --heads 16 --seq 1024 --batch 64 --budget-mb 16384

which is exactly the BERT-large-shaped stack ``examples/remat_memory.py``
documents as failing to compile on one v5e without remat.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DETAILS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_DETAILS.json")


def build_trainer(layers, units, hidden, heads, remat, donate, budget,
                  use_flash=False):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.models.bert import TransformerEncoderLayer

    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(TransformerEncoderLayer(units, hidden, heads, dropout=0.0,
                                        use_flash=use_flash))
    net.add(nn.Dense(2))
    net.initialize()
    L = gloss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    return parallel.SPMDTrainer(
        net, lambda out, y: L(out, y).mean(),
        opt.create("adam", learning_rate=1e-4), mesh,
        donate_params=donate, remat=remat, remat_budget_bytes=budget)


def spmd_peak():
    """Newest spmd_step entry in the per-program ledger."""
    from mxnet_tpu import memory
    entries = [e for e in memory.ledger() if e["kind"] == "spmd_step"]
    return entries[-1]["peak_bytes"] if entries else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--units", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--budget-mb", type=float, default=1408.0,
                    help="device memory budget the step program's ledger "
                         "peak must fit (default models a ~1.4 GB device "
                         "slice for the CPU-sized demo config; use 16384 "
                         "for a v5e)")
    ap.add_argument("--record", action=argparse.BooleanOptionalAction,
                    default=True)
    args = ap.parse_args()
    budget = int(args.budget_mb * 2**20)

    # fresh compile-cache root: warm-loaded executables report
    # memory_analysis without the alias table, which would misread the
    # donating lean program's peak on a second invocation
    import tempfile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="mxnet-longctx-bench-")

    import numpy as onp
    from mxnet_tpu import health, nd, util, memory

    # pin the health diagnostics tail OFF: the fat-vs-lean peak referee
    # compares against the pre-diagnostics committed trajectory, and the
    # diag tail keeps old params live past the update (extra outputs),
    # which would shift XLA's buffer-assignment peaks under measurement
    health.enable(False)

    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(args.batch, args.seq, args.units)
                 .astype("float32"))
    y = nd.array(rng.randint(0, 2, (args.batch,)).astype("float32"))

    cfg = dict(layers=args.layers, units=args.units, hidden=args.hidden,
               heads=args.heads, seq=args.seq, batch=args.batch,
               budget_mb=args.budget_mb)
    print(f"longctx config: {cfg}", flush=True)

    # -- fat: the pre-PR configuration (no remat, no donation) ------------
    memory.reset()
    fat = build_trainer(args.layers, args.units, args.hidden, args.heads,
                        remat=False, donate=False, budget=None)
    fat.precompile(x, y)
    fat_peak = spmd_peak()
    fat_fits = fat_peak is not None and fat_peak <= budget
    print(f"fat  (remat off, donate off): peak "
          f"{fat_peak / 2**20:.1f} MB -> "
          f"{'fits' if fat_fits else 'EXCEEDS'} budget "
          f"{args.budget_mb:.0f} MB"
          f"{' — refused to train' if not fat_fits else ''}", flush=True)

    # -- lean: ledger-guided remat + buffer donation ----------------------
    memory.reset()
    lean = build_trainer(args.layers, args.units, args.hidden, args.heads,
                         remat="auto", donate=None, budget=budget)
    lean.precompile(x, y)
    rep = lean.remat_report or {}
    chosen = rep.get("chosen")
    # the peak from the search's FRESH compile of the chosen candidate —
    # the final precompile may hit the persistent compile cache, whose
    # deserialized executable strips the donation alias table
    chosen_row = next((r for r in rep.get("candidates", ())
                       if r["policy"] == chosen and r.get("peak_bytes")),
                      None)
    lean_peak = chosen_row["peak_bytes"] if chosen_row else spmd_peak()
    lean_fits = lean_peak is not None and lean_peak <= budget
    print(f"lean (remat={chosen!r}, donate on): peak "
          f"{lean_peak / 2**20:.1f} MB -> "
          f"{'fits' if lean_fits else 'EXCEEDS'} budget", flush=True)
    if not lean_fits:
        print("lean config exceeds the budget too — nothing to "
              "demonstrate at this size", flush=True)
        sys.exit(1)

    # the lean config TRAINS (the fat one was refused above)
    loss = lean.step(x, y)
    first = float(loss.astype("float32").asnumpy())
    ts = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        loss = lean.step(x, y)
        last = float(loss.astype("float32").asnumpy())
        ts.append(time.perf_counter() - t0)
    step_ms = sorted(ts)[len(ts) // 2] * 1e3
    toks = args.batch * args.seq / (step_ms / 1e3)
    print(f"lean trains: {args.steps} steps, {step_ms:.0f} ms/step "
          f"({toks:.0f} tok/s), loss {first:.4f} -> {last:.4f}",
          flush=True)

    if args.record:
        now = time.strftime("%Y-%m-%dT%H:%M:%S")
        recs = [
            {"metric": "longctx_budget_fat_peak_mb",
             "value": round(fat_peak / 2**20, 1), "unit": "MB",
             "vs_baseline": round(fat_peak / budget, 3),
             "extra": dict(cfg, fits_budget=bool(fat_fits),
                           refused=not fat_fits, basis="none"),
             "basis_note": "ledger peak (XLA buffer assignment) of the "
                           "pre-PR step program: remat off, donation off "
                           "— over budget means this config was refused/"
                           "OOM'd before the memory-lean fused step work",
             "ts": now},
            {"metric": "longctx_budget_lean_peak_mb",
             "value": round(lean_peak / 2**20, 1), "unit": "MB",
             "vs_baseline": round(lean_peak / fat_peak, 3),
             "extra": dict(cfg, fits_budget=bool(lean_fits),
                           remat_chosen=chosen,
                           peak_drop_pct=round(
                               100 * (1 - lean_peak / fat_peak), 1),
                           basis="longctx_budget_fat_peak_mb"),
             "basis_note": "ledger peak of the memory-lean step: "
                           "SPMDTrainer(remat='auto') ledger-guided "
                           "checkpointing + buffer donation — must fit "
                           "the same budget the fat config exceeded",
             "ts": now},
            {"metric": "longctx_budget_lean_train",
             "value": round(step_ms, 1), "unit": "ms_per_step",
             "vs_baseline": None,
             "extra": dict(cfg, steps=args.steps,
                           tok_per_s=round(toks, 1),
                           first_loss=round(first, 5),
                           last_loss=round(last, 5),
                           peak_mb=round(lean_peak / 2**20, 1),
                           basis="none"),
             "basis_note": "the lean config actually training under the "
                           "budget the fat config exceeded (loss "
                           "decreasing over the recorded steps) — the "
                           "previously-over-budget longctx demonstrator",
             "ts": now},
        ]
        # replace by EXACT metric name (serve_bench convention): a rerun
        # must not stack duplicate records
        names = {r["metric"] for r in recs}
        util.write_json_records(
            _DETAILS_PATH, recs, append=False,
            keep=lambda r: r.get("metric") not in names)
        print(f"recorded longctx_budget_* -> {_DETAILS_PATH}", flush=True)


if __name__ == "__main__":
    main()
