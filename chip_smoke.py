"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of BERT-base (weights random, from a seed):

* ``train`` — ``BERTModel`` (12 x 768 x 3072, 12 heads, vocabulary 30522,
  ``max_length`` 512, dropout 0.1) -> ``amp.convert_hybrid_block(bfloat16)``
  -> ``BERTPretrainingLoss`` -> LAMB -> ``parallel.SPMDTrainer`` over a
  ``data`` mesh of every device found, batch 32 x 512 with 80 predictions.
  Loss and the step's ``finite`` flag are read every step; on one chip the
  packed flash attention (forward and backward), fused FFN and fused
  residual-LN kernels must have been dispatched and compiled by Mosaic.
* ``serve`` — ``TransformerLM`` at the same widths behind
  ``GenerationEngine(slots=8, max_len=512)`` -> ``ModelServer`` ->
  ``ServingClient.generate`` over loopback HTTP: every request answered
  with 32 tokens, no compilation after warm-up, and prefill-then-decode
  logits against ``TransformerLM.forward`` as a max-abs difference.

A chip belongs to one process at a time, so the parent never imports jax
and runs the legs as child processes in turn (``--leg train|serve`` runs one
leg in this process).  Nothing is caught and carried past: any leg, step,
request or kernel check that fails makes the exit code non-zero, and so
does a platform that is not ``tpu`` or a ``device_kind`` with no row in
``mxnet_tpu.costs.PEAKS``.  On success the last line of stdout is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.compile_cache/`` in the checkout (docs/COMPILE.md), so a second run
against the same directory is a warm one; each leg prints its compile
seconds.  Times printed here are first observations, not metrics.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as onp

LEGS = ("train", "serve")
LEG_TIMEOUT_S = {"train": 660, "serve": 480}    # together < the 1200 s limit

VOCAB, LAYERS, UNITS, HIDDEN, HEADS, MAX_LENGTH = 30522, 12, 768, 3072, 12, 512
TRAIN = dict(vocab=VOCAB, layers=LAYERS, units=UNITS, hidden=HIDDEN,
             heads=HEADS, max_length=MAX_LENGTH, batch=32, seq=512,
             max_pred=80, steps=6, sync_steps=5)
SERVE = dict(vocab=VOCAB, layers=LAYERS, units=UNITS, hidden=HIDDEN,
             heads=HEADS, max_length=MAX_LENGTH, slots=8, max_len=512,
             prefill_buckets=(32, 128), prompt_lens=(5, 20, 31, 40, 100, 128),
             new_tokens=32, check_prompt=24, check_decode=4)
# float32 model, matmuls pinned to "highest" for the check: the two paths
# differ only in summation order
LOGITS_TOL = 1e-3
KERNELS = ("attention_packed_fwd_bwd", "ffn_fused_fwd_bwd",
           "residual_ln_fwd_bwd")


def say(**record):
    print(json.dumps(record, separators=(",", ":"), default=str), flush=True)


def require(cond, message):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {message}")


def device_record(require_tpu=True):
    """State what jax found, first; refuse anything that is not a TPU with
    a row in the peaks table."""
    import jax
    from mxnet_tpu import costs
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices())}
    say(device=dev, jax=jax.__version__,
        cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if require_tpu:
        require(d.platform == "tpu" and jax.default_backend() == "tpu",
                f"no accelerator: jax found platform {d.platform!r} "
                f"({d.device_kind!r} x {dev['count']})")
        require(d.device_kind in costs.PEAKS,
                f"device_kind {d.device_kind!r} has no row in "
                f"mxnet_tpu.costs.PEAKS")
    return dev


def count_compiles():
    """Counter of XLA backend compilations in this process from now on."""
    import jax
    n = [0]

    def on_event(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return n


# ---------------------------------------------------------------------------
# training leg
# ---------------------------------------------------------------------------
def build_bert_trainer(cfg, mesh):
    """The path of examples/bert_pretrain.py (GluonNLP scripts/bert
    shape), with the all-finite guard compiled into the step so that
    ``last_step_finite`` is a real check."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.models import BERTModel, BERTPretrainingLoss

    mx.random.seed(0)
    net = BERTModel(vocab_size=cfg["vocab"], num_layers=cfg["layers"],
                    units=cfg["units"], hidden_size=cfg["hidden"],
                    num_heads=cfg["heads"], max_length=cfg["max_length"],
                    dropout=0.1)
    net.initialize()
    mx.amp.convert_hybrid_block(net, "bfloat16")
    loss_core = BERTPretrainingLoss()

    def loss_fn(outputs, labels):
        _, _, nsp_logits, mlm_logits = outputs
        mlab, mw, nsp = labels
        return loss_core(mlm_logits, nsp_logits.astype("float32"),
                         mlab, mw, nsp)

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.create("lamb", learning_rate=1e-4, wd=0.01), mesh,
        skip_nonfinite=True)
    rng = onp.random.RandomState(0)
    B, L, M, V = cfg["batch"], cfg["seq"], cfg["max_pred"], cfg["vocab"]
    data = (nd.array(rng.randint(0, V, (B, L)).astype("int32")),
            nd.array(onp.zeros((B, L), dtype="int32")),
            nd.array(onp.full((B,), L, dtype="float32")),
            nd.array(rng.randint(0, L, (B, M)).astype("int32")))
    labels = (nd.array(rng.randint(0, V, (B, M)).astype("int32")),
              nd.array(onp.ones((B, M), dtype="float32")),
              nd.array(rng.randint(0, 2, (B,)).astype("int32")))
    return trainer, data, labels


def check_kernels(compiled, single_device):
    """On one device the fused kernels must have been chosen and compiled
    by Mosaic; over a wider mesh ``kernel_dispatch_allowed()`` turns them
    off and the report says so."""
    from mxnet_tpu.ops.flash_attention import kernel_report
    report = kernel_report()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    say(kernel_report=report, mosaic_custom_calls=mosaic_calls)
    if not single_device:
        require(not report and mosaic_calls == 0,
                f"a mesh wider than one device runs the XLA paths, yet "
                f"{len(report)} probes ran and the step holds "
                f"{mosaic_calls} Mosaic calls")
        say(kernel_path="XLA (kernel_dispatch_allowed() is False under a "
                        "mesh wider than one device)")
        return
    for r in report:
        require(r["compiled"], f"Mosaic refused {r['kernel']}"
                               f"{r['signature']}: {r['message']}")
    for k in KERNELS:
        require(any(r["kernel"] == k for r in report),
                f"kernel {k} was not dispatched at these shapes "
                f"(probes run: {sorted({r['kernel'] for r in report})})")
    require(mosaic_calls > 0,
            "the compiled step's HLO holds no tpu_custom_call")


def check_placement(compiled, mesh):
    """Batch shards and bytes on every device of the mesh, not all on the
    first."""
    import jax
    args_sh = compiled.input_shardings[0]
    batch = [{"devices": sorted(d.id for d in sh.device_set),
              "spec": str(getattr(sh, "spec", None))}
             for sh in jax.tree_util.tree_leaves((args_sh[2], args_sh[3]))]
    peaks = {d.id: d.memory_stats()["peak_bytes_in_use"]
             for d in mesh.devices.flat}
    say(batch_shardings=batch, peak_bytes_in_use=peaks)
    want = sorted(d.id for d in mesh.devices.flat)
    for b in batch:
        require(b["devices"] == want and (mesh.size == 1
                                          or "data" in b["spec"]),
                f"batch leaf placed on {b} instead of a 'data' shard on "
                f"each of {want}")
    require(all(v > 0 for v in peaks.values()),
            f"a device of the mesh holds no bytes: {peaks}")


def train_leg(cfg=TRAIN, on_chip=True):
    import jax
    device_record(require_tpu=on_chip)
    from mxnet_tpu import memory, parallel
    t_start = time.perf_counter()
    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer, data, labels = build_bert_trainer(cfg, mesh)
    t_built = time.perf_counter()
    info = trainer.precompile(data, labels)
    say(leg="train", mesh=dict(mesh.shape), build_s=round(t_built - t_start, 2),
        lower_s=round(info["lower_s"], 2),
        compile_s=round(info["compile_s"], 2), cache_dir=info["cache_dir"])
    if on_chip:
        check_kernels(info["compiled"], single_device=mesh.size == 1)

    for i in range(cfg["steps"]):
        t0 = time.perf_counter()
        loss = float(trainer.step(data, labels).astype("float32").asnumpy())
        finite = bool(trainer.last_step_finite)
        say(step=i + 1, loss=round(loss, 5), finite=finite,
            wall_s=round(time.perf_counter() - t0, 4))
        require(onp.isfinite(loss) and finite,
                f"step {i + 1}: loss {loss}, finite flag {finite}")

    # the sync rule: the same N steps ending in block_until_ready and
    # ending in a host read of the loss
    n = cfg["sync_steps"]
    sync = {"block_until_ready_ms": [], "host_read_ms": []}
    for _ in range(2):
        for name in sync:
            t0 = time.perf_counter()
            for _ in range(n):
                loss = trainer.step(data, labels)
            if name == "block_until_ready_ms":
                loss.wait_to_read()
            else:
                float(loss.astype("float32").asnumpy())
            sync[name].append(round(1000 * (time.perf_counter() - t0) / n, 3))
    say(sync_rule=sync, steps_per_window=n)
    require(bool(trainer.last_step_finite), "non-finite step in the windows")

    if on_chip:
        check_placement(info["compiled"], mesh)
        # real libtpu exposes memory_stats(): the telemetry span sampler
        # must have taken its backend branch
        require(memory.sample_source() == "backend",
                f"memory sampler reads {memory.sample_source()!r}, "
                f"not the backend's memory_stats()")
        say(memory_sampler=memory.sample_source(),
            sampled_peak_bytes=memory.peak_bytes_in_use())


# ---------------------------------------------------------------------------
# serving leg
# ---------------------------------------------------------------------------
def build_lm(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import TransformerLM
    mx.random.seed(0)
    net = TransformerLM(vocab_size=cfg["vocab"], num_layers=cfg["layers"],
                        units=cfg["units"], hidden_size=cfg["hidden"],
                        num_heads=cfg["heads"], max_length=cfg["max_length"])
    net.initialize()
    net(nd.array(onp.zeros((1, 4), onp.int32)),
        nd.array(onp.asarray([4], onp.int32)))       # materialize params
    return net


def logits_check(net, cfg):
    """Prefill the prompt, decode the rest through the ring cache, and
    compare every position's logits with one full forward."""
    import jax
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray.ndarray import NDArray
    P, n = cfg["check_prompt"], cfg["check_decode"]
    H, D, M = cfg["heads"], cfg["units"] // cfg["heads"], 2 * (P + n)
    rng = onp.random.RandomState(1)
    toks = rng.randint(0, cfg["vocab"], (P + n,)).astype("int32")
    with jax.default_matmul_precision("highest"):
        ref = net(nd.array(toks[None]),
                  nd.array(onp.asarray([P + n], onp.int32))).asnumpy()[0]
        logits, kvs = net.prefill(nd.array(toks[None, :P]),
                                  nd.array(onp.asarray([P], onp.int32)))
        got = [logits.asnumpy()[0, P - 1]]
        caches = []
        for k, v in kvs:
            ring = onp.zeros((2, 1, H, M, D), onp.float32)
            ring[0, :, :, :P] = k.asnumpy()
            ring[1, :, :, :P] = v.asnumpy()
            caches.append((NDArray(ring[0]), NDArray(ring[1])))
        for j in range(n - 1):
            logits, caches = net.decode_step(
                nd.array(toks[P + j:P + j + 1]), caches,
                nd.array(onp.asarray([P + j], onp.int32)))
            got.append(logits.asnumpy()[0])
    got = onp.stack(got)
    want = ref[P - 1:P + n - 1]
    require(got.shape == want.shape == (n, cfg["vocab"])
            and onp.isfinite(got).all(),
            f"logits shape {got.shape} vs {want.shape}, or non-finite")
    diff = float(onp.abs(got - want).max())
    say(logits_max_abs_diff=diff, logits_max_abs=float(onp.abs(want).max()),
        positions=n, tolerance=LOGITS_TOL)
    require(diff < LOGITS_TOL,
            f"prefill+decode logits differ from forward by {diff}")


def serve_leg(cfg=SERVE, on_chip=True):
    device_record(require_tpu=on_chip)
    from mxnet_tpu import serving
    from mxnet_tpu.serving.generate import GenerationEngine
    compiles = count_compiles()
    t0 = time.perf_counter()
    net = build_lm(cfg)
    t1 = time.perf_counter()
    engine = GenerationEngine(net, slots=cfg["slots"], max_len=cfg["max_len"],
                              prefill_buckets=cfg["prefill_buckets"])
    t2 = time.perf_counter()
    counters = engine.metrics.stats()["counters"]
    say(leg="serve", build_s=round(t1 - t0, 2),
        engine_compile_s=round(t2 - t1, 2),
        programs_compiled=counters["prefill_compiles"]
        + counters["decode_compiles"],
        programs_warm_loaded=counters["prefill_cache_hits"]
        + counters["decode_cache_hits"], kv_cache_bytes=engine.kv_cache_bytes)

    predict = serving.InferenceEngine(lambda x: (onp.asarray(x),),
                                      batch_buckets=(1,))
    rng = onp.random.RandomState(2)
    prompts = [rng.randint(0, cfg["vocab"], (n,)).tolist()
               for n in cfg["prompt_lens"]]
    buckets = {min(b for b in engine.prefill_buckets if b >= len(p))
               for p in prompts}
    require(len(buckets) >= 2, f"prompts land in buckets {buckets} only")
    results = [None] * len(prompts)
    with serving.ModelServer(predict, port=0, generator=engine) as srv:
        client = serving.ServingClient(srv.url)
        # warm-up: one request through every bucket and the decode program
        for b in sorted(buckets):
            client.generate([1] * b, max_new_tokens=2)
        warm = compiles[0]

        def ask(i):
            results[i] = client.generate(prompts[i],
                                         max_new_tokens=cfg["new_tokens"])
        t3 = time.perf_counter()
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        wall = time.perf_counter() - t3
        again = client.generate(prompts[0], max_new_tokens=cfg["new_tokens"])
        after = compiles[0] - warm
        stats = client.stats()["generate"]["counters"]
    engine.stop()
    for i, r in enumerate(results):
        require(r is not None, f"request {i} was not answered")
        require(len(r["tokens"]) == cfg["new_tokens"]
                and r["finish_reason"] == "length"
                and all(0 <= t < cfg["vocab"] for t in r["tokens"]),
                f"request {i} (prompt {len(prompts[i])}): {r}")
        say(request=i, prompt_len=len(prompts[i]), tokens=len(r["tokens"]),
            ttft_ms=r["ttft_ms"], tokens_per_s=r["tokens_per_s"])
    require(again["tokens"] == results[0]["tokens"],
            "the same prompt decoded to different tokens alone and batched")
    say(requests_answered=len(results), buckets=sorted(buckets),
        window_s=round(wall, 3), compilations_before_window=warm,
        compilations_after_warmup=after, completed=stats["completed"],
        errors=stats["errors"])
    require(after == 0, f"{after} compilations after warm-up")
    require(stats["errors"] == 0, f"engine counted {stats['errors']} errors")
    logits_check(net, cfg)


# ---------------------------------------------------------------------------
# parent: never imports jax, runs the legs as children in turn
# ---------------------------------------------------------------------------
def run_leg(leg):
    """Run one leg as a child in its own process group, echo its output,
    return the device it reported.  The group is killed whatever happens,
    so nothing this script started outlives it."""
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__),
                             "--leg", leg], stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=LEG_TIMEOUT_S[leg])
    except subprocess.TimeoutExpired:
        raise SystemExit(f"chip_smoke: FAILED: leg {leg} exceeded "
                         f"{LEG_TIMEOUT_S[leg]} s") from None
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    sys.stdout.write(out)
    sys.stdout.flush()
    if proc.returncode != 0:
        raise SystemExit(f"chip_smoke: FAILED: leg {leg} exited with "
                         f"{proc.returncode}")
    return json.loads(out.splitlines()[0])["device"]


def main(argv):
    if len(argv) == 2 and argv[0] == "--leg" and argv[1] in LEGS:
        {"train": train_leg, "serve": serve_leg}[argv[1]]()
        say(leg=argv[1], ok=True)
        return
    require(not argv, "usage: chip_smoke.py [--leg train|serve]")
    t0 = time.perf_counter()
    devices = [run_leg(leg) for leg in LEGS]
    require(devices[0] == devices[1], f"legs saw different devices: {devices}")
    say(wall_s=round(time.perf_counter() - t0, 1))
    say(ok=True, device=devices[0])


if __name__ == "__main__":
    main(sys.argv[1:])
