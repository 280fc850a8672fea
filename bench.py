"""Headline benchmarks on one TPU chip: ResNet-50 v1 + BERT-base pretraining.

ResNet-50 matches the reference's headline workload (GluonCV ResNet-50
recipe, BASELINE.md): full training step (forward + backward + SGD-momentum
update, batch-norm stats included) in bfloat16 at batch 256 / 224x224
(TPU-sized per-chip batch; the reference recipe uses 64/GPU).

BERT-base matches the GluonNLP ``scripts/bert`` pretraining loop shape:
MLM+NSP heads, seq 512, max_predictions 80, LAMB, bfloat16, flash
attention.

Baseline anchors (BASELINE.md): ResNet-50 ~360 img/s (V100 fp32,
upstream perf.md); BERT ~2.5k tok/s/GPU (V100, GluonNLP logs).
Prints one JSON line per workload (ResNet-50 last — primary headline).
"""
import json
import sys
import time
import traceback
from datetime import datetime, timezone

import numpy as onp



def _peak_bf16():
    """bf16 peak FLOP/s of the chip this run is on, from the one
    device_kind-keyed table (a kind not in it is an error there)."""
    from mxnet_tpu import costs
    return costs.peak_flops()

# ---------------------------------------------------------------------------
# MFU flop sources: where a compiled program is available, the numerator
# comes from the mxnet_tpu.costs ledger (XLA's own cost model over the
# fused step — flop_source "cost_analysis"); the hand-derived 2xMACs
# formulas remain the fallback (flop_source "analytic") and the referee
# (tests/test_costs.py asserts the two agree within 10% on Dense/Conv).
# cost_analysis counts EXECUTED flops, so rematerialized compute (flash-
# attention recompute) is included where the analytic convention skips
# it — every record says which basis it used (benchmark/README.md).
# ---------------------------------------------------------------------------


def _step_flops(trainer, data, labels, analytic_step_flops):
    """(flops_per_step, flop_source): AOT-precompile the fused step so
    its ``cost_analysis()`` lands in the costs ledger keyed by the
    program fingerprint (the first timed step warm-loads the same
    fingerprint from the persistent cache, so no compile is paid twice),
    and read the measured per-step flops back; any failure falls back to
    the analytic figure."""
    try:
        from mxnet_tpu import costs
        info = trainer.precompile(data, labels)
        flops = (info or {}).get("flops")
        if not flops and (info or {}).get("key"):
            flops = costs.ledger_flops(info["key"])
        if flops and flops > 0:
            return float(flops), "cost_analysis"
    except Exception:
        traceback.print_exc(file=sys.stderr)
    return float(analytic_step_flops), "analytic"

# ---------------------------------------------------------------------------
# Output discipline (round-5 fix): the driver records a fixed-size TAIL of
# stdout, so every metric line must be compact enough that all of them fit,
# and lines print in ASCENDING importance (BERT and ResNet-50 last).  The
# stdout line carries a short ``basis`` tag; the full basis prose, workload
# config and loss go to benchmark/BENCH_DETAILS.json.
# ---------------------------------------------------------------------------
_BASIS_NOTES = {
    "v100_anchor_unverified":
        "estimate: anchored to the reference's V100 number from BASELINE.md "
        "(recorded from memory — UNVERIFIED; BASELINE.md caveat applies). "
        "MFU is the load-bearing metric.",
    "ctx_ratio_vs_512cap":
        "context-length ratio over the reference's 512-token cap — NOT a "
        "throughput comparison (the reference's O(L^2) dense scores cannot "
        "represent 32k at all: 4 GB/head fp32).",
    "vs_our_bf16":
        "measured on-chip ratio vs OUR bf16 path at the same batch (not a "
        "reference-hardware anchor).",
    "none":
        "no published reference training throughput for this workload in "
        "BASELINE.md (it records quality metrics only).",
}
_DETAILS = []


def _now_iso():
    return datetime.now(timezone.utc).isoformat(timespec="milliseconds")


def emit(metric, value, unit, vs_baseline, basis, **extra):
    """One compact driver-visible JSON line + a verbose details record
    (the details record carries a real per-line ``ts`` — measurement
    time, not file-write time — so the record can be ordered against
    outages and driver timeouts)."""
    line = {"metric": metric, "value": value, "unit": unit,
            "vs_baseline": vs_baseline, "extra": dict(extra, basis=basis)}
    _DETAILS.append(dict(line, basis_note=_BASIS_NOTES.get(basis, basis),
                         ts=_now_iso()))
    print(json.dumps(line, separators=(",", ":")), flush=True)


def _write_details():
    import os
    from mxnet_tpu.util import write_json_records
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "BENCH_DETAILS.json")
    # training records are rewritten each run; serving_*/fleet_*/trace_*/
    # compile_*/io_*/fused_step_*/telemetry_*/mem_*/cost_*/
    # longctx_budget_*/record_floor_*/health_*/run_ledger_*/generate_*/
    # parallel_*/zerohop_* records belong to serve_bench.py/compile_bench.py/
    # io_overlap.py/io_scaling.py/dispatch_profile.py/
    # memory_overhead.py/longctx_memory.py/health_bench.py/
    # generate_bench.py and must survive a rerun
    write_json_records(path, _DETAILS, append=False, keep=_keep_foreign)


def _keep_foreign(r):
    """Records owned by the other bench tools (never rewritten here —
    also the complement of what ``--check`` requires a fresh run to
    reproduce).  dispatch_chain_*/opperf_* belong to
    dispatch_profile.py/opperf.py: before PR 12 they matched no keep
    prefix, so a bench.py rewrite silently deleted them AND --check
    would have required metrics bench.py never emits."""
    return str(r.get("metric", "")).startswith(
        ("serving_", "fleet_", "trace_", "compile_", "io_",
         "fused_step_", "telemetry_", "mem_", "cost_", "longctx_budget_",
         "record_floor_", "dispatch_chain_", "opperf_", "health_",
         "run_ledger_", "generate_", "parallel_", "autopilot_",
         "zerohop_"))


def build_r50_trainer(batch):
    """Headline-workload builder (shared with benchmark/profile_r50.py so
    the profiler always profiles exactly the step the benchmark times)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    import os
    mx.random.seed(0)
    # MXNET_R50_FUSED=1 routes through the Pallas fused conv+BN+ReLU blocks
    # (ops/conv_fused.py); stays opt-in until it beats the XLA layer path.
    # MXNET_R50_S2D=1 enables the space-to-depth stem (exact
    # reformulation; measured NOT a win on v5e — r50_roofline.md §7:
    # stage device time 9.30 vs 7.86 ms, end-to-end a wash)
    fused = os.environ.get("MXNET_R50_FUSED", "0") == "1"
    s2d = os.environ.get("MXNET_R50_S2D", "0") == "1"
    net = resnet50_v1(classes=1000, fused=fused, stem_s2d=s2d)
    net.initialize()
    net.cast("bfloat16")
    # BN stats/eps stay stable enough in bf16 for throughput purposes

    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])

    lossfn = gloss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, label):
        return lossfn(out.astype("float32"), label)

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.SGD(learning_rate=0.01, momentum=0.9), mesh)

    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(batch, 3, 224, 224).astype("float32")) \
        .astype("bfloat16")
    y = nd.array(rng.randint(0, 1000, (batch,)).astype("float32"))
    return trainer, x, y


def build_bert_trainer(batch, seq_len=512, max_pred=80, num_layers=12,
                       units=768, hidden_size=3072, num_heads=12):
    """BERT pretraining step builder (GluonNLP scripts/bert shape);
    defaults = base config; large = (24, 1024, 4096, 16).  Shared with
    benchmark/profile_bert.py."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.models import BERTModel, BERTPretrainingLoss

    VOCAB = 30522
    mx.random.seed(0)
    net = BERTModel(vocab_size=VOCAB, num_layers=num_layers, units=units,
                    hidden_size=hidden_size, num_heads=num_heads,
                    max_length=seq_len, dropout=0.1)
    net.initialize()
    mx.amp.convert_hybrid_block(net, "bfloat16")

    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    loss_core = BERTPretrainingLoss()

    def loss_fn(outputs, labels):
        _, _, nsp_logits, mlm_logits = outputs
        mlab, mw, nsp = labels
        # mlm_logits stay bf16: the fused CE does fp32 math on the fly
        # without materializing an fp32 (B*M, V) tensor
        return loss_core(mlm_logits, nsp_logits.astype("float32"),
                         mlab, mw, nsp)

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.create("lamb", learning_rate=1e-4, wd=0.01), mesh)

    rng = onp.random.RandomState(0)
    B, L, M = batch, seq_len, max_pred
    data = (nd.array(rng.randint(0, VOCAB, (B, L)).astype("int32")),
            nd.array(onp.zeros((B, L), dtype="int32")),
            nd.array(onp.full((B,), L, dtype="float32")),
            nd.array(rng.randint(0, L, (B, M)).astype("int32")))
    labels = (nd.array(rng.randint(0, VOCAB, (B, M)).astype("int32")),
              nd.array(onp.ones((B, M), dtype="float32")),
              nd.array(rng.randint(0, 2, (B,)).astype("int32")))
    return trainer, data, labels


def build_transformer_trainer(batch, src_len, tgt_len):
    """Transformer-base MT training step (GluonNLP
    ``scripts/machine_translation`` WMT14 En-De workload shape:
    6+6 layers, 512 units, 2048 hidden, 8 heads, shared 32k vocab);
    shared with benchmark/profile_* discipline."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import Transformer

    VOCAB = 32768
    mx.random.seed(0)
    net = Transformer(src_vocab_size=VOCAB, tgt_vocab_size=VOCAB,
                      num_layers=6, units=512, hidden_size=2048,
                      num_heads=8, max_length=max(src_len, tgt_len),
                      dropout=0.1)
    net.initialize()
    mx.amp.convert_hybrid_block(net, "bfloat16")

    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    lossfn = gloss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, labels):
        # bf16 logits stay bf16: the loss dispatches to the fused CE
        # (fp32 math on the fly, no (B*L, 32k) fp32 materialization)
        B, L, V = out.shape
        return lossfn(out.reshape(B * L, V), labels.reshape(-1))

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.Adam(learning_rate=3e-4), mesh)

    rng = onp.random.RandomState(0)
    src = nd.array(rng.randint(2, VOCAB, (batch, src_len)).astype("int32"))
    tgt = nd.array(rng.randint(2, VOCAB, (batch, tgt_len)).astype("int32"))
    y = nd.array(rng.randint(2, VOCAB, (batch, tgt_len)).astype("float32"))
    return trainer, (src, tgt), y


def transformer_train_flops_per_token(src_len, tgt_len):
    """FLOPs per processed token (src+tgt counted) for transformer-base,
    2xMACs, fwd x3 — same conventions as the BERT/R50 numbers."""
    d, h, layers, vocab = 512, 2048, 6, 32768
    enc_tok = layers * (4 * d * d + 2 * d * h)       # qkv+out+ffn
    enc_tok += layers * 2 * src_len * d              # qk^T + av
    enc_tok += layers * 2 * d * d                    # cross kv_proj on mem
    dec_tok = layers * (4 * d * d + 2 * d * d + 2 * d * h)  # self+cross(q,out)+ffn
    dec_tok += layers * 2 * (tgt_len + src_len) * d  # self + cross scores/av
    dec_tok += d * vocab                             # output projection
    total_macs = src_len * enc_tok + tgt_len * dec_tok
    return 3 * 2 * total_macs / (src_len + tgt_len)


def bench_transformer():
    import jax

    B, LS, LT = 32, 128, 128
    trainer, data, y = build_transformer_trainer(B, LS, LT)
    step_flops, flop_source = _step_flops(
        trainer, data, y,
        B * (LS + LT) * transformer_train_flops_per_token(LS, LT))
    for _ in range(3):
        loss = trainer.step(data, y)
    float(loss.astype("float32").asnumpy())

    # the ~24 ms step needs a longer window than the big workloads: at
    # 20 steps the r4 record showed a ±10% run-to-run band
    steps = 80
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(data, y)
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    toks = B * (LS + LT) * steps / dt
    mfu = steps * step_flops / dt / _peak_bf16()
    emit("transformer_mt_train_throughput", round(toks, 1), "tok/s/chip",
         None, "none", mfu=round(mfu, 4), flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2))
    _DETAILS[-1].update(
        batch=B, src_len=LS, tgt_len=LT,
        arch="transformer_base (6+6L, 512d, 2048h, 32k vocab)",
        dtype="bfloat16", platform=jax.devices()[0].platform,
        loss=float(loss.astype("float32").asnumpy()))


def build_yolo_trainer(batch, image_size=416, num_classes=20):
    """YOLOv3-darknet53 VOC training step (GluonCV
    ``scripts/detection/yolo/train_yolo3.py`` workload shape), synthetic
    device-resident batch, full loss (target assignment + dynamic ignore
    mask) inside the one jitted program."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.models import YOLOV3Loss, yolo3_darknet53_voc

    mx.random.seed(0)
    net = yolo3_darknet53_voc(num_classes=num_classes,
                              image_size=image_size)
    net.initialize()
    net.cast("bfloat16")

    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    loss_core = YOLOV3Loss()

    def loss_fn(outs, labels):
        outs = [o.astype("float32") for o in outs]
        return loss_core(net, outs, labels)

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.SGD(learning_rate=1e-3, momentum=0.9), mesh)

    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(batch, 3, image_size, image_size)
                 .astype("float32")).astype("bfloat16")
    # (B, M, 5) [cls, x1, y1, x2, y2] normalized; ~4 objects per image
    M = 8
    cls = rng.randint(0, num_classes, (batch, M, 1)).astype("float32")
    cls[:, 4:] = -1.0                                  # pad rows
    x1 = rng.uniform(0.0, 0.6, (batch, M, 1))
    y1 = rng.uniform(0.0, 0.6, (batch, M, 1))
    wh = rng.uniform(0.1, 0.4, (batch, M, 2))
    boxes = onp.concatenate(
        [cls, x1, y1, onp.minimum(x1 + wh[..., :1], 1.0),
         onp.minimum(y1 + wh[..., 1:], 1.0)], axis=-1).astype("float32")
    return trainer, x, nd.array(boxes)


def bench_yolo():
    import jax

    BATCH = 32
    trainer, x, labels = build_yolo_trainer(BATCH)
    # 3.2714e10 conv/dense MACs/img fwd at 416^2/20 classes — summed
    # exactly over every conv_general_dilated/dot_general in our traced
    # forward (2xMACs, fwd x3; same conventions as the R50/BERT lines)
    step_flops, flop_source = _step_flops(
        trainer, x, labels, BATCH * 3 * 2 * 3.2714e10)
    for _ in range(3):
        loss = trainer.step(x, labels)
    float(loss.astype("float32").asnumpy())

    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, labels)
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    imgs = BATCH * steps / dt
    mfu = steps * step_flops / dt / _peak_bf16()
    emit("yolo3_darknet53_train_throughput", round(imgs, 2), "img/s/chip",
         None, "none", mfu=round(mfu, 4), flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2))
    _DETAILS[-1].update(
        batch=BATCH, image_size=416, num_classes=20, dtype="bfloat16",
        platform=jax.devices()[0].platform,
        loss=float(loss.astype("float32").asnumpy()))


def bench_int8():
    """INT8 PTQ serving line (reference: calibrated int8 deployment,
    src/operator/quantization/): ResNet-50 inference, minmax-calibrated
    int8 convs/dense on the MXU vs the bf16 net, batch 256."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.gluon.model_zoo import get_model

    B = 256
    rng = onp.random.RandomState(0)
    x_np = rng.randn(B, 3, 224, 224).astype("float32")

    def infer_rate(net, x):
        net.hybridize(static_alloc=True)
        for _ in range(10):
            out = net(x)
        float(out.asnumpy().ravel()[0])
        t0 = time.perf_counter()
        for _ in range(20):
            out = net(x)
        float(out.asnumpy().ravel()[0])
        return B * 20 / (time.perf_counter() - t0)

    mx.random.seed(0)
    net = get_model("resnet50_v1", classes=1000)
    net.initialize()
    net.cast("bfloat16")
    bf16 = infer_rate(net, nd.array(x_np).astype("bfloat16"))

    mx.random.seed(0)
    net = get_model("resnet50_v1", classes=1000)
    net.initialize()
    q.quantize_net(net, calib_data=[nd.array(x_np[:32])],
                   calib_mode="naive")
    # bf16 feed keeps the non-quantized glue (BN/ReLU/pool) and all
    # inter-layer activations at bf16 width; the convs run int8 on the MXU
    int8 = infer_rate(net, nd.array(x_np).astype("bfloat16"))

    emit("resnet50_int8_infer_throughput", round(int8, 1), "img/s/chip",
         round(int8 / bf16, 3), "vs_our_bf16",
         bf16_img_s=round(bf16, 1))
    _DETAILS[-1].update(
        batch=B, calib="naive minmax, 32 imgs",
        platform=jax.devices()[0].platform,
        note="int8 path: per-layer minmax requantize, int8 MXU convs/"
             "dense, dequant epilogues in the activation dtype "
             "(bf16-resident between layers)")


def bert_train_flops_per_token(seq_len=512, max_pred=80, d=768, h=3072,
                               layers=12):
    """FLOPs/token for the BERT pretraining step (2xMACs convention,
    fwd x3 for fwd+bwd; flash-attention recompute not counted — same
    discipline as the ResNet number which also ignores remat)."""
    vocab = 30522
    per_tok_macs = layers * (4 * d * d + 2 * d * h)       # qkv+out+ffn
    per_tok_macs += layers * 2 * seq_len * d              # qk^T + av
    per_tok_macs += (max_pred / seq_len) * (d * d + d * vocab)  # mlm head
    return 3 * 2 * per_tok_macs


def bench_bert():
    import jax

    BATCH, L, M = 32, 512, 80
    trainer, data, labels = build_bert_trainer(BATCH, L, M)
    step_flops, flop_source = _step_flops(
        trainer, data, labels,
        BATCH * L * bert_train_flops_per_token(L, M))
    for _ in range(3):
        loss = trainer.step(data, labels)
    float(loss.astype("float32").asnumpy())

    steps = 20
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(data, labels)
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    toks_per_sec = BATCH * L * steps / dt
    platform = jax.devices()[0].platform
    mfu = steps * step_flops / dt / _peak_bf16()
    baseline = 2500.0  # V100 tok/s (BASELINE.md, GluonNLP scripts/bert)
    emit("bert_base_pretrain_throughput", round(toks_per_sec, 1),
         "tok/s/chip", round(toks_per_sec / baseline, 3),
         "v100_anchor_unverified", mfu=round(mfu, 4),
         flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2))
    _DETAILS[-1].update(
        batch=BATCH, seq_len=L, max_predictions=M, dtype="bfloat16",
        platform=platform, loss=float(loss.astype("float32").asnumpy()))


def bench_bert_large():
    """BERT-large single-chip line at B=4 — the config that fits this
    host's 16 GB HBM (PROGRESS r4); the intended multi-chip dp×tp+ZeRO-1
    configuration is validated by __graft_entry__.dryrun_multichip's
    bert-large mode with a per-device byte assertion."""
    import jax

    BATCH, L, M = 4, 512, 80
    trainer, data, labels = build_bert_trainer(
        BATCH, L, M, num_layers=24, units=1024, hidden_size=4096,
        num_heads=16)
    step_flops, flop_source = _step_flops(
        trainer, data, labels,
        BATCH * L * bert_train_flops_per_token(L, M, d=1024, h=4096,
                                               layers=24))
    for _ in range(3):
        loss = trainer.step(data, labels)
    float(loss.astype("float32").asnumpy())

    steps = 20
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(data, labels)
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    toks = BATCH * L * steps / dt
    mfu = steps * step_flops / dt / _peak_bf16()
    emit("bert_large_pretrain_throughput", round(toks, 1), "tok/s/chip",
         None, "none", mfu=round(mfu, 4), flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2))
    _DETAILS[-1].update(
        batch=BATCH, seq_len=L, max_predictions=M, dtype="bfloat16",
        arch="bert_large (24L, 1024d, 4096h, 16 heads)",
        note="B=4 is the single-16GB-chip HBM limit; multi-chip dp*tp+"
             "ZeRO-1 is the intended config (dryrun_multichip bert-large "
             "mode asserts per-device bytes)",
        platform=jax.devices()[0].platform,
        loss=float(loss.astype("float32").asnumpy()))


def build_ssd_trainer(batch, num_classes=20):
    """SSD-300 training step (GluonCV SSD-300 recipe shape, SURVEY §6):
    forward + MultiBoxTarget assignment + hard-negative-mining loss +
    SGD, all inside the one jitted program; synthetic device-resident
    batch (same discipline as the YOLO line)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.models import (MultiBoxTarget, SSDMultiBoxLoss,
                                  ssd_300_resnet18)

    mx.random.seed(0)
    net = ssd_300_resnet18(num_classes=num_classes)
    net.initialize()
    net.cast("bfloat16")
    # one eager forward materializes anchors/feature sizes
    warm = nd.array(onp.zeros((2, 3, 300, 300), dtype="float32")) \
        .astype("bfloat16")
    net(warm)
    anchors = net.anchors.astype("float32")

    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    loss_core = SSDMultiBoxLoss()

    def loss_fn(outs, labels):
        cls_pred, box_pred = outs
        bt, bm, ct = MultiBoxTarget(anchors, labels)
        s, _, _ = loss_core(cls_pred.astype("float32"),
                            box_pred.astype("float32"), ct, bt, bm)
        return s.mean()

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.SGD(learning_rate=1e-3, momentum=0.9), mesh)

    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(batch, 3, 300, 300).astype("float32")) \
        .astype("bfloat16")
    M = 8
    cls = rng.randint(0, num_classes, (batch, M, 1)).astype("float32")
    cls[:, 4:] = -1.0
    x1 = rng.uniform(0.0, 0.6, (batch, M, 1))
    y1 = rng.uniform(0.0, 0.6, (batch, M, 1))
    wh = rng.uniform(0.1, 0.4, (batch, M, 2))
    boxes = onp.concatenate(
        [cls, x1, y1, onp.minimum(x1 + wh[..., :1], 1.0),
         onp.minimum(y1 + wh[..., 1:], 1.0)], axis=-1).astype("float32")
    return trainer, x, nd.array(boxes)


def bench_ssd():
    import jax

    BATCH = 32
    trainer, x, labels = build_ssd_trainer(BATCH)
    # 1.7222e10 conv/dense MACs/img fwd at 300^2/20 classes — counted
    # exactly over the traced forward by benchmark/count_macs.py (2xMACs,
    # fwd x3; same conventions as the R50/BERT/YOLO lines).  Constant for
    # the 6-stage GluonCV-layout SSD (heads at strides 8-64, r5)
    step_flops, flop_source = _step_flops(
        trainer, x, labels, BATCH * 3 * 2 * 1.7222e10)
    for _ in range(3):
        loss = trainer.step(x, labels)
    float(loss.astype("float32").asnumpy())

    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, labels)
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    imgs = BATCH * steps / dt
    mfu = steps * step_flops / dt / _peak_bf16()
    emit("ssd300_train_throughput", round(imgs, 2), "img/s/chip",
         None, "none", mfu=round(mfu, 4), flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2))
    _DETAILS[-1].update(
        batch=BATCH, image_size=300, num_classes=20, dtype="bfloat16",
        platform=jax.devices()[0].platform,
        loss=float(loss.astype("float32").asnumpy()))


def bench_moe():
    """Single-chip MoE perf line (SURVEY §2.3 EP — greenfield, no
    reference analogue): Switch/GShard-style position-wise FFN MoE
    training step at transformer-base width."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.parallel import moe

    B, L, d, h, E, K, CF, G = 8, 2048, 768, 3072, 8, 2, 1.25, 16
    mx.random.seed(0)

    class _MoENet(HybridBlock):
        """MoE layer + its router aux loss as a second output, so the
        whole step (fwd + aux + bwd + update) is ONE jitted program."""

        def __init__(self, **kw):
            super().__init__(**kw)
            self.moe = moe.MoE(units=d, hidden_size=h, num_experts=E,
                               k=K, capacity_factor=CF, num_groups=G,
                               dtype="bfloat16")

        def forward(self, x):
            with moe.aux_loss_scope() as aux:
                y = self.moe(x)
            return y, moe.collected_aux_loss(aux)

        hybrid_forward = None

    net = _MoENet()
    net.initialize()
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])

    def loss_fn(outs, label):
        y, aux = outs
        return (y.astype("float32") ** 2).mean() + 0.01 * aux

    trainer = parallel.SPMDTrainer(
        net, loss_fn, opt.Adam(learning_rate=1e-3), mesh)

    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(B, L, d).astype("float32")).astype("bfloat16")
    zero = nd.array(onp.zeros((1,), dtype="float32"))

    T = B * L
    cap = net.moe.capacity(T // G)   # per-group capacity (GShard groups)

    # static-shape MoE step MACs: router T*E*d + dispatch/combine einsums
    # 2*T*E*c*d at the PER-GROUP capacity c + expert FFNs G*E*c*2*d*h
    # (every slot computed whether or not a token fills it — that IS the
    # cost model of static routing)
    macs = T * E * d + 2 * T * E * cap * d + G * E * cap * 2 * d * h
    step_flops, flop_source = _step_flops(trainer, x, zero, 3 * 2 * macs)

    for _ in range(3):
        loss = trainer.step(x, zero)
    float(loss.astype("float32").asnumpy())
    steps = 20
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, zero)
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    toks = T * steps / dt
    mfu = steps * step_flops / dt / _peak_bf16()
    # measured drop rate at this batch: fraction of (token, k) assignments
    # that found no capacity slot in their group — computed from the
    # TRAINED router's own logits over the bench batch (not a synthetic
    # distribution)
    from mxnet_tpu.ndarray.ndarray import unwrap
    gate = unwrap(net.moe.gate_weight.data()).astype(jnp.float32)
    x2d = unwrap(x).reshape(T, d).astype(jnp.float32)
    probs = jax.nn.softmax(x2d @ gate.T, axis=-1).reshape(G, T // G, E)
    combine, _ = jax.vmap(lambda p: moe.moe_dispatch(p, K, cap))(probs)
    kept = float(onp.asarray((combine > 0).sum())) / (T * K)
    emit("moe_ffn_train_throughput", round(toks, 1), "tok/s/chip",
         None, "none", mfu=round(mfu, 4), flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2),
         drop_rate=round(1.0 - kept, 4))
    _DETAILS[-1].update(
        batch=B, seq_len=L, units=d, hidden=h, experts=E, k=K,
        capacity_factor=CF, capacity=cap, dtype="bfloat16",
        platform=jax.devices()[0].platform,
        loss=float(loss.astype("float32").asnumpy()))


def bench_longctx():
    """Long-context demonstration (SURVEY §5.7): single-chip flash
    attention fwd+bwd at seq 32k — a length the reference's O(L^2) dense
    score path cannot represent at all (32k^2 fp32 scores = 4 GB/head).
    ``vs_baseline`` reports the context-length ratio over the reference's
    512-token BERT attention cap."""
    import jax
    import jax.numpy as jnp

    B, H, L, D = 1, 16, 32768, 64
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, L, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, L, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, L, D), jnp.bfloat16)

    from mxnet_tpu.ops.flash_attention import flash_attention

    def train(q, k, v):
        def loss(q, k, v):
            return (flash_attention(q, k, v, True, None)
                    .astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(train).lower(q, k, v).compile()
    g = compiled(q, k, v)
    onp.asarray(g[0][0, 0, 0])  # sync: host read of a dependent value
    steps = 5
    t0 = time.perf_counter()
    for _ in range(steps):
        g = compiled(q, k, v)
    onp.asarray(g[0][0, 0, 0])
    dt = (time.perf_counter() - t0) / steps

    ms = jax.local_devices()[0].memory_stats()
    peak_gb = round(ms["peak_bytes_in_use"] / 2 ** 30, 3)
    mem_source = "backend_memory_stats"
    toks = B * L / dt
    emit("flash_attention_seq32k_train_throughput", round(toks, 1),
         "tok/s/chip", round(L / 512, 1), "ctx_ratio_vs_512cap",
         step_ms=round(dt * 1000, 2), peak_hbm_gb=peak_gb,
         mem_source=mem_source)
    _DETAILS[-1].update(batch=B, heads=H, seq_len=L, head_dim=D,
                        causal=True, dtype="bfloat16")


def bench_r50():
    import jax

    BATCH = 256
    trainer, x, y = build_r50_trainer(BATCH)

    # R50 v1 @224 forward = 3.858e9 MACs = 7.716e9 FLOPs (multiply and add
    # counted separately — the standard MFU convention, same as PaLM's
    # 6N-per-token and MLPerf).  Counted exactly over the traced program
    # by benchmark/count_macs.py: our BottleneckV1 puts the stride on the
    # first 1x1 conv (upstream model_zoo parity) = the paper's 3.86-GMAC
    # v1; rounds 1-4 used 4.087e9, the stride-on-3x3 v1.5 figure, which
    # overstated MFU by ~5.9%.  Training ~3x forward (fwd + dgrad + wgrad).
    step_flops, flop_source = _step_flops(
        trainer, x, y, BATCH * 3 * 2 * 3.858e9)

    # warmup / compile; the host read of the loss is the sync
    for _ in range(3):
        loss = trainer.step(x, y)
    float(loss.astype("float32").asnumpy())

    steps = 20
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, y)
    # the final loss depends transitively on all prior steps' updates
    float(loss.astype("float32").asnumpy())
    dt = time.perf_counter() - t0

    imgs_per_sec = BATCH * steps / dt
    platform = jax.devices()[0].platform
    mfu = steps * step_flops / dt / _peak_bf16()
    baseline = 360.0  # V100 fp32 img/s (BASELINE.md)

    emit("resnet50_v1_train_throughput", round(imgs_per_sec, 2),
         "img/s/chip", round(imgs_per_sec / baseline, 3),
         "v100_anchor_unverified", mfu=round(mfu, 4),
         flop_source=flop_source,
         step_ms=round(1000 * dt / steps, 2))
    _DETAILS[-1].update(
        batch=BATCH, baseline_batch_per_gpu=64, dtype="bfloat16",
        platform=platform, loss=float(loss.astype("float32").asnumpy()))


def _sentinel_check():
    """``--check`` gate: compare this run's fresh records against the
    committed BENCH_DETAILS trajectory through tools/perf_sentinel.py
    (noise-aware per-metric tolerances, parseable verdict lines).
    Returns the process exit code; the committed file is NOT rewritten —
    a regressed run must not overwrite the baseline it failed against."""
    import importlib.util
    import os
    repo = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "perf_sentinel", os.path.join(repo, "tools", "perf_sentinel.py"))
    ps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ps)
    path = os.path.join(repo, "benchmark", "BENCH_DETAILS.json")
    try:
        with open(path) as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(json.dumps({"error": "sentinel_no_baseline",
                          "detail": str(e)}), flush=True)
        return 1
    # this run must reproduce every training metric bench.py owns in the
    # committed trajectory; missing = the workload crashed = a failure
    required = [str(r.get("metric")) for r in baseline
                if r.get("metric") and not _keep_foreign(r)]
    verdicts = ps.compare(_DETAILS, baseline, require=required)
    return ps.render(verdicts, out=sys.stdout)


def main():
    check_mode = "--check" in sys.argv[1:]
    import jax
    if jax.default_backend() != "tpu":
        # every line below is a per-chip rate and an MFU against the
        # chip's peak: there is nothing to print without the chip
        sys.exit(f"bench.py needs a TPU: jax.default_backend() is "
                 f"{jax.default_backend()!r}")

    # ascending importance — the driver records a fixed-size stdout TAIL,
    # so the headline lines (BERT, ResNet-50) print LAST; each bench is
    # isolated so one failure cannot clip the lines after it, and any
    # failure makes the exit code non-zero.
    failed = []
    for fn in (bench_moe, bench_int8, bench_ssd, bench_yolo,
               bench_bert_large, bench_longctx, bench_transformer,
               bench_bert, bench_r50):
        try:
            fn()
        except Exception:
            traceback.print_exc(file=sys.stderr)
            failed.append(fn.__name__)
    if check_mode:
        # CI-style perf gate (opt-in): fresh records vs the committed
        # trajectory; read-only — pass/regress verdict lines + exit code
        sys.exit(_sentinel_check() or bool(failed))
    _write_details()
    if failed:
        sys.exit(f"bench.py: workloads failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
