"""Job kind ``train_bert``: BERT pretraining through
``parallel.SPMDTrainer.step``, as ``examples/bert_pretrain.py`` and
``chip_smoke.py`` drive it (the builder below is copied from the latter).

The window is a user's loop and nothing more: batches already on the
device, ``trainer.step`` called back to back, the host kept at most
``run_ahead`` steps in front of the device by waiting on an *old* loss
(which never drains the queue), one ``block_until_ready`` at the end.
Losses and finite flags stay on the device until the window has closed.
"""
import time

import numpy as onp

from .. import common, required
from ..common import say

END_TO_END = {"train_tokens_per_s": "tokens/s"}
MODULE_ROLES = {"step": {"prefix": "jit_step"}}


def shape_of(cfg):
    """The configuration's published keys under the names required.py and
    the generators use."""
    r = cfg["recipe"]
    return {"units": cfg["hidden_size"],
            "hidden_size": cfg["intermediate_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "vocab_size": cfg["vocab_size"],
            "max_length": cfg["max_position_embeddings"],
            "seq_length": r["seq_length"],
            "max_predictions": r["max_predictions"],
            "storage_bytes": {"bfloat16": 2, "float32": 4}[r["dtype"]]}


def build_trainer(cfg, shape, mesh, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import parallel
    from mxnet_tpu.models import BERTModel, BERTPretrainingLoss
    r = cfg["recipe"]
    mx.random.seed(seed)
    net = BERTModel(vocab_size=shape["vocab_size"],
                    token_type_vocab_size=cfg["type_vocab_size"],
                    num_layers=shape["num_layers"], units=shape["units"],
                    hidden_size=shape["hidden_size"],
                    num_heads=shape["num_heads"],
                    max_length=shape["max_length"],
                    dropout=cfg["hidden_dropout_prob"])
    net.initialize()
    if r["dtype"] != "float32":
        mx.amp.convert_hybrid_block(net, r["dtype"])
    loss_core = BERTPretrainingLoss()

    def loss_fn(outputs, labels):
        _, _, nsp_logits, mlm_logits = outputs
        mlab, mw, nsp = labels
        return loss_core(mlm_logits, nsp_logits.astype("float32"),
                         mlab, mw, nsp)

    trainer = parallel.SPMDTrainer(
        net, loss_fn,
        opt.create(r["optimizer"], learning_rate=r["learning_rate"],
                   wd=r["wd"]),
        mesh, skip_nonfinite=r["skip_nonfinite"])
    return net, trainer


def to_nd(batch):
    from mxnet_tpu import nd
    return tuple(tuple(nd.array(a) for a in part) for part in batch)


def run(cell, cfg, traffic, args, devs, tracer):
    import jax
    from jax.profiler import TraceAnnotation
    from mxnet_tpu import compile as mx_compile
    from mxnet_tpu import parallel
    from mxnet_tpu.ndarray.ndarray import unwrap
    mx_compile.enable_persistent_cache()
    compiles = common.count_compiles()
    shape = shape_of(cfg)
    chips = cell["chips"]
    batch = cfg["recipe"]["per_chip_batch"] * chips
    phases = {"import_s": time.perf_counter() - common.T_PROCESS_START}

    t = time.perf_counter()
    mesh = parallel.make_mesh({"data": chips}, devices=devs)
    net, trainer = build_trainer(cfg, shape, mesh, common.fold_seed(args.seed))
    gen = common.plugin("generators", traffic["generator"])
    batches = [to_nd(b) for b in gen.make(
        traffic, shape, common.fold_seed(args.seed, 1), batch)]
    check_np = gen.make(traffic, shape, common.fold_seed(args.seed, 2),
                        batch, count=1)[0]
    check_batch = to_nd(check_np)
    phases["build_s"] = time.perf_counter() - t

    info = trainer.precompile(*batches[0])
    phases["lower_s"], phases["compile_s"] = info["lower_s"], info["compile_s"]
    mosaic_calls = info["compiled"].as_text().count("tpu_custom_call")

    t = time.perf_counter()
    for data, labels in batches[:traffic["warmup_steps"]]:
        loss = trainer.step(data, labels)
    jax.block_until_ready(unwrap(loss))
    phases["warmup_s"] = time.perf_counter() - t
    say(phase="setup", **phases, mosaic_custom_calls=mosaic_calls,
        global_batch=batch, cache_dir=info["cache_dir"],
        programs_compiled=compiles[0])

    # -- the window --------------------------------------------------------
    ahead = traffic["run_ahead"]
    losses, flags = [], []
    compiled_before = compiles[0]
    setup_s = time.perf_counter() - common.T_PROCESS_START
    t0 = time.perf_counter()
    t_end = t0 + args.seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        tracer.poll(now - t0, sync=lambda: losses and
                    jax.block_until_ready(losses[-1]))
        data, labels = batches[len(losses) % len(batches)]
        with TraceAnnotation("chipbench:trainer.step"):
            losses.append(unwrap(trainer.step(data, labels)))
        flags.append(trainer.last_step_finite)
        if len(losses) > ahead:
            with TraceAnnotation("chipbench:wait_old_loss"):
                jax.block_until_ready(losses[-1 - ahead])
    with TraceAnnotation("chipbench:block_until_ready"):
        jax.block_until_ready(losses[-1])
    window_s = time.perf_counter() - t0
    tracer.finish()
    steps = len(losses)
    in_window = compiles[0] - compiled_before
    peak = common.memory_peak_bytes(devs)

    loss_values = onp.asarray([float(x) for x in losses])
    finite = onp.asarray([bool(f) for f in flags])
    bad = int((~onp.isfinite(loss_values) | ~finite).sum())
    say(phase="window", steps=steps, window_s=window_s,
        step_wall_ms=1e3 * window_s / steps,
        loss_first=float(loss_values[0]), loss_last=float(loss_values[-1]),
        nonfinite_steps=bad, compilations_in_window=in_window,
        memory_stats=devs[0].memory_stats())

    agrees, check = check_loss(cfg, shape, net, trainer, check_np,
                               check_batch)
    say(phase="check", **check)
    tokens = batch * shape["seq_length"]
    return {
        "correct": bool(bad == 0 and agrees),
        "attempted": steps + 1, "failed": bad + (0 if agrees else 1),
        "setup_s": setup_s, "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": tokens * steps / window_s},
        "readings": {
            "phases": phases, "roles": MODULE_ROLES,
            "compile_keys": ["lower_s", "compile_s"],
            # per chip and per run of the role's program
            "required": {"step": {
                "flops": required.bert_step_flops(shape, batch // chips),
                "bytes": required.bert_step_bytes(shape, batch // chips)}},
        },
    }


def check_loss(cfg, shape, net, trainer, check_np, check_batch):
    """On a batch the window never saw, against the plain float32
    reference on the same weights: the logits of the program's own
    inference path (``HybridBlock.inference_fn``: bfloat16, the forward
    kernels, no dropout), and the loss of one more training step (which
    has dropout, so it agrees only loosely)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import unwrap
    from ..reference import bert as ref
    chk = cfg["check"]
    # copies: the step donates the buffers it updates
    weights = {name: jnp.array(unwrap(p.data()), dtype=jnp.float32)
               for name, p in net._collect_params_with_prefix().items()}
    data, labels = ([jnp.asarray(a) for a in part] for part in check_np)
    pure_fn, read_params = net.inference_fn()
    _seq, _pooled, nsp_got, mlm_got = jax.jit(pure_fn)(read_params(), *data)
    step_loss = float(unwrap(trainer.step(*check_batch)))
    finite = bool(trainer.last_step_finite)
    mlm_want, nsp_want = ref.logits(weights, data, shape["num_layers"],
                                    shape["num_heads"], cfg["layer_norm_eps"])
    mlm, nsp = (float(x) for x in ref.loss(mlm_want, nsp_want, *labels))
    scale = float(jnp.std(mlm_want))
    mlm_err = float(jnp.abs(mlm_got.astype(jnp.float32) - mlm_want).max())
    nsp_err = float(jnp.abs(nsp_got.astype(jnp.float32) - nsp_want).max())
    agrees = finite \
        and abs(step_loss - mlm - nsp) <= chk["loss_tolerance"] \
        and mlm_err <= chk["logits_tolerance_in_std"] * scale \
        and nsp_err <= chk["logits_tolerance_in_std"] * scale
    return agrees, {"step_loss": step_loss, "reference_mlm": mlm,
                    "reference_nsp": nsp,
                    "difference": step_loss - mlm - nsp,
                    "loss_tolerance": chk["loss_tolerance"],
                    "mlm_logits_max_abs_diff": mlm_err,
                    "nsp_logits_max_abs_diff": nsp_err,
                    "reference_logits_std": scale,
                    "logits_tolerance_in_std": chk["logits_tolerance_in_std"],
                    "finite": finite, "agrees": bool(agrees)}
