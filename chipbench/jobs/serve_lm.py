"""Job kind ``serve_lm``: a decoder-only LM served as the program serves
today: ``TransformerLM`` -> ``GenerationEngine`` -> ``ModelServer`` over
loopback HTTP, float32 weights and KV ring.

This process holds the chip and runs the server and nothing of the load:
the clients are ``load_processes`` children (``chipbench/loadgen.py``,
``JAX_PLATFORMS=cpu``) that are started first, so that their imports
overlap the model build, and stamp tokens on the machine-wide monotonic
clock.  The window opens ``warmup_s`` after the first session starts and
closes ``--seconds`` later; a request in flight at either edge gives the
window the tokens that arrived inside it, and is counted as attempted only
if it completed inside it.
"""
import json
import os
import subprocess
import sys
import time

import numpy as onp

from .. import common, required
from ..common import say

END_TO_END = {"serve_tokens_per_s": "tokens/s", "ttft_p95_ms": "ms",
              "itl_p95_ms": "ms"}
MODULE_ROLES = {"decode": {"prefix": "jit_pure", "pick": "most_frequent"},
                "prefill": {"prefix": "jit_pure", "pick": "rest"}}


def shape_of(cfg):
    s = cfg["serving"]
    return {"units": cfg["n_embd"], "hidden_size": 4 * cfg["n_embd"],
            "num_layers": cfg["n_layer"], "num_heads": cfg["n_head"],
            "vocab_size": cfg["vocab_size"], "max_length": cfg["n_positions"],
            "weight_bytes": {"float32": 4, "bfloat16": 2}[s["weight_dtype"]],
            "kv_bytes": {"float32": 4, "bfloat16": 2}[s["kv_dtype"]]}


def build_lm(shape, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import TransformerLM
    mx.random.seed(seed)
    net = TransformerLM(vocab_size=shape["vocab_size"],
                        num_layers=shape["num_layers"], units=shape["units"],
                        hidden_size=shape["hidden_size"],
                        num_heads=shape["num_heads"],
                        max_length=shape["max_length"])
    net.initialize()
    net(nd.array(onp.zeros((1, 4), onp.int32)),
        nd.array(onp.asarray([4], onp.int32)))       # materialize params
    return net


def start_children(n):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return [subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen"], cwd=common.REPO,
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        for _ in range(n)]


def stop_children(children):
    for c in children:
        if c.poll() is None:
            c.kill()
        c.wait()


def run(cell, cfg, traffic, args, devs, tracer):
    children = start_children(traffic["load_processes"])
    try:
        return serve(cfg, traffic, args, devs, tracer, children)
    finally:
        stop_children(children)


def serve(cfg, traffic, args, devs, tracer, children):
    from mxnet_tpu import compile as mx_compile
    from mxnet_tpu import serving
    from mxnet_tpu.serving.generate import GenerationEngine
    mx_compile.enable_persistent_cache()
    compiles = common.count_compiles()
    shape, s = shape_of(cfg), cfg["serving"]
    phases = {"import_s": time.perf_counter() - common.T_PROCESS_START}
    t = time.perf_counter()
    net = build_lm(shape, common.fold_seed(args.seed))
    phases["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = GenerationEngine(net, slots=s["slots"], max_len=s["max_len"],
                              prefill_buckets=tuple(s["prefill_buckets"]),
                              max_queue=s["max_queue"])
    phases["engine_s"] = time.perf_counter() - t
    predict = serving.InferenceEngine(lambda x: (onp.asarray(x),),
                                      batch_buckets=(1,))
    gen = common.plugin("generators", traffic["generator"])
    plan = gen.sessions(traffic, common.fold_seed(args.seed, 1))
    for i, sess in enumerate(plan):
        sess["index"] = i
    with serving.ModelServer(predict, port=0, generator=engine) as srv:
        # one request through every bucket and the decode program
        t = time.perf_counter()
        client = serving.ServingClient(srv.url, timeout_s=120.0, pool=False)
        for b in engine.prefill_buckets:
            client.generate([1] * b, max_new_tokens=2)
        phases["warmup_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for c in children:
            if c.stdout.readline().strip() != "ready":
                common.fail("a load generator child did not start")
        phases["children_wait_s"] = time.perf_counter() - t
        counters = engine.metrics.stats()["counters"]
        say(phase="setup", **phases, kv_cache_bytes=engine.kv_cache_bytes,
            programs_compiled=compiles[0], sessions=len(plan),
            programs={"compiled": counters["prefill_compiles"]
                      + counters["decode_compiles"],
                      "warm_loaded": counters["prefill_cache_hits"]
                      + counters["decode_cache_hits"]})

        t_go = time.monotonic() + 0.2
        for r, c in enumerate(children):
            c.stdin.write(json.dumps({
                "url": srv.url, "vocab": shape["vocab_size"], "t_go": t_go,
                "timeout_s": 120.0,
                "sessions": plan[r::len(children)]}) + "\n")
            c.stdin.flush()
        t0 = t_go + traffic["warmup_s"]
        time.sleep(max(0.0, t0 - time.monotonic()))
        # -- the window ----------------------------------------------------
        setup_s = time.perf_counter() - common.T_PROCESS_START
        compiled_before = compiles[0]
        before = engine.metrics.stats()
        t1 = t0 + args.seconds
        while time.monotonic() < t1:
            tracer.poll(time.monotonic() - t0)
            time.sleep(min(0.05, max(0.0, t1 - time.monotonic())))
        after = engine.metrics.stats()
        in_window = compiles[0] - compiled_before
        peak = common.memory_peak_bytes(devs)
        tracer.finish()
        records, unfinished = [], 0
        for c in children:
            c.stdin.write("stop\n")
            c.stdin.flush()
        for c in children:
            got = json.loads(c.stdout.readline())
            records += got["records"]
            unfinished += got["unfinished"]
        obs = window_numbers(records, t0, t1)
        say(phase="window", **obs["summary"], unfinished_threads=unfinished,
            compilations_in_window=in_window,
            gauges_at_close=after["gauges"],
            memory_stats=devs[0].memory_stats())
        # the clients have hung up; what was in flight decodes to its end
        # in the background while the check runs
        agrees, check = check_outputs(cfg, shape, net, obs["completed"],
                                      common.fold_seed(args.seed, 2))
        say(phase="check", **check)
    delta = {k: after["counters"][k] - before["counters"][k]
             for k in after["counters"]}
    active = delta["tokens_generated"] / max(1, delta["decode_steps"])
    context = active * obs["mean_context_tokens"]   # valid KV positions a step
    failed = len(obs["failed"])
    return {
        "correct": bool(failed == 0 and agrees and obs["completed"]),
        "attempted": len(obs["completed"]) + failed, "failed": failed,
        "setup_s": setup_s, "memory_peak_bytes": peak,
        "end_to_end": obs["end_to_end"],
        "readings": {
            "phases": phases, "roles": MODULE_ROLES,
            "counters": delta, "compile_keys": ["engine_s"],
            "required": {"decode": {
                "flops": required.lm_decode_step_flops(shape, active, context),
                "bytes": required.lm_decode_step_bytes(shape, context)}},
            "client_ttft_ms": obs["client_ttft_ms"],
            "engine_ttft_ms": obs["engine_ttft_ms"],
            "late_ms": obs["late_ms"],
            "open_loop": traffic["arrivals"]["kind"] != "closed",
        },
    }


def window_numbers(records, t0, t1):
    """Everything the clients' stamps give for the window [t0, t1]."""
    tokens, gaps, ttft, late, contexts = 0, [], [], [], []
    halves = ([], [])                       # a backlog that grows shows here
    wire_client, wire_engine = [], []       # the same requests in both
    completed, failed = [], []
    for r in records:
        st = r["stamps"]
        for i, t in enumerate(st):
            if t0 <= t <= t1:
                tokens += 1
                contexts.append(r["prompt_len"] + i)
                if i:
                    gaps.append(1e3 * (t - st[i - 1]))
        if st and t0 <= st[0] <= t1:
            ttft.append(1e3 * (st[0] - r["due"]))
            halves[st[0] > (t0 + t1) / 2].append(ttft[-1])
            late.append(1e3 * (r["sent"] - r["due"]))
            if r.get("engine_ttft_ms") is not None:
                wire_client.append(1e3 * (st[0] - r["sent"]))
                wire_engine.append(r["engine_ttft_ms"])
        end = st[-1] if st else r.get("sent", r["due"])
        if r["error"] and t0 <= end <= t1:
            failed.append(r)
        elif r["done"] and t0 <= end <= t1:
            completed.append(r)
    if not ttft or not gaps:
        common.fail(f"the window saw {len(ttft)} first tokens and "
                    f"{len(gaps)} gaps: nothing to measure")
    pct = common.percentile
    return {
        "completed": completed, "failed": failed,
        "end_to_end": {"serve_tokens_per_s": tokens / (t1 - t0),
                       "ttft_p95_ms": pct(ttft, 95),
                       "itl_p95_ms": pct(gaps, 95)},
        "client_ttft_ms": wire_client, "engine_ttft_ms": wire_engine,
        "late_ms": late,
        "mean_context_tokens": sum(contexts) / max(1, len(contexts)),
        "summary": {"requests_completed": len(completed),
                    "requests_failed": len(failed),
                    "first_tokens": len(ttft), "tokens_in_window": tokens,
                    "gaps": len(gaps), "ttft_p50_ms": pct(ttft, 50),
                    "ttft_p50_by_half_ms": [pct(h, 50) if h else None
                                            for h in halves],
                    "itl_p50_ms": pct(gaps, 50),
                    "late_p95_ms": pct(late, 95),
                    "errors": sorted({r["error"] for r in failed})[:3]},
    }


def check_outputs(cfg, shape, net, completed, seed):
    """Served tokens against the plain reference, and the cached decode
    path's logits against it.  Decided from tokens and weights alone."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import unwrap
    from ..generators.sessions import prompt_tokens
    from ..reference import lm as ref
    chk = cfg["check"]
    weights = {name: unwrap(p.data())
               for name, p in net._collect_params_with_prefix().items()}
    layers, heads, eps = shape["num_layers"], shape["num_heads"], \
        cfg["program_layer_norm_eps"]
    rng = onp.random.RandomState(seed)
    order = sorted(completed, key=lambda r: (r["session"], r["k"]))
    picks = [order[i] for i in sorted(rng.choice(
        len(order), min(chk["requests"], len(order)), replace=False))]
    pad = shape["max_length"]
    worst = {"margin": -1.0}
    for r in picks:
        prompt = prompt_tokens(shape["vocab_size"], r["token_seed"],
                               r["prompt_len"])
        seq = (prompt + r["tokens"][:-1])[:pad]
        padded = onp.zeros(pad, onp.int32)
        padded[:len(seq)] = seq
        rows = onp.asarray(ref.logits(weights, jnp.asarray(padded), layers,
                                      heads, eps))
        rows = rows[r["prompt_len"] - 1:len(seq)]
        served = onp.asarray(r["tokens"][:len(rows)])
        margin = rows.max(axis=1) - rows[onp.arange(len(rows)), served]
        if float(margin.max()) > worst["margin"]:
            worst = {"margin": float(margin.max()), "session": r["session"],
                     "k": r["k"], "position": int(margin.argmax()),
                     "prompt_len": r["prompt_len"],
                     "logit_std": float(rows.std())}
    diff = cached_decode_diff(net, weights, shape, chk, layers, heads, eps,
                              seed)
    agrees = bool(picks) and worst["margin"] <= chk["margin_tolerance"] \
        and diff <= chk["logits_tolerance"]
    return agrees, {"requests": [[r["session"], r["k"]] for r in picks],
                    "worst": worst,
                    "margin_tolerance": chk["margin_tolerance"],
                    "cached_decode_max_abs_diff": diff,
                    "logits_tolerance": chk["logits_tolerance"],
                    "agrees": bool(agrees)}


def cached_decode_diff(net, weights, shape, chk, layers, heads, eps, seed):
    """``TransformerLM.prefill`` then ``decode_step`` through a ring
    cache, at matmul precision ``highest``, against the reference's full
    forward: the largest absolute difference of logits over the checked
    positions (as ``chip_smoke.py``'s ``logits_check``)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray.ndarray import NDArray
    from ..reference import lm as ref
    p_len, n = chk["decode_prompt"], chk["decode_positions"]
    h, d, m = heads, shape["units"] // heads, 2 * (p_len + n)
    toks = onp.random.RandomState(seed).randint(
        0, shape["vocab_size"], (p_len + n,)).astype("int32")
    want = onp.asarray(ref.logits(weights, jnp.asarray(toks), layers, heads,
                                  eps))[p_len - 1:p_len + n - 1]
    with jax.default_matmul_precision("highest"):
        logits, kvs = net.prefill(nd.array(toks[None, :p_len]),
                                  nd.array(onp.asarray([p_len], onp.int32)))
        got = [logits.asnumpy()[0, p_len - 1]]
        caches = []
        for k, v in kvs:
            ring = onp.zeros((2, 1, h, m, d), onp.float32)
            ring[0, :, :, :p_len] = k.asnumpy()
            ring[1, :, :, :p_len] = v.asnumpy()
            caches.append((NDArray(ring[0]), NDArray(ring[1])))
        for j in range(n - 1):
            logits, caches = net.decode_step(
                nd.array(toks[p_len + j:p_len + j + 1]), caches,
                nd.array(onp.asarray([p_len + j], onp.int32)))
            got.append(logits.asnumpy()[0])
    got = onp.stack(got)
    if not onp.isfinite(got).all():
        return float("inf")
    return float(onp.abs(got - want).max())
