"""Job kind ``serve_lfm2``: LFM2-24B-A2B, cut in depth to one pipeline
stage that fits a chip with every expert of its layers, served as the
program serves a model today: ``LFM2MoeLM`` -> ``GenerationEngine`` ->
``ModelServer`` over loopback HTTP, bfloat16 weights, a bfloat16 conv state
a conv layer and two bfloat16 rings an attention layer.

The load, the clients' stamps and the window's numbers are ``serve_lm``'s
own (its children, ``window_numbers``), the probed requests
``serve_dsv32``'s.  What differs is the model that is built, what a decode
step requires (``required_lfm2``), and ``correct``: the window's served
tokens against the float32 reference run on the program's expert
selections, and a prompt shorter than its bucket through the engine's own
programs, probed, in a slot of the caches the window left, beside the
streams still in flight: logits, router scores, and whether every expert
the program chose was defensible by the reference's scores.  Decided from
tokens and weights alone, never from a time.
"""
import functools
import json
import time

import numpy as onp

from .. import common, required_lfm2
from ..common import say
from .serve_dsv32 import BYTES, probed_requests
from .serve_lm import (END_TO_END, MODULE_ROLES, start_children,  # noqa: F401
                       stop_children, window_numbers)


def model_config(cfg):
    """The configuration's published keys as the model takes them."""
    from mxnet_tpu.models.lfm2 import LFM2_PUBLISHED
    return {k: cfg[k] for k in LFM2_PUBLISHED}


def shape_of(cfg):
    s = cfg["serving"]
    return dict(model_config(cfg), held=cfg["num_experts"],
                weight_bytes=BYTES[s["weight_dtype"]],
                cache_bytes=BYTES[s["kv_dtype"]])


def build(cfg, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.models import LFM2MoeLM
    s = cfg["serving"]
    mx.random.seed(seed)
    net = LFM2MoeLM(model_config(cfg), dtype=s["weight_dtype"],
                    cache_dtype=s["kv_dtype"])
    net.initialize()
    return net


def run(cell, cfg, traffic, args, devs, tracer):
    # a program without the model fails here, before anything is started
    from mxnet_tpu.models import lfm2  # noqa: F401
    children = start_children(traffic["load_processes"])
    try:
        return serve(cfg, traffic, args, devs, tracer, children)
    finally:
        stop_children(children)


def serve(cfg, traffic, args, devs, tracer, children):
    import jax
    from mxnet_tpu import compile as mx_compile
    mx_compile.enable_persistent_cache()    # the weights' makers compile too
    shape = shape_of(cfg)
    compiles = common.count_compiles()
    phases = {"import_s": time.perf_counter() - common.T_PROCESS_START}
    t = time.perf_counter()
    net = build(cfg, common.fold_seed(args.seed))
    jax.block_until_ready(net.norm.data()._data)
    phases["build_s"] = time.perf_counter() - t
    seed = common.fold_seed(args.seed, 2)
    w = serve_window(
        net, cfg["serving"], shape["vocab_size"], traffic, args, devs, tracer,
        children, phases, compiles,
        setup=dict(parameters=sum(int(onp.prod(p.shape))
                                  for p in net._tree_params()),
                   parameters_reckoned=required_lfm2.weight_params(shape)),
        # the clients have hung up; their streams go on in their slots, and
        # the probed requests take the next ones that come free
        after=lambda engine, timeout_s: probed_requests(engine, cfg, seed,
                                                        timeout_s))
    delta, obs = w["counters"], w["obs"]
    steps = max(1, delta["decode_steps"])
    # kv_context_mean.lfm2: cached positions an attention layer reads a step
    delta["attention_layer_steps"] = \
        delta["decode_steps"] * shape["layer_types"].count("full_attention")
    need = {
        "bytes": required_lfm2.decode_step_bytes(
            shape, delta["tokens_generated"] / steps,
            delta["experts_touched"] / steps,
            delta["attn_valid_positions"] / steps),
        "flops": required_lfm2.decode_step_flops(
            shape, delta["tokens_generated"] / steps,
            delta["routed_pairs"] / steps,
            delta["attn_valid_positions"] / steps)}
    say(phase="required", role="decode", **need,
        per_step={k: delta[k] / steps for k, _help in net.step_counters})
    agrees, check = check_outputs(cfg, net, obs["completed"], w["after"],
                                  seed)
    say(phase="check", **check)
    failed = len(obs["failed"])
    return {
        "correct": bool(failed == 0 and agrees and obs["completed"]),
        "attempted": len(obs["completed"]) + failed, "failed": failed,
        "setup_s": w["setup_s"], "memory_peak_bytes": w["peak"],
        "end_to_end": obs["end_to_end"],
        "readings": {
            "phases": phases, "roles": MODULE_ROLES,
            "counters": delta, "compile_keys": ["engine_s"],
            "required": {"decode": need},
        },
    }


def serve_window(net, s, vocab, traffic, args, devs, tracer, children,
                 phases, compiles, setup, after):
    """The serving window of any model that speaks the generation
    protocol, as ``serve_lm`` and ``serve_dsv32`` each run it inside their
    ``serve``: ``net`` behind ``GenerationEngine`` (sized by ``s``, a
    configuration's ``serving``) -> ``ModelServer``; every bucket and the
    decode program warmed; the children given the plan; ``warmup_s`` of
    traffic, then ``--seconds`` of window.  ``after(engine, timeout_s)``
    runs on the live engine when the clients have hung up; then the engine
    is aborted (what is in flight is minutes of decode steps) and its
    caches go back to the device.  Returns ``setup_s``, ``peak``, ``obs``
    (``window_numbers``), ``counters`` (the window's deltas) and
    ``after``'s result; ``phases`` gains the engine's.  The caller has
    turned the persistent compile cache on before it built ``net``."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving.generate import GenerationEngine
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
    timeout_s = s.get("client_timeout_s", 120.0)
    with serving.ModelServer(predict, port=0, generator=engine) as srv:
        # one request through every bucket and the decode program
        t = time.perf_counter()
        client = serving.ServingClient(srv.url, timeout_s=timeout_s,
                                       pool=False)
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
            kv_cache_bytes_by_kind=engine.kv_cache_bytes_by_kind, **setup,
            programs_compiled=compiles[0], sessions=len(plan),
            memory_stats=devs[0].memory_stats(),
            programs={"compiled": counters["prefill_compiles"]
                      + counters["decode_compiles"],
                      "warm_loaded": counters["prefill_cache_hits"]
                      + counters["decode_cache_hits"]})

        t_go = time.monotonic() + 0.2
        for r, c in enumerate(children):
            c.stdin.write(json.dumps({
                "url": srv.url, "vocab": vocab, "t_go": t_go,
                "timeout_s": timeout_s,
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
        stats = engine.metrics.stats()
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
            gauges_at_close=stats["gauges"],
            memory_stats=devs[0].memory_stats())
        result = after(engine, timeout_s)
        engine.abort()
    return {"setup_s": setup_s, "peak": peak, "obs": obs, "after": result,
            "counters": {k: stats["counters"][k] - before["counters"][k]
                         for k in stats["counters"]}}


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------
def selections_within(found, limits):
    return bool(found["router_score_error"] <= limits["router_tolerance"]
                and found["expert_shortfall"] <= limits["router_tolerance"])


def within(found, limits):
    """Whether what :func:`judge` found of a probed request lies within
    the limits."""
    return bool(found["forward_diff"] <= limits["logits_tolerance"]
                and selections_within(found, limits))


def check_outputs(cfg, net, completed, probed, seed):
    """Served requests and the probed ones against the plain reference.
    Returns (agrees, what was found beside each limit)."""
    chk = cfg["check"]
    limits = chk["limits"]
    weights = net.raw_weights()
    rcfg = model_config(cfg)
    rng = onp.random.RandomState(seed)
    order = sorted(completed, key=lambda r: (r["session"], r["k"]))
    picks = [order[i] for i in sorted(rng.choice(
        len(order), min(chk["requests"], len(order)), replace=False))]
    found = served_requests(net, weights, rcfg, cfg, picks) if picks else {}
    through_engine = [probed_path(weights, rcfg, *one) for one in probed]
    ok = bool(picks) and selections_within(found, limits) \
        and found["margin"] <= limits["margin_tolerance"] \
        and found["served_largest_share"] >= limits["served_largest_share_least"] \
        and all(within(c, dict(limits, logits_tolerance=c["logits_tolerance"]))
                for c in through_engine)
    return ok, {"requests": [[r["session"], r["k"]] for r in picks],
                **found, "probed": through_engine, "limits": limits,
                "agrees": ok}


@functools.lru_cache(maxsize=None)
def judge(per_token):
    """A jitted ``(mine, want, biases, selection_rows, logit_rows, logits,
    served) -> {name: scalar}``, over whole [L, ...] arrays with the rows
    that count as masks, so that one program serves every request of a
    length.  ``mine`` holds the program's experts [L, k] and router scores
    [L, E] an expert layer, ``want`` what the reference found on those
    experts, ``biases`` the routers' selection biases.

    * ``router_score_error``: the largest difference of the program's
      router scores from the reference's.
    * ``expert_shortfall``: how far below the reference's
      ``per_token``-th largest biased score the reference scores an expert
      the program chose: 0 where every choice was the reference's own,
      small where near-ties flipped.
    * ``forward_diff``: the largest difference of ``logits`` from the
      reference's; ``margin``: how far below the reference's largest logit
      its logit of the ``served`` token lies, at worst; ``served_largest``:
      the rows whose ``served`` token is the reference's largest."""
    import jax
    import jax.numpy as jnp

    def worst(x, where):
        return jnp.maximum(0.0, jnp.where(where, x, -jnp.inf).max())

    def found(mine, want, biases, selection_rows, logit_rows, logits,
              served):
        rows = selection_rows[:, None]
        errors, shortfalls = [], []
        for idx, own, scores, bias in zip(
                mine["experts"], mine["router_scores"],
                want["router_scores"], biases):
            biased = scores + bias
            kth = jnp.sort(biased, -1)[:, -per_token][:, None]
            errors.append(worst(jnp.abs(own - scores), rows))
            shortfalls.append(worst(
                kth - jnp.take_along_axis(biased, idx, axis=-1), rows))
        lr = want["logits"]
        at_served = jnp.take_along_axis(lr, served[:, None], axis=-1)[:, 0]
        n = jnp.maximum(logit_rows.sum(), 1)
        return {
            "router_score_error": jnp.stack(errors).max(),
            "expert_shortfall": jnp.stack(shortfalls).max(),
            "margin": worst(lr.max(-1) - at_served, logit_rows),
            "served_largest": ((lr.argmax(-1) == served) & logit_rows).sum(),
            "forward_diff": worst(jnp.abs(logits - lr).max(-1), logit_rows),
            "logit_std": jnp.sqrt(jnp.where(
                logit_rows[:, None], lr ** 2, 0.0).sum()
                / (n * lr.shape[-1]))}
    return jax.jit(found)


def _judged(rcfg, weights, mine, want, selection_rows, logit_rows, logits,
            served):
    import jax.numpy as jnp
    biases = [weights[f"layers.{i}.ffn.select_bias"].astype(jnp.float32)
              for i in range(rcfg["num_dense_layers"],
                             rcfg["num_hidden_layers"])]
    out = judge(rcfg["num_experts_per_tok"])(
        mine, {k: want[k] for k in ("router_scores", "logits")}, biases,
        selection_rows, logit_rows, logits, served)
    return {k: (int(v) if k == "served_largest" else float(v))
            for k, v in out.items()}


def served_requests(net, weights, rcfg, cfg, picks):
    """For each picked request, over the positions whose token was
    served: the program's router scores against the reference's, the
    reference's score of every expert the program chose against the
    reference's own k-th, and the served token's logit against the
    largest, in the reference run on the program's choices.  The worst of
    each over the requests.  The program here is its full forward: the
    timed decode program returns no selections.  Every request is padded
    to one length, so each program compiles once.  ``full_forward_diff``,
    that forward's logits against the reference's, is a reading with no
    limit: no cache is in it (the probed path's has both readings)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import lfm2
    from ..generators.sessions import prompt_tokens
    from ..reference import lfm2 as ref
    c = net.config
    pad_to = cfg["check"]["pad_to"]
    seqs = [(prompt_tokens(rcfg["vocab_size"], r["token_seed"],
                           r["prompt_len"]) + r["tokens"][:-1])
            for r in picks]
    L = -(-max(len(s) for s in seqs) // pad_to) * pad_to

    @jax.jit
    def program(w, t):
        logits, _caches, sel = lfm2.run_full(c, w, t[None],
                                             want_selections=True)
        return logits[0], sel
    worst, per_request, largest = {}, [], 0
    for r, seq in zip(picks, seqs):
        t = time.perf_counter()
        toks = onp.zeros(L, onp.int32)
        toks[:len(seq)] = seq
        served = onp.zeros(L, onp.int32)
        served[r["prompt_len"] - 1:len(seq)] = r["tokens"]
        rows = onp.zeros(L, bool)
        rows[r["prompt_len"] - 1:len(seq)] = True
        toks, rows = jnp.asarray(toks), jnp.asarray(rows)
        logits, mine = program(weights, toks)
        want = ref.forward(weights, toks, rcfg,
                           selections={"experts": mine["experts"]})
        one = _judged(rcfg, weights, mine, want, rows, rows, logits,
                      jnp.asarray(served))
        per_request.append(dict(
            one, seconds=time.perf_counter() - t, session=r["session"],
            k=r["k"], prompt_len=r["prompt_len"], length=len(seq)))
        for key in ("margin", "forward_diff", "router_score_error",
                    "expert_shortfall"):
            worst[key] = max(worst.get(key, 0), one[key])
        largest += one["served_largest"]
    worst["full_forward_diff"] = worst.pop("forward_diff")
    return dict(worst, padded_length=L, per_request=per_request,
                served_largest_share=largest / sum(
                    len(r["tokens"]) for r in picks))


def selections_of(head, steps, p_len):
    """The prefill program's selections over its bucket (the first
    ``p_len`` rows are the prompt's) and a decode step's row each, as the
    selections of the whole sequence: experts [L, k] and router scores
    [L, E] an expert layer."""
    import jax.numpy as jnp
    return {name: [jnp.concatenate(
        [jnp.asarray(first)[:p_len]]
        + [jnp.asarray(s[name][i])[None] for s in steps])
        for i, first in enumerate(head[name])]
        for name in ("experts", "router_scores")}


def probed_path(weights, rcfg, case, prompt, result):
    """One probed request (``serve_dsv32.probed_requests``): the engine's
    prefill program at a bucket longer than the prompt into a slot of the
    live caches and its decode program over every slot in flight, against
    the reference's full forward over prompt + tokens run on the experts
    those programs chose: ``forward_diff`` over the emitted positions
    beside the case's ``logits_tolerance``, the router scores and choices
    of every position, and the ``margin`` of each emitted token."""
    import jax.numpy as jnp
    from ..reference import lfm2 as ref
    t0 = time.perf_counter()
    p_len, seen = len(prompt), result["probe"]
    toks = onp.concatenate([prompt, result["tokens"][:-1]]).astype("int32")
    L = len(toks)
    mine = selections_of(seen[0], seen[1:], p_len)
    want = ref.forward(weights, jnp.asarray(toks), rcfg,
                       selections={"experts": mine["experts"]})
    got = jnp.stack([jnp.asarray(s["logits"]) for s in seen])
    logit_rows = jnp.arange(L) >= p_len - 1
    logits = jnp.zeros_like(want["logits"]).at[p_len - 1:].set(got)
    served = jnp.zeros((L,), jnp.int32).at[p_len - 1:].set(
        jnp.asarray(result["tokens"], jnp.int32))
    found = _judged(rcfg, weights, mine, want, jnp.ones((L,), bool),
                    logit_rows, logits, served)
    if not bool(jnp.isfinite(got).all()):
        found["forward_diff"] = float("inf")
    return dict(case, **found, seconds=time.perf_counter() - t0)
