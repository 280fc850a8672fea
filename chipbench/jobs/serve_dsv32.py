"""Job kind ``serve_dsv32``: DeepSeek-V3.2-Exp, cut to one chip's share of
its deployment, served as the program serves a model today:
``DeepSeekV32LM`` -> ``GenerationEngine`` -> ``ModelServer`` over loopback
HTTP, bfloat16 weights and two bfloat16 rings a layer.

The load, the window and the clients' stamps are ``serve_lm``'s own (its
children, ``window_numbers``).  What differs is the model that is built,
what a decode step requires (``required_dsv32``), and ``correct``: besides
logits, the indexer's and the routers' scores against the float32
reference, whether every discrete choice the program made was defensible
by the reference's scores, and the cached path with the selection active:
once through the engine's own programs, probed, in a slot of the rings
the window left, beside the streams still in flight.  Decided from tokens
and weights alone, never from a time.
"""
import functools
import json
import time

import numpy as onp

from .. import common, required_dsv32
from ..common import say
from .serve_lm import (END_TO_END, MODULE_ROLES, start_children,  # noqa: F401
                       stop_children, window_numbers)

BYTES = {"float32": 4, "bfloat16": 2, "float8_e4m3fn": 1}


def model_config(cfg):
    """The configuration's published keys as the model takes them: its
    ``n_routed_experts`` counts the experts held here, the router keeps
    the deployment's width."""
    from mxnet_tpu.models.deepseek import V32_PUBLISHED
    dep = cfg["deployment"]
    model = {k: cfg[k] for k in V32_PUBLISHED}
    held = (dep["rank"] * cfg["n_routed_experts"], cfg["n_routed_experts"])
    model["n_routed_experts"] = dep["router_width"]
    return model, held


def shape_of(cfg):
    model, held = model_config(cfg)
    s = cfg["serving"]
    return dict(model, router_width=model["n_routed_experts"], held=held[1],
                weight_bytes=BYTES[s["weight_dtype"]],
                cache_bytes=BYTES[s["kv_dtype"]])


def build(cfg, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.models import DeepSeekV32LM
    model, held = model_config(cfg)
    s = cfg["serving"]
    mx.random.seed(seed)
    net = DeepSeekV32LM(model, held=held, dtype=s["weight_dtype"],
                        cache_dtype=s["kv_dtype"])
    net.initialize()
    return net


def run(cell, cfg, traffic, args, devs, tracer):
    children = start_children(traffic["load_processes"])
    try:
        return serve(cfg, traffic, args, devs, tracer, children)
    finally:
        stop_children(children)


def serve(cfg, traffic, args, devs, tracer, children):
    import jax
    from mxnet_tpu import compile as mx_compile
    from mxnet_tpu import serving
    from mxnet_tpu.serving.generate import GenerationEngine
    mx_compile.enable_persistent_cache()
    compiles = common.count_compiles()
    shape, s = shape_of(cfg), cfg["serving"]
    phases = {"import_s": time.perf_counter() - common.T_PROCESS_START}
    t = time.perf_counter()
    net = build(cfg, common.fold_seed(args.seed))
    jax.block_until_ready(net.head.data()._data)
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
            kv_cache_bytes_by_kind=engine.kv_cache_bytes_by_kind,
            parameters=sum(int(onp.prod(p.shape))
                           for p in net._tree_params()),
            parameters_reckoned=required_dsv32.weight_params(shape),
            programs_compiled=compiles[0], sessions=len(plan),
            memory_stats=devs[0].memory_stats(),
            programs={"compiled": counters["prefill_compiles"]
                      + counters["decode_compiles"],
                      "warm_loaded": counters["prefill_cache_hits"]
                      + counters["decode_cache_hits"]})

        t_go = time.monotonic() + 0.2
        for r, c in enumerate(children):
            c.stdin.write(json.dumps({
                "url": srv.url, "vocab": shape["vocab_size"], "t_go": t_go,
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
        # the clients have hung up; their streams go on in their slots,
        # and the probed requests take the next ones that come free
        probed = probed_requests(engine, cfg, common.fold_seed(args.seed, 2),
                                 timeout_s)
        # what is in flight is minutes of decode steps: it is dropped, and
        # the rings go back to the device for the reference (one layer in
        # float32 is gigabytes)
        engine.abort()
    delta = {k: after["counters"][k] - before["counters"][k]
             for k in after["counters"]}
    steps = max(1, delta["decode_steps"])
    need = {
        "bytes": required_dsv32.decode_step_bytes(
            shape, delta["experts_touched"] / steps,
            delta["index_valid_positions"] / steps,
            delta["index_selected_positions"] / steps),
        "flops": required_dsv32.decode_step_flops(
            shape, delta["tokens_generated"] / steps,
            delta["routed_pairs_held"] / steps,
            delta["index_valid_positions"] / steps,
            delta["index_selected_positions"] / steps)}
    say(phase="required", role="decode", **need,
        per_step={k: delta[k] / steps for k, _help in net.step_counters})
    agrees, check = check_outputs(cfg, net, obs["completed"], probed,
                                  common.fold_seed(args.seed, 2))
    say(phase="check", **check)
    failed = len(obs["failed"])
    return {
        "correct": bool(failed == 0 and agrees and obs["completed"]),
        "attempted": len(obs["completed"]) + failed, "failed": failed,
        "setup_s": setup_s, "memory_peak_bytes": peak,
        "end_to_end": obs["end_to_end"],
        "readings": {
            "phases": phases, "roles": MODULE_ROLES,
            "counters": delta, "compile_keys": ["engine_s"],
            "required": {"decode": need},
        },
    }


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------
FOUND = ("margin", "forward_diff", "index_score_error_in_std",
         "router_score_error", "position_shortfall_in_std",
         "expert_shortfall", "group_shortfall", "selected_count_wrong")


def reference_config(cfg):
    model, held = model_config(cfg)
    return dict(model, held=held)


def within(found, limits):
    """Whether what :func:`judge` found of a cached path lies within the
    limits."""
    return bool(
        found["forward_diff"] <= limits["logits_tolerance"]
        and selections_within(found, limits))


def selections_within(found, limits):
    return bool(
        found["index_score_error_in_std"] <= limits["index_tolerance_in_std"]
        and found["position_shortfall_in_std"] <= limits["index_tolerance_in_std"]
        and found["router_score_error"] <= limits["router_tolerance"]
        and found["expert_shortfall"] <= limits["router_tolerance"]
        and found["group_shortfall"] <= 2 * limits["router_tolerance"]
        and found["selected_count_wrong"] == 0)


def probed_requests(engine, cfg, seed, timeout_s):
    """The check's own requests, through the engine and probed
    (``GenerationEngine.submit(probe=True)``): ``[(case, prompt, result)]``
    for the cases of ``check.probed``, on the host."""
    out = []
    for i, case in enumerate(cfg["check"]["probed"]):
        prompt = onp.random.RandomState(seed + i).randint(
            0, cfg["vocab_size"], (case["prompt"],)).astype("int32")
        out.append((case, prompt, engine.submit(
            prompt, max_new_tokens=case["positions"], probe=True)))
    return [(case, prompt, stream.result(timeout_s))
            for case, prompt, stream in out]


def check_outputs(cfg, net, completed, probed, seed):
    """Served requests, the probed ones and the cached path against the
    plain reference.  Returns (agrees, what was found beside each
    limit)."""
    chk = cfg["check"]
    limits = chk["limits"]
    weights = net.raw_weights()
    rcfg = reference_config(cfg)
    rng = onp.random.RandomState(seed)
    order = sorted(completed, key=lambda r: (r["session"], r["k"]))
    picks = [order[i] for i in sorted(rng.choice(
        len(order), min(chk["requests"], len(order)), replace=False))]
    found = served_requests(net, weights, rcfg, cfg, picks) if picks else {}
    through_engine = [probed_path(weights, rcfg, *one) for one in probed]
    cached = [cached_path(net, weights, rcfg, case, seed + i)
              for i, case in enumerate(chk["cached"])]
    ok = bool(picks) and selections_within(found, limits) \
        and found["margin"] <= limits["margin_tolerance"] \
        and found["served_largest_share"] >= limits["served_largest_share_least"] \
        and all(within(c, dict(limits, logits_tolerance=c["logits_tolerance"]))
                for c in through_engine + cached)
    return ok, {"requests": [[r["session"], r["k"]] for r in picks],
                **found, "probed": through_engine, "cached": cached,
                "limits": limits, "agrees": ok}


@functools.lru_cache(maxsize=None)
def judge(topk, n_group, topk_group, per_token):
    """A jitted ``(mine, want, biases, selection_rows, logit_rows,
    logits, served) -> {name: scalar}``, over whole [L, ...] arrays with
    the rows that count as masks, so that one program serves every
    request of a length.  ``mine`` holds the program's selections as
    ``deepseek.run_full`` gives them for one sequence, ``want`` what the
    reference found on them, ``biases`` the routers' selection biases.

    * ``index_score_error_in_std`` / ``router_score_error``: the largest
      difference of the program's scores from the reference's, the first
      in units of the reference's scores' standard deviation.
    * ``position_shortfall_in_std`` / ``expert_shortfall`` /
      ``group_shortfall``: how far below the reference's ``topk``-th
      position (``per_token``-th expert among the groups the program
      took, ``topk_group``-th group) the reference scores one the program
      chose: 0 where every choice was the reference's own, small where
      near-ties flipped.
    * ``selected_count_wrong``: rows whose selection does not hold
      ``min(topk, t + 1)`` positions (more only by exact ties at the last
      place, by the program's own scores), or holds an invalid one.
    * ``forward_diff``: the largest difference of ``logits`` from the
      reference's; ``margin``: how far below the reference's largest
      logit its logit of the ``served`` token lies, at worst;
      ``served_largest``: the rows whose ``served`` token is the
      reference's largest."""
    import jax
    import jax.numpy as jnp
    from ..reference import deepseek_v32 as ref
    rcfg = {"n_group": n_group, "topk_group": topk_group,
            "num_experts_per_tok": per_token}

    def worst(x, where):
        return jnp.where(where, x, -jnp.inf).max()

    def positions(mask, own, scores, rows):
        valid = jnp.isfinite(scores)
        n_valid = valid.sum(-1)
        count, ties = mask.sum(-1), 0
        out = {"index_score_error_in_std": 0.0,
               "position_shortfall_in_std": 0.0}
        if own is not None:
            own = jnp.where(valid, own, -jnp.inf)
            kth_own = jnp.sort(own, axis=-1)[:, -min(topk, own.shape[-1])]
            ties = jnp.where(n_valid > topk,
                             (own == kth_own[:, None]).sum(-1) - 1, 0)
        need = jnp.minimum(topk, n_valid)
        wrong = ((count < need) | (count > need + ties)) & rows
        out["selected_count_wrong"] = wrong.sum() \
            + (mask & ~valid & rows[:, None]).sum()
        if own is None or scores.shape[-1] <= topk:
            return out
        use = valid & rows[:, None]
        n = jnp.maximum(use.sum(), 1)
        mean = jnp.where(use, scores, 0.0).sum() / n
        std = jnp.sqrt(jnp.where(use, (scores - mean) ** 2, 0.0).sum() / n)
        kth = jnp.sort(scores, axis=-1)[:, -topk][:, None]
        out["index_score_error_in_std"] = worst(
            jnp.abs(own - scores), use) / std
        out["position_shortfall_in_std"] = worst(
            kth - scores, use & mask) / std
        return out

    def experts(idx, own, scores, bias, rows):
        _idx, group_score = ref.route(rcfg, scores, bias)
        biased = scores + bias
        T, E = biased.shape
        per_group = E // n_group
        kth_group = jnp.sort(group_score, -1)[:, -topk_group][:, None]
        # the per_token-th largest, by the reference, among the groups the
        # program took its experts from: a flip between two near-tied
        # groups is the groups' shortfall, not every expert's behind it
        taken = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], idx // per_group].set(True)
        among = jnp.where(jnp.repeat(taken, per_group, axis=-1), biased,
                          -jnp.inf)
        kth = jnp.sort(among, -1)[:, -per_token][:, None]
        rows = rows[:, None]
        return {
            "router_score_error": worst(jnp.abs(own - scores), rows),
            "group_shortfall": worst(kth_group - jnp.take_along_axis(
                group_score, idx // per_group, axis=-1), rows),
            "expert_shortfall": worst(
                kth - jnp.take_along_axis(biased, idx, axis=-1), rows)}

    def found(mine, want, biases, selection_rows, logit_rows, logits,
              served):
        parts = [positions(m, o, s, selection_rows) for m, o, s in zip(
            mine["positions"], mine["index_scores"], want["index_scores"])]
        parts += [experts(i, o, s, b, selection_rows) for i, o, s, b in zip(
            mine["experts"], mine["router_scores"], want["router_scores"],
            biases)]
        out = {"selected_count_wrong": sum(
            p.pop("selected_count_wrong", 0) for p in parts)}
        for name in parts[0].keys() | parts[-1].keys():
            out[name] = jnp.maximum(0.0, jnp.stack(
                [jnp.asarray(p[name], jnp.float32)
                 for p in parts if name in p]).max())
        lr = want["logits"]
        at_served = jnp.take_along_axis(lr, served[:, None], axis=-1)[:, 0]
        n = jnp.maximum(logit_rows.sum(), 1)
        out["margin"] = worst(lr.max(-1) - at_served, logit_rows)
        out["served_largest"] = ((lr.argmax(-1) == served)
                                 & logit_rows).sum()
        out["forward_diff"] = worst(jnp.abs(logits - lr).max(-1), logit_rows)
        out["logit_std"] = jnp.sqrt(jnp.where(
            logit_rows[:, None], lr ** 2, 0.0).sum() / (n * lr.shape[-1]))
        return out
    return jax.jit(found)


def _judged(rcfg, weights, mine, want, selection_rows, logit_rows, logits,
            served):
    import jax.numpy as jnp
    biases = [weights[f"layers.{i}.ffn.select_bias"].astype(jnp.float32)
              for i in range(rcfg["num_hidden_layers"])
              if f"layers.{i}.ffn.select_bias" in weights]
    out = judge(rcfg["index_topk"], rcfg["n_group"], rcfg["topk_group"],
                rcfg["num_experts_per_tok"])(
        mine, {k: want[k] for k in ("index_scores", "router_scores",
                                    "logits")},
        biases, selection_rows, logit_rows, logits, served)
    return {k: (int(v) if k in ("selected_count_wrong", "served_largest")
                else float(v)) for k, v in out.items()}


def served_requests(net, weights, rcfg, cfg, picks):
    """For each picked request, over the positions whose token was
    served: (a) the program's index and router scores against the
    reference's; (b) the reference's score of every position and expert
    the program chose against the reference's own k-th; (c) the served
    token's logit against the largest, in the reference run on the
    program's choices.  The worst of each over the requests.  Every
    request is padded to one length, so each program compiles once.
    ``forward_diff``, the full forward's logits against that reference's,
    is a reading with no limit: no ring is in it, so storing the rings in
    fewer bits does not move it (the probed path's has both readings)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import deepseek
    from ..generators.sessions import prompt_tokens
    from ..reference import deepseek_v32 as ref
    c = net.config
    pad_to = cfg["check"]["pad_to"]
    seqs = [(prompt_tokens(rcfg["vocab_size"], r["token_seed"],
                           r["prompt_len"]) + r["tokens"][:-1])
            for r in picks]
    L = -(-max(len(s) for s in seqs) // pad_to) * pad_to

    @jax.jit
    def program(w, t):
        logits, _caches, sel = deepseek.run_full(c, w, t[None],
                                                 want_selections=True)
        return logits[0], dict(
            sel, positions=[m[0] for m in sel["positions"]],
            index_scores=[None if s is None else s[0]
                          for s in sel["index_scores"]])
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
        want = ref.forward(weights, toks, rcfg, selections={
            "positions": mine["positions"], "experts": mine["experts"]})
        one = _judged(rcfg, weights, mine, want, rows, rows, logits,
                      jnp.asarray(served))
        per_request.append(dict(
            one, seconds=time.perf_counter() - t, session=r["session"],
            k=r["k"], prompt_len=r["prompt_len"], length=len(seq)))
        for key in FOUND:
            worst[key] = max(worst.get(key, 0), one[key])
        largest += one["served_largest"]
    worst["full_forward_diff"] = worst.pop("forward_diff")
    return dict(worst, padded_length=L, per_request=per_request,
                served_largest_share=largest / sum(
                    len(r["tokens"]) for r in picks))


def cached_path(net, weights, rcfg, case, seed):
    """``prefill`` then ``decode_step`` through both rings, one slot, at
    the timed widths and types but with the case's ``index_topk`` (which
    the engine's programs do not take: a selection of 8 of 24 is this
    job's own two programs), against the reference's full forward run on
    the selections the cached path made: the largest absolute difference
    of logits over the decoded positions (``forward_diff``, beside the
    case's own ``logits_tolerance``), and what :func:`judge` finds of the
    scores and selections of every position (the decode step's among
    them)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import deepseek
    from ..reference import deepseek_v32 as ref
    t0 = time.perf_counter()
    c = net.config
    p_len, n, topk = case["prompt"], case["positions"], case["index_topk"]
    m, L = p_len + 2 * n, p_len + n - 1
    toks = onp.random.RandomState(seed).randint(
        0, rcfg["vocab_size"], (L,)).astype("int32")

    @jax.jit
    def prefill(w, t):
        logits, caches, sel = deepseek.run_full(
            c, w, t[None], index_topk=topk, want_selections=True)
        rings = [tuple(jnp.zeros((1, m) + a.shape[2:], a.dtype)
                       .at[:, :p_len].set(a) for a in layer)
                 for layer in caches]
        return logits[0, -1], rings, sel

    @jax.jit
    def step(w, tok, rings, pos):
        logits, rings, _counts, sel = deepseek.decode(
            c, w, tok, rings, pos, index_topk=topk, want_selections=True)
        return logits[0], rings, jax.tree_util.tree_map(lambda a: a[0], sel)

    first, rings, sel = prefill(weights, jnp.asarray(toks[:p_len]))
    got, steps = [first], []
    for j in range(p_len, L):
        logits, rings, one = step(weights, jnp.asarray(toks[j:j + 1]), rings,
                                  jnp.asarray([j], jnp.int32))
        got.append(logits)
        steps.append(one)

    mine = selections_of(sel, steps, p_len, L)
    k = rcfg["index_topk"] if topk is None else topk
    want = ref.forward(weights, jnp.asarray(toks), rcfg, index_topk=k,
                       selections={"positions": mine["positions"],
                                   "experts": mine["experts"]})
    got = jnp.stack(got)
    logit_rows = jnp.arange(L) >= p_len - 1
    logits = jnp.zeros_like(want["logits"]).at[p_len - 1:].set(got)
    found = _judged(dict(rcfg, index_topk=k), weights, mine, want,
                    jnp.ones((L,), bool), logit_rows, logits,
                    logits.argmax(-1).astype(jnp.int32))
    if not bool(jnp.isfinite(got).all()):
        found["forward_diff"] = float("inf")
    return dict(case, **found, seconds=time.perf_counter() - t0)


def selections_of(head, steps, p_len, L):
    """``run_full``'s selections of one sequence's first ``p_len``
    positions and ``decode``'s of each later one, as the selections of
    the ``L`` positions of the whole: masks and index scores [L, L],
    experts [L, k], router scores [L, E], a layer."""
    import jax.numpy as jnp

    def rows_of(name, i, square):
        first = head[name][i]
        if first is None:
            return None
        if square:          # [1, p, p] and [M] over positions
            first = jnp.asarray(first)[0, :p_len, :p_len]
            first = jnp.pad(first, ((0, 0), (0, L - p_len)),
                            constant_values=False if first.dtype == bool
                            else -jnp.inf)
            return jnp.concatenate(
                [first] + [jnp.asarray(s[name][i])[None, :L] for s in steps])
        return jnp.concatenate(
            [jnp.asarray(first)[:p_len]]
            + [jnp.asarray(s[name][i])[None] for s in steps])
    layers, moe = range(len(head["positions"])), range(len(head["experts"]))
    return {"positions": [rows_of("positions", i, True) for i in layers],
            "index_scores": [rows_of("index_scores", i, True)
                             for i in layers],
            "experts": [rows_of("experts", i, False) for i in moe],
            "router_scores": [rows_of("router_scores", i, False)
                              for i in moe]}


def probed_path(weights, rcfg, case, prompt, result):
    """One probed request (:func:`probed_requests`): the engine's
    prefill program into a slot of the live rings and its decode program
    over every slot in flight, against the reference's full forward over
    prompt + tokens run on the selections those programs made.  As
    :func:`cached_path` finds it: ``forward_diff`` over the emitted
    positions beside the case's ``logits_tolerance``, the scores and
    selections of every position, and the ``margin`` of each emitted
    token."""
    import jax.numpy as jnp
    from ..reference import deepseek_v32 as ref
    t0 = time.perf_counter()
    p_len, seen = len(prompt), result["probe"]
    toks = onp.concatenate([prompt, result["tokens"][:-1]]).astype("int32")
    L = len(toks)
    mine = selections_of(seen[0], seen[1:], p_len, L)
    want = ref.forward(weights, jnp.asarray(toks), rcfg, selections={
        "positions": mine["positions"], "experts": mine["experts"]})
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
