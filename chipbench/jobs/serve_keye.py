"""Job kind ``serve_keye``: the language model of Keye-VL-2.0-30B-A3B, cut
in depth to one pipeline stage that fits a chip with every expert of its
layers, served as the program serves a model today: ``KeyeVL2LM`` ->
``GenerationEngine`` -> ``ModelServer`` over loopback HTTP, bfloat16
weights and three bfloat16 rings a layer (keys, values, the indexer's
keys).

The load, the clients' stamps and the window's numbers are ``serve_lm``'s
own (its children, ``window_numbers``), the window ``serve_lfm2``'s
(``serve_window``), the probed requests and the limits' comparison
``serve_dsv32``'s (``probed_requests``, ``within``, ``selections_within``).
What differs is the model that is built, what a decode step requires
(``required_keye``), and how ``correct`` is found: at contexts of 6-12 k
and a vocabulary of 151,936 neither an [L, L] array of index scores nor
[L, V] logits fit beside the weights, so the program's selections come as
``top_k`` gave them ([L, K] indices and their scores), the reference judges
them a block of queries at a time, and logits are compared a block of rows
at a time.  Decided from tokens, logits and weights alone, never from a
time.
"""
import functools
import time

import numpy as onp

from .. import common, required_keye
from ..common import say
from .serve_dsv32 import (BYTES, probed_requests, selections_within,
                          within)
from .serve_lfm2 import serve_window
from .serve_lm import (END_TO_END, MODULE_ROLES, start_children,  # noqa: F401
                       stop_children)

# rows of logits a block: [512, 151936] float32 is 311 MB a side
LOGIT_ROWS = 512


def model_config(cfg):
    """The configuration's published keys as the model takes them."""
    from mxnet_tpu.models.keye import KEYE_PUBLISHED
    return {k: cfg[k] for k in KEYE_PUBLISHED}


def shape_of(cfg):
    s = cfg["serving"]
    return dict(model_config(cfg), held=cfg["num_experts"],
                weight_bytes=BYTES[s["weight_dtype"]],
                cache_bytes=BYTES[s["kv_dtype"]])


def build(cfg, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.models import KeyeVL2LM
    s = cfg["serving"]
    mx.random.seed(seed)
    net = KeyeVL2LM(model_config(cfg), dtype=s["weight_dtype"],
                    cache_dtype=s["kv_dtype"])
    net.initialize()
    return net


def run(cell, cfg, traffic, args, devs, tracer):
    # a program without the model fails here, before anything is started
    from mxnet_tpu.models import keye  # noqa: F401
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
    jax.block_until_ready(net.head.data()._data)
    phases["build_s"] = time.perf_counter() - t
    seed = common.fold_seed(args.seed, 2)
    w = serve_window(
        net, cfg["serving"], shape["vocab_size"], traffic, args, devs, tracer,
        children, phases, compiles,
        setup=dict(parameters=sum(int(onp.prod(p.shape))
                                  for p in net._tree_params()),
                   parameters_reckoned=required_keye.weight_params(shape)),
        # the clients have hung up; their streams go on in their slots, and
        # the probed requests take the next ones that come free
        after=lambda engine, timeout_s: probed_requests(engine, cfg, seed,
                                                        timeout_s))
    delta, obs = w["counters"], w["obs"]
    steps = max(1, delta["decode_steps"])
    # kv_context_mean.keye: cached positions a layer's rings hold a step
    delta["attention_layer_steps"] = \
        delta["decode_steps"] * shape["num_hidden_layers"]
    need = {
        "bytes": required_keye.decode_step_bytes(
            shape, delta["experts_touched"] / steps,
            delta["index_valid_positions"] / steps,
            delta["index_selected_positions"] / steps),
        "flops": required_keye.decode_step_flops(
            shape, delta["tokens_generated"] / steps,
            delta["routed_pairs"] / steps,
            delta["index_valid_positions"] / steps,
            delta["index_selected_positions"] / steps)}
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


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------
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
    through_engine = [probed_path(net, weights, rcfg, *one)
                      for one in probed]
    ok = bool(picks) and selections_within(found, limits) \
        and found["margin"] <= limits["margin_tolerance"] \
        and found["served_largest_share"] >= limits["served_largest_share_least"] \
        and all(within(c, dict(limits, logits_tolerance=c["logits_tolerance"]))
                for c in through_engine)
    return ok, {"requests": [[r["session"], r["k"]] for r in picks],
                **found, "probed": through_engine, "limits": limits,
                "agrees": ok}


@functools.lru_cache(maxsize=None)
def judge_selections(topk, per_token):
    """A jitted ``(mine, want, rows) -> {name: scalar}`` over one
    sequence.  ``mine`` holds the program's selections a layer as
    ``keye.trunk`` gives them (``positions`` [L, K] indices or None,
    ``index_scores`` their scores, ``experts`` [L, k], ``router_scores``
    [L, E]), ``want`` what the reference found on them (``index_at``,
    ``index_kth``, ``index_moments``, ``router_scores``), ``rows`` [L]
    the queries that count.

    * ``index_score_error_in_std`` / ``router_score_error``: the largest
      difference of the program's scores from the reference's, the first
      over the positions the program selected and in units of the
      reference's scores' standard deviation.
    * ``position_shortfall_in_std`` / ``expert_shortfall``: how far below
      the reference's ``topk``-th position (``per_token``-th expert) the
      reference scores one the program chose: 0 where every choice was
      the reference's own, small where near-ties flipped.
    * ``selected_count_wrong``: rows whose selection does not hold
      ``min(topk, t + 1)`` positions, and selected positions after their
      query.
    ``group_shortfall`` is 0: the router has one group."""
    import jax
    import jax.numpy as jnp

    def worst(x, where):
        return jnp.maximum(0.0, jnp.where(where, x, -jnp.inf).max())

    def positions(chosen, own, at, kth, moments, rows):
        kept = chosen >= 0
        use = kept & rows[:, None]
        t = jnp.arange(chosen.shape[0])
        need = jnp.minimum(topk, t + 1)
        wrong = ((kept.sum(-1) != need) & rows).sum() \
            + (use & (chosen > t[:, None])).sum()
        n = jnp.maximum(moments[2], 1.0)
        std = jnp.sqrt(moments[1] / n - (moments[0] / n) ** 2)
        # a selected position that its query may not see reads -inf: wrong
        # above, and left out of the differences here
        use = use & jnp.isfinite(at)
        return {"selected_count_wrong": wrong,
                "index_score_error_in_std": worst(jnp.abs(own - at),
                                                  use) / std,
                "position_shortfall_in_std": worst(kth[:, None] - at,
                                                   use) / std}

    def experts(idx, own, scores, rows):
        kth = jnp.sort(scores, -1)[:, -per_token][:, None]
        return {"router_score_error": worst(jnp.abs(own - scores),
                                            rows[:, None]),
                "expert_shortfall": worst(
                    kth - jnp.take_along_axis(scores, idx, axis=-1),
                    rows[:, None])}

    def found(mine, want, rows):
        parts = [positions(c, o, a, k, m, rows) for c, o, a, k, m in zip(
            mine["positions"], mine["index_scores"], want["index_at"],
            want["index_kth"], want["index_moments"]) if c is not None]
        parts += [experts(i, o, s, rows) for i, o, s in zip(
            mine["experts"], mine["router_scores"], want["router_scores"])]
        out = {"selected_count_wrong": sum(
            p.pop("selected_count_wrong", 0) for p in parts),
            "group_shortfall": 0.0,
            "index_score_error_in_std": 0.0,
            "position_shortfall_in_std": 0.0}
        for name in set().union(*parts):
            out[name] = jnp.stack([jnp.asarray(p[name], jnp.float32)
                                   for p in parts if name in p]).max()
        return out
    return jax.jit(found)


@functools.lru_cache(maxsize=None)
def judge_logits():
    """A jitted ``(logits, reference's, served, rows) -> [margin, rows
    whose served token is the reference's largest, largest difference, sum
    of the reference's squares, rows]`` over a block of rows."""
    import jax
    import jax.numpy as jnp

    def worst(x, where):
        return jnp.maximum(0.0, jnp.where(where, x, -jnp.inf).max())

    def found(logits, lr, served, rows):
        at_served = jnp.take_along_axis(lr, served[:, None], axis=-1)[:, 0]
        return jnp.stack([
            worst(lr.max(-1) - at_served, rows),
            ((lr.argmax(-1) == served) & rows).sum().astype(jnp.float32),
            worst(jnp.abs(logits - lr).max(-1), rows),
            jnp.where(rows[:, None], lr ** 2, 0.0).sum(),
            rows.sum().astype(jnp.float32)])
    return jax.jit(found)


@functools.lru_cache(maxsize=None)
def program_head(net):
    """The program's head on rows of its stream, jitted once a model."""
    import jax
    from mxnet_tpu.models import keye
    c = net.config
    return jax.jit(lambda w, x: keye.head(c, w, x))


def logits_judged(net, weights, rcfg, hidden, want_hidden, served, rows,
                  given=None):
    """The program's logits (its head on its own stream ``hidden`` [L, d],
    or ``given`` [L, V]) against the reference's head on the reference's
    stream, a block of rows at a time, over the ``rows`` that count."""
    from ..reference import keye as ref
    head = program_head(net)
    rows_host = onp.asarray(rows)
    margin = diff = 0.0
    largest = squares = n = 0.0
    for lo in range(0, len(rows_host), LOGIT_ROWS):
        hi = lo + LOGIT_ROWS
        if not rows_host[lo:hi].any():
            continue
        mine = given[lo:hi] if given is not None \
            else head(weights, hidden[lo:hi])
        got = onp.asarray(judge_logits()(
            mine, ref.head(weights, want_hidden[lo:hi], rcfg),
            served[lo:hi], rows[lo:hi]))
        margin, diff = max(margin, got[0]), max(diff, got[2])
        largest, squares, n = largest + got[1], squares + got[3], n + got[4]
    return {"margin": float(margin), "served_largest": int(largest),
            "forward_diff": float(diff),
            "logit_std": float((squares / max(n, 1)
                                / rcfg["vocab_size"]) ** 0.5)}


def _judged(rcfg, mine, want, rows):
    out = judge_selections(rcfg["sa_config"]["topk"],
                           rcfg["num_experts_per_tok"])(
        mine, {k: want[k] for k in ("index_at", "index_kth", "index_moments",
                                    "router_scores")}, rows)
    return {k: (int(v) if k == "selected_count_wrong" else float(v))
            for k, v in out.items()}


def served_requests(net, weights, rcfg, cfg, picks):
    """For each picked request, over the positions whose token was
    served: the program's index scores (at the positions it selected) and
    router scores against the reference's, the reference's score of every
    position and expert the program chose against the reference's own
    k-th, and the served token's logit against the largest, in the
    reference run on the program's choices.  The worst of each over the
    requests.  The program here is its full forward: the timed decode
    program returns no selections.  Every request is padded to one length,
    so each program compiles once.  ``full_forward_diff``, that forward's
    logits against the reference's, is a reading with no limit: no ring is
    in it (the probed path's has both readings)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import keye
    from ..generators.sessions import prompt_tokens
    from ..reference import keye as ref
    c = net.config
    pad_to = cfg["check"]["pad_to"]
    seqs = [(prompt_tokens(rcfg["vocab_size"], r["token_seed"],
                           r["prompt_len"]) + r["tokens"][:-1])
            for r in picks]
    L = -(-max(len(s) for s in seqs) // pad_to) * pad_to

    @jax.jit
    def program(w, t):
        x, _caches, sel = keye.trunk(c, w, t[None], want_selections=True)
        return x[0], dict(
            sel, positions=[None if p is None else p[0]
                            for p in sel["positions"]],
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
        toks, rows, served = jnp.asarray(toks), jnp.asarray(rows), \
            jnp.asarray(served)
        hidden, mine = program(weights, toks)
        want = ref.forward(
            weights, toks, rcfg, whole=False, logits=False, selections={
                "positions": mine["positions"], "experts": mine["experts"]})
        one = dict(_judged(rcfg, mine, want, rows),
                   **logits_judged(net, weights, rcfg, hidden,
                                   want["hidden"], served, rows))
        per_request.append(dict(
            one, seconds=time.perf_counter() - t, session=r["session"],
            k=r["k"], prompt_len=r["prompt_len"], length=len(seq)))
        for key in ("margin", "forward_diff", "index_score_error_in_std",
                    "router_score_error", "position_shortfall_in_std",
                    "expert_shortfall", "group_shortfall",
                    "selected_count_wrong"):
            worst[key] = max(worst.get(key, 0), one[key])
        largest += one["served_largest"]
    worst["full_forward_diff"] = worst.pop("forward_diff")
    return dict(worst, padded_length=L, per_request=per_request,
                served_largest_share=largest / sum(
                    len(r["tokens"]) for r in picks))


def selections_of(first, steps, p_len):
    """The prefill program's selections over its bucket (the first
    ``p_len`` rows are the prompt's) and a decode step's row each, as the
    selections of the whole sequence: positions and their scores [L, K],
    experts [L, k] and router scores [L, E], a layer."""
    import jax.numpy as jnp

    def rows_of(name, i):
        head = jnp.asarray(first[name][i])
        head = head[0] if name in ("positions", "index_scores") else head
        return jnp.concatenate(
            [head[:p_len]] + [jnp.asarray(s[name][i])[None] for s in steps])
    return {name: [rows_of(name, i) for i in range(len(first[name]))]
            for name in ("positions", "index_scores", "experts",
                         "router_scores")}


def probed_path(net, weights, rcfg, case, prompt, result):
    """One probed request (``serve_dsv32.probed_requests``): the engine's
    prefill program at a bucket longer than the prompt into a slot of the
    live rings and its decode program over every slot in flight, against
    the reference's full forward over prompt + tokens run on the
    selections those programs made: ``forward_diff`` over the emitted
    positions beside the case's ``logits_tolerance``, the scores and
    selections of every position, and the ``margin`` of each emitted
    token."""
    import jax.numpy as jnp
    from ..reference import keye as ref
    t0 = time.perf_counter()
    p_len, seen = len(prompt), result["probe"]
    toks = onp.concatenate([prompt, result["tokens"][:-1]]).astype("int32")
    L = len(toks)
    mine = selections_of(seen[0], seen[1:], p_len)
    want = ref.forward(
        weights, jnp.asarray(toks), rcfg, whole=False, logits=False,
        selections={"positions": mine["positions"],
                    "experts": mine["experts"]})
    got = jnp.stack([jnp.asarray(s["logits"]) for s in seen])
    found = dict(
        _judged(rcfg, mine, want, jnp.ones((L,), bool)),
        **logits_judged(net, weights, rcfg, None, want["hidden"][p_len - 1:],
                        jnp.asarray(result["tokens"], jnp.int32),
                        jnp.ones((len(seen),), bool), given=got))
    if not bool(jnp.isfinite(got).all()):
        found["forward_diff"] = float("inf")
    return dict(case, **found, seconds=time.perf_counter() - t0)
