"""Job kind ``serve_solar``: Solar Open 2, cut to one chip's share of an
expert-parallel deployment crossed with a pipeline, served as the program
serves a model today: ``SolarOpen2LM`` -> ``GenerationEngine`` ->
``ModelServer`` over loopback HTTP, bfloat16 weights; a KDA layer keeps a
slot's convolution rows (bfloat16) and its delta-rule state (float32), an
attention layer two bfloat16 rings.

The load, the clients' stamps and the window's numbers are ``serve_lm``'s
own (its children, ``window_numbers``), the window ``serve_lfm2``'s
(``serve_window``), the probed requests ``serve_dsv32``'s.  What differs is
the model that is built, what a decode step requires (``required_solar``),
and ``correct``: the window's served tokens against the float32 reference
run on the program's expert selections, a block of logit rows at a time;
and a prompt shorter than its bucket through the engine's own programs,
probed, in a slot of the caches the window left, beside the streams still
in flight: logits, router scores, every expert the program chose against
the reference's scores, and the float32 delta-rule state the prefill left
in the slot against the recurrence's.  Decided from tokens, states and
weights alone, never from a time.
"""
import functools
import time

import numpy as onp

from .. import common, required_solar
from ..common import say
from .serve_dsv32 import BYTES, probed_requests
from .serve_keye import LOGIT_ROWS, judge_logits
from .serve_lfm2 import selections_of, selections_within, serve_window
from .serve_lm import (END_TO_END, MODULE_ROLES, start_children,  # noqa: F401
                       stop_children)


def model_config(cfg):
    """The configuration's published keys as the model takes them: its
    ``n_routed_experts`` counts the experts held here, the router keeps
    the deployment's width.  Returns ``(keys, held)``."""
    from mxnet_tpu.models.solar import SOLAR_OPEN2_PUBLISHED
    dep = cfg["deployment"]
    model = {k: cfg[k] for k in SOLAR_OPEN2_PUBLISHED}
    held = (dep["rank"] * cfg["n_routed_experts"], cfg["n_routed_experts"])
    model["n_routed_experts"] = dep["router_width"]
    return model, held


def reference_config(cfg):
    model, held = model_config(cfg)
    return dict(model, held=held)


def shape_of(cfg):
    model, held = model_config(cfg)
    s = cfg["serving"]
    return dict(model, router_width=model["n_routed_experts"], held=held[1],
                weight_bytes=BYTES[s["weight_dtype"]],
                cache_bytes=BYTES[s["kv_dtype"]])


def build(cfg, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.models import SolarOpen2LM
    from mxnet_tpu.models.solar import DELTA_CHUNK
    model, held = model_config(cfg)
    s = cfg["serving"]
    if s["delta_chunk"] != DELTA_CHUNK:
        common.fail(f"serving.delta_chunk {s['delta_chunk']} is not the "
                    f"program's DELTA_CHUNK {DELTA_CHUNK}")
    mx.random.seed(seed)
    net = SolarOpen2LM(model, held=held, dtype=s["weight_dtype"],
                       cache_dtype=s["kv_dtype"],
                       state_dtype=s["state_dtype"])
    net.initialize()
    return net


def run(cell, cfg, traffic, args, devs, tracer):
    # a program without the model fails here, before anything is started
    from mxnet_tpu.models import solar  # noqa: F401
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
                   parameters_reckoned=required_solar.weight_params(shape)),
        # the clients have hung up; their streams go on in their slots, and
        # the probed requests take the next ones that come free
        after=lambda engine, timeout_s: probed_requests(engine, cfg, seed,
                                                        timeout_s))
    delta, obs = w["counters"], w["obs"]
    steps = max(1, delta["decode_steps"])
    need = {
        "bytes": required_solar.decode_step_bytes(
            shape, delta["tokens_generated"] / steps,
            delta["experts_touched"] / steps,
            delta["attn_valid_positions"] / steps,
            delta["delta_state_kib"] / steps),
        "flops": required_solar.decode_step_flops(
            shape, delta["tokens_generated"] / steps,
            delta["routed_pairs_held"] / steps,
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


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------
def within(found, limits):
    """Whether what :func:`probed_path` found of a probed request lies
    within the limits."""
    return bool(
        found["forward_diff"] <= limits["logits_tolerance"]
        and found["scan_state_error_in_rms"] <= limits["state_tolerance_in_rms"]
        and selections_within(found, limits))


def check_outputs(cfg, net, completed, probed, seed):
    """Served requests and the probed ones against the plain reference.
    Returns (agrees, what was found beside each limit)."""
    chk = cfg["check"]
    limits = chk["limits"]
    weights = net.raw_weights()
    rcfg = reference_config(cfg)
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
def judge_router(per_token):
    """A jitted ``(experts, own scores, reference's scores, biases, rows)
    -> {name: scalar}`` over one sequence, a list an expert layer:
    ``router_score_error``, the largest difference of the program's router
    scores from the reference's; ``expert_shortfall``, how far below the
    reference's ``per_token``-th largest biased score the reference puts an
    expert the program chose (0 where every choice was the reference's
    own, small where near-ties flipped)."""
    import jax
    import jax.numpy as jnp

    def worst(x, where):
        return jnp.maximum(0.0, jnp.where(where, x, -jnp.inf).max())

    def found(experts, own, want, biases, rows):
        errors, shortfalls = [], []
        for idx, mine, scores, bias in zip(experts, own, want, biases):
            biased = scores + bias
            kth = jnp.sort(biased, -1)[:, -per_token][:, None]
            errors.append(worst(jnp.abs(mine - scores), rows[:, None]))
            shortfalls.append(worst(
                kth - jnp.take_along_axis(biased, idx, axis=-1),
                rows[:, None]))
        return {"router_score_error": jnp.stack(errors).max(),
                "expert_shortfall": jnp.stack(shortfalls).max()}
    return jax.jit(found)


def router_judged(rcfg, weights, mine, want, rows):
    import jax.numpy as jnp
    biases = [weights[f"layers.{i}.ffn.select_bias"].astype(jnp.float32)
              for i in range(rcfg["num_hidden_layers"])]
    out = judge_router(rcfg["num_experts_per_tok"])(
        mine["experts"], mine["router_scores"], want["router_scores"],
        biases, rows)
    return {k: float(v) for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def program_head(net):
    """The program's head on rows of its stream, jitted once a model."""
    import jax
    from mxnet_tpu.models import solar
    c = net.config
    return jax.jit(lambda w, x: solar.head(c, w, x))


def logits_judged(net, weights, rcfg, hidden, want_hidden, served, rows,
                  given=None):
    """The program's logits (its head on its own stream ``hidden`` [L, d],
    or ``given`` [L, V]) against the reference's head on the reference's
    stream, a block of rows at a time, over the ``rows`` that count."""
    from ..reference import solar_open2 as ref
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


def served_requests(net, weights, rcfg, cfg, picks):
    """For each picked request, over the positions whose token was
    served: the program's router scores against the reference's, the
    reference's score of every expert the program chose against the
    reference's own k-th, and the served token's logit against the
    largest, in the reference run on the program's choices.  The worst of
    each over the requests.  The program here is its full forward: the
    timed decode program returns no selections.  Every request is padded
    to one length, so each program compiles once.
    ``full_forward_diff``, that forward's logits against the reference's,
    is a reading with no limit: no cache is in it (the probed path's has
    both readings)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import solar
    from ..generators.sessions import prompt_tokens
    from ..reference import solar_open2 as ref
    c = net.config
    pad_to = cfg["check"]["pad_to"]
    seqs = [(prompt_tokens(rcfg["vocab_size"], r["token_seed"],
                           r["prompt_len"]) + r["tokens"][:-1])
            for r in picks]

    @jax.jit
    def program(w, t):
        x, _caches, sel = solar.trunk(c, w, t[None], want_selections=True)
        return x[0], {k: sel[k] for k in ("experts", "router_scores")}
    L = -(-max(len(s) for s in seqs) // pad_to) * pad_to
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
        want = ref.forward(weights, toks, rcfg, logits=False,
                           selections={"experts": mine["experts"]})
        one = dict(router_judged(rcfg, weights, mine, want, rows),
                   **logits_judged(net, weights, rcfg, hidden,
                                   want["hidden"], served, rows))
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


def state_error_in_rms(mine, want):
    """The largest difference of the program's delta-rule states from
    another's, each layer's in units of that layer's root mean square,
    over the KDA layers."""
    worst = 0.0
    for a, b in zip(mine, want):
        a = onp.asarray(a, onp.float32).reshape(onp.shape(b))
        b = onp.asarray(b, onp.float32)
        worst = max(worst, float(onp.abs(a - b).max()
                                 / onp.sqrt((b ** 2).mean())))
    return worst


def scan_states(inputs, p_len):
    """The recurrence's states after the prompt's ``p_len`` positions, run
    on the inputs the program's prefill gave its scan (batch row 0)."""
    from ..reference import solar_open2 as ref
    return [ref.state_after(*(a[0, :p_len] for a in layer))
            for layer in inputs]


def probed_path(net, weights, rcfg, case, prompt, result):
    """One probed request (``serve_dsv32.probed_requests``): the engine's
    prefill program at a bucket longer than the prompt into a slot of the
    live caches and its decode program over every slot in flight, against
    the reference's full forward over prompt + tokens run on the experts
    those programs chose: ``forward_diff`` over the emitted positions
    beside the case's ``logits_tolerance``, the router scores and choices
    of every position, the ``margin`` of each emitted token, and the
    delta-rule states the prefill handed the slot: against the recurrence
    run on the q, k, v, g and beta the prefill gave its scan
    (``scan_state_error_in_rms``: the scan and the state's type alone,
    with a limit), and against the reference's own forward
    (``full_state_diff_in_rms``: a reading with no limit, because the
    bfloat16 activations before the rule move it more than any type of
    the state could)."""
    import jax.numpy as jnp
    from ..reference import solar_open2 as ref
    t0 = time.perf_counter()
    p_len, seen = len(prompt), result["probe"]
    toks = onp.concatenate([prompt, result["tokens"][:-1]]).astype("int32")
    L = len(toks)
    mine = selections_of(seen[0], seen[1:], p_len)
    want = ref.forward(weights, jnp.asarray(toks), rcfg, logits=False,
                       state_at=p_len,
                       selections={"experts": mine["experts"]})
    got = jnp.stack([jnp.asarray(s["logits"]) for s in seen])
    found = dict(
        router_judged(rcfg, weights, mine, want, jnp.ones((L,), bool)),
        **logits_judged(net, weights, rcfg, None, want["hidden"][p_len - 1:],
                        jnp.asarray(result["tokens"], jnp.int32),
                        jnp.ones((len(seen),), bool), given=got),
        scan_state_error_in_rms=state_error_in_rms(
            seen[0]["delta_states"],
            scan_states(seen[0]["delta_inputs"], p_len)),
        full_state_diff_in_rms=state_error_in_rms(seen[0]["delta_states"],
                                                  want["delta_states"]))
    if not bool(jnp.isfinite(got).all()):
        found["forward_diff"] = float("inf")
    return dict(case, **found, seconds=time.perf_counter() - t0)
