"""Solar Open 2 at tiny sizes on the CPU, float32, seeded weights: the whole
model against the plain reference (``chipbench/reference/solar_open2.py``),
the gated delta rule's chunked scan against its recurrence, the three kinds
of cache a slot holds (the convolution's rows and a float32 delta-rule
state with no position axis, key and value rings) through the generation
engine, the share of the experts a chip holds, and the benchmark's
check."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import parts, solar, tiny_solar
from mxnet_tpu.parallel import moe
from mxnet_tpu.serving.generate import GenerationEngine

COUNTERS = [name for name, _help in solar.STEP_COUNTERS]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from chipbench.reference import solar_open2 as ref  # noqa: E402

# float32 throughout on the CPU: the chunked scan and the recurrence sum in
# other orders, so logits of standard deviation ~0.6 differ by ~1e-5
TOL = 5e-5


def _net(seed=3, **kw):
    mx.random.seed(seed)
    net = tiny_solar(**kw)
    net.initialize()
    return net


def _ref_cfg(net):
    c = net.config
    return dict({k: getattr(c, k) for k in solar.SOLAR_OPEN2_PUBLISHED},
                gqa_layers=list(c.gqa_layers), held=c.held)


def _tokens(n, seed=0, batch=1, vocab=96):
    return onp.random.RandomState(seed).randint(
        0, vocab, (batch, n)).astype("int32")


def _reference(net, seq, **kw):
    return ref.forward(net.raw_weights(), jnp.asarray(seq, jnp.int32),
                       _ref_cfg(net), **kw)


def _caches(kvs, M, P):
    """What ``prefill`` returned as the caches ``decode_step`` takes:
    states as they are, rows at the front of a ring of ``M`` positions."""
    caches = []
    for layer, (kind, *_rest) in zip(kvs, kinds_of(kvs)):
        if kind == "kda":
            caches.append(layer)
            continue
        rings = []
        for rows in layer:
            ring = onp.zeros((rows.shape[0], M, rows.shape[-1]), "float32")
            ring[:, :P] = rows.asnumpy()
            rings.append(nd.array(ring))
        caches.append(tuple(rings))
    return caches


def kinds_of(kvs):
    """A KDA layer's state has no position axis: [B, H, K, V]."""
    return [("kda",) if layer[1].ndim == 4 else ("gqa",) for layer in kvs]


# -- the model ----------------------------------------------------------------
def test_full_forward_is_the_reference():
    net = _net()
    toks = _tokens(45)
    got, sel = net.forward(nd.array(toks), want_selections=True)
    want = _reference(net, toks[0])
    assert onp.abs(got.asnumpy()[0] - onp.asarray(want["logits"])).max() < TOL
    assert len(sel["experts"]) == 4                 # every layer routes
    assert len(sel["delta_states"]) == 3            # layers 1..3 are KDA
    for idx, scores, r_idx, r_scores in zip(
            sel["experts"], sel["router_scores"], want["experts"],
            want["router_scores"]):
        assert onp.abs(onp.asarray(scores) - onp.asarray(r_scores)).max() < 1e-5
        assert (onp.sort(onp.asarray(idx), -1)
                == onp.sort(onp.asarray(r_idx), -1)).all()
    for mine, theirs in zip(sel["delta_states"], want["delta_states"]):
        assert onp.abs(onp.asarray(mine)[0] - onp.asarray(theirs)).max() \
            < 1e-5 * onp.abs(onp.asarray(theirs)).max()
    # and on imposed experts the reference gives the same logits
    again = _reference(net, toks[0], selections={"experts": sel["experts"]})
    assert onp.abs(onp.asarray(again["logits"])
                   - onp.asarray(want["logits"])).max() < 1e-6


def test_cache_spec_names_float32_states_beside_rings():
    net = _net(cache_dtype="bfloat16")
    spec = net.cache_spec(40)
    assert [[kind for kind, _s, _d in layer] for layer in spec] == [
        ["k", "v"], ["conv", "delta"], ["conv", "delta"], ["conv", "delta"]]
    assert spec[0][0][1] == (40, 2 * 8)                 # no head axis
    assert spec[1][0][1] == (3 * 3 * 4 * 8,)            # three rows of qkv
    assert spec[1][1][1] == (4, 8, 8)                   # a head's S
    # the rings and rows take the cache's type, never the state
    assert [onp.dtype(d).name for layer in spec for _k, _s, d in layer] == \
        ["bfloat16"] * 3 + ["float32", "bfloat16", "float32",
                            "bfloat16", "float32"]
    assert onp.dtype(_net(state_dtype="bfloat16").cache_spec(8)[1][1][2]) \
        == jnp.bfloat16
    with pytest.raises(ValueError, match="NoPE"):
        tiny_solar(config={"use_rope": True})


def test_beta_above_one_is_drawn_and_decays_stay_below_one():
    """``kda_allow_neg_eigval``: beta = 2 sigmoid, and the seeded weights
    put it on both sides of 1, so that the transition's eigenvalue along
    k, 1 - beta, is negative at some positions; every decay is in (0, 1]."""
    net = _net()
    c, w = net.config, net.raw_weights()
    lw = parts.sub_weights(w, "layers.1.")
    x = parts.rms_norm(w["embed"][jnp.asarray(_tokens(64)[0])],
                       lw["op_norm"], c.rms_norm_eps)
    z, _state = parts.short_conv(parts.matmul(x, lw["wqkv"])[None],
                                 lw["conv_w"], jnp.asarray([64]))
    _q, _k, _v, g, beta = solar._kda_inputs(c, lw, x, z[0])
    beta = onp.asarray(beta)
    assert beta.max() > 1.2 and beta.min() < 0.8 and 0 < beta.min()
    assert (onp.asarray(g) <= 0).all() and onp.asarray(g).min() < -0.5


def _recurrence(q, k, v, g, beta, n):
    """The state after ``n`` positions and every output, batch by batch,
    by the reference's recurrence."""
    outs, states = [], []
    for b in range(q.shape[0]):
        o, S = ref.recurrence(q[b], k[b], v[b], g[b], beta[b], int(n[b]))
        outs.append(o)
        states.append(S)
    return jnp.stack(outs), jnp.stack(states)


@pytest.mark.parametrize("L,valid", [(37, (37, 29)), (70, (70, 45)),
                                     (13, (5, 13))])
def test_the_chunked_scan_is_the_recurrence_with_padded_tails(L, valid):
    """Lengths no chunk divides, padded to whole chunks as the model pads
    them, and tails past the valid length where beta = 0 and g = 0: the
    outputs at valid positions and the state as of the valid length are the
    recurrence's, whatever the chunk; steep decays (log decay down to -30 a
    position) do not overflow."""
    rng = onp.random.RandomState(L)
    B, H, K, C = 2, 3, 8, 16
    q = parts.l2_norm(jnp.asarray(rng.randn(B, L, H, K), jnp.float32), 1e-6)
    k = parts.l2_norm(jnp.asarray(rng.randn(B, L, H, K), jnp.float32), 1e-6)
    v = jnp.asarray(rng.randn(B, L, H, K), jnp.float32)
    g = -jnp.asarray(rng.exponential(1.0, (B, L, H, K)) ** 3, jnp.float32)
    g = jnp.maximum(g, -30.0)
    beta = jnp.asarray(rng.uniform(0, 2, (B, L, H)), jnp.float32)
    n = onp.asarray(valid)
    live = jnp.arange(L)[None, :] < jnp.asarray(n)[:, None]
    beta = jnp.where(live[..., None], beta, 0.0)
    g = jnp.where(live[..., None, None], g, 0.0)
    want_o, want_S = _recurrence(q, k, v, g, beta, n)
    pad = -L % C

    def padded(a):
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
    o, S = parts.delta_rule_chunked(
        *(padded(a) for a in (q, k, v, g, beta)),
        jnp.zeros((B, H, K, K), jnp.float32), C)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    assert onp.abs(onp.asarray(S - want_S)).max() < 2e-5
    for b in range(B):
        assert onp.abs(onp.asarray(o[b, :n[b]] - want_o[b, :n[b]])).max() \
            < 2e-5
    # the one-step form, position by position, from the chunked state
    S1 = S
    for t in range(3):
        o1, S1, _passes = parts.delta_rule_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S1,
            jnp.ones((B,), jnp.int32))
        want = (S1 * q[:, t, :, :, None]).sum(-2)
        assert onp.abs(onp.asarray(o1 - want)).max() < 1e-5


@pytest.mark.parametrize("valid", [(21, 21), (19, 12)])
def test_prefill_hands_both_states_over_at_the_valid_length(valid):
    """Prompts padded to one bucket: the convolution's rows and the delta
    state are those of each prompt's own end, and six decode steps on from
    there are the reference's full forward."""
    net = _net()
    P, N, M = 21, 6, 32
    toks = _tokens(P + N, batch=2)
    vl = onp.asarray(valid, "int32")
    padded = toks[:, :P].copy()
    for b in range(2):
        padded[b, vl[b]:] = 0                       # what a bucket pads with
    logits, kvs, sel = net.prefill(nd.array(padded), nd.array(vl),
                                   probe=True)
    wants = [_reference(net, toks[b, :vl[b] + N], state_at=int(vl[b]))
             for b in range(2)]
    for b, want in enumerate(wants):
        assert onp.abs(logits.asnumpy()[b, 0]
                       - onp.asarray(want["logits"])[vl[b] - 1]).max() < TOL
        for mine, theirs in zip(sel["delta_states"], want["delta_states"]):
            assert onp.abs(onp.asarray(mine)[b] - onp.asarray(theirs)).max() \
                < 1e-5 * onp.abs(onp.asarray(theirs)).max()
    caches, pos = _caches(kvs, M, P), vl.copy()
    for j in range(N):
        tok = onp.asarray([toks[b, pos[b]] for b in range(2)], "int32")
        lg, caches, counts = net.decode_step(nd.array(tok), caches,
                                             nd.array(pos))
        for b, want in enumerate(wants):
            assert onp.abs(lg.asnumpy()[b] - onp.asarray(
                want["logits"])[pos[b]]).max() < TOL
        pos += 1
        counts = dict(zip(COUNTERS, counts.asnumpy()))
        assert counts["routed_pairs"] == 2 * 4 * 2
        assert counts["routed_pairs_held"] <= counts["routed_pairs"]
        assert counts["attn_valid_positions"] == int(pos.sum())
        # on a CPU the attention is einsums over both whole rings
        assert counts["kv_rows_read"] == 2 * M
        # 2 slots x 3 layers x 4 heads x 8 x 8 float32 read and written
        assert counts["delta_state_kib"] * 1024 == 2 * 3 * 4 * 8 * 8 * 4 * 2
        assert len(counts) == len(solar.STEP_COUNTERS)


def test_a_slot_that_sits_out_a_step_keeps_its_states_and_rings():
    net = _net()
    P, M = 12, 24
    toks = _tokens(P + 3, batch=2, seed=4)
    full = net.forward(nd.array(toks)).asnumpy()
    _lg, kvs = net.prefill(nd.array(toks[:, :P]))
    caches = _caches(kvs, M, P)
    before = [[r.asnumpy().copy() for r in layer] for layer in caches]
    # slot 0 sits out, slot 1 rides
    lg, caches, counts = net.decode_step(
        nd.array(toks[:, P]), caches, nd.array(onp.full(2, P, "int32")),
        active=nd.array(onp.asarray([0.0, 1.0], "float32")))
    for layer, was in zip(caches, before):
        for ring, old in zip(layer, was):
            assert (ring.asnumpy()[0] == old[0]).all()
            assert not (ring.asnumpy()[1] == old[1]).all()
    assert onp.abs(lg.asnumpy()[1] - full[1, P]).max() < TOL
    counts = dict(zip(COUNTERS, counts.asnumpy()))
    assert counts["routed_pairs"] == 4 * 2          # one rider
    assert counts["attn_valid_positions"] == P + 1
    assert counts["delta_state_kib"] * 1024 == 3 * 4 * 8 * 8 * 4 * 2
    # and rides the next one as if no step had passed
    lg, caches, _counts = net.decode_step(
        nd.array(onp.asarray([toks[0, P], toks[1, P + 1]], "int32")), caches,
        nd.array(onp.asarray([P, P + 1], "int32")))
    assert onp.abs(lg.asnumpy()[0] - full[0, P]).max() < TOL
    assert onp.abs(lg.asnumpy()[1] - full[1, P + 1]).max() < TOL


def test_decode_with_the_kernel_is_decode_in_the_xla_form(monkeypatch):
    """Three decode steps of three slots, a different one sitting out each
    step, with the delta rule's kernel (interpreted, two blocks of heads)
    against the XLA form: the same logits and states to float32 rounding,
    a sitting slot's delta states bit for bit, and the step counter of the
    state moved reading 1.0 times ``delta_state_kib`` with the kernel
    (read once, written once) and 1.5 times with XLA's two fusions."""
    from mxnet_tpu.ops import delta_rule_step as drs
    net = _net()
    P, M, B = 12, 24, 3
    toks = _tokens(P + 4, batch=B, seed=6)
    _lg, kvs = net.prefill(nd.array(toks[:, :P]))

    def run():
        caches = _caches(kvs, M, P)
        pos = onp.full(B, P, "int32")
        steps = []
        for t in range(3):
            act = onp.ones(B, "float32")
            act[t] = 0.0
            before = [layer[1].asnumpy() for layer, kind in
                      zip(caches, kinds_of(caches)) if kind == ("kda",)]
            lg, caches, counts = net.decode_step(
                nd.array(toks[onp.arange(B), pos]), caches, nd.array(pos),
                active=nd.array(act))
            after = [layer[1].asnumpy() for layer, kind in
                     zip(caches, kinds_of(caches)) if kind == ("kda",)]
            for was, now in zip(before, after):
                assert (now[t] == was[t]).all()
            steps.append((lg.asnumpy(), after,
                          dict(zip(COUNTERS, counts.asnumpy()))))
            pos = pos + (act > 0)
        return steps

    xla = run()
    with monkeypatch.context() as patch:
        patch.setattr(drs, "kernel_heads", lambda *a: 2)
        patch.setattr(drs, "delta_rule_step", functools.partial(
            drs.delta_rule_step, interpret=True))
        kernel = run()
    for (lg_k, st_k, n_k), (lg_x, st_x, n_x) in zip(kernel, xla):
        assert onp.abs(lg_k - lg_x).max() < 1e-5
        for a, b in zip(st_k, st_x):
            assert onp.abs(a - b).max() < 1e-5
        # two riders x 3 KDA layers x 4 heads x 8 x 8 float32, twice
        assert n_k["delta_state_kib"] * 1024 == 2 * 3 * 4 * 8 * 8 * 4 * 2
        assert n_k["delta_state_kib"] == n_x["delta_state_kib"]
        assert n_k["delta_state_kib_moved"] == n_k["delta_state_kib"]
        assert 2 * n_x["delta_state_kib_moved"] == 3 * n_x["delta_state_kib"]
        assert {k: v for k, v in n_k.items() if "delta" not in k} \
            == {k: v for k, v in n_x.items() if "delta" not in k}


def test_no_position_signal_a_shifted_ring_gives_the_same_logits():
    """NoPE: the attention layer sees no position, so a decode step whose
    rows lie rotated in a full ring, at positions shifted by ``r``, gives
    the logits of the unshifted step."""
    net = _net()
    P = 15
    M = P + 1
    toks = _tokens(P + 1, seed=9)
    _lg, kvs = net.prefill(nd.array(toks[:, :P]))
    tok = nd.array(toks[:, P])
    base, _c, _n = net.decode_step(tok, _caches(kvs, M, P),
                                   nd.array(onp.asarray([P], "int32")))
    for r in (1, 6):
        caches = []
        for layer, (kind,) in zip(kvs, kinds_of(kvs)):
            if kind == "kda":
                caches.append(layer)
                continue
            rings = []
            for rows in layer:
                ring = onp.zeros((1, M, rows.shape[-1]), "float32")
                ring[0, (onp.arange(P) + r) % M] = rows.asnumpy()[0]
                rings.append(nd.array(ring))
            caches.append(tuple(rings))
        got, _c, counts = net.decode_step(
            tok, caches, nd.array(onp.asarray([P + r], "int32")))
        assert dict(zip(COUNTERS, counts.asnumpy()))[
            "attn_valid_positions"] == M
        assert onp.abs(got.asnumpy() - base.asnumpy()).max() < 1e-5


def test_the_shares_of_four_chips_add_up_to_the_whole_layer():
    """Four disjoint shares of the eight experts, the shared expert
    counted once, add up to the expert layer that holds all of them."""
    net = _net(held=(0, 8))
    w = parts.sub_weights(net.raw_weights(), "layers.2.ffn.")
    x = jnp.asarray(onp.random.RandomState(2).randn(10, 32), jnp.float32)
    whole, idx, _g, _s = moe.dropless_moe(x, w, k=2, first=0)
    total = 0.0
    for share in range(4):
        lo = 2 * share
        part = dict(w, **{f"held_w{j}": w[f"held_w{j}"][lo:lo + 2]
                          for j in (1, 2, 3)})
        y, idx_s, _g, _s = moe.dropless_moe(x, part, k=2, first=lo,
                                            with_shared=share == 0)
        assert (onp.asarray(idx_s) == onp.asarray(idx)).all()
        total = total + y
    assert onp.abs(onp.asarray(total - whole)).max() < 1e-5


# -- the engine ---------------------------------------------------------------
@pytest.mark.parametrize("prompt_len", [5, 11])
def test_through_the_engine_a_padded_prompt_is_the_references_forward(
        prompt_len):
    """Prompts shorter than their bucket (8 or 16): every emitted
    position's logits, from the prefill program and the decode program,
    against the reference's full forward over prompt + tokens, and the
    probed prefill's float32 states against the recurrence's at the
    prompt's end."""
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=2, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert prompt_len not in eng.prefill_buckets
        assert [onp.dtype(d).name for _k, _s, d in eng._ring_specs][:4] == \
            ["float32"] * 4
        prompt = _tokens(prompt_len, seed=prompt_len)[0].tolist()
        got = eng.submit(prompt, max_new_tokens=7, probe=True).result(300)
    finally:
        eng.stop()
    want = _reference(net, prompt + got["tokens"][:-1], state_at=prompt_len)
    assert len(got["probe"]) == 7
    for j, seen in enumerate(got["probe"]):
        assert onp.abs(seen["logits"] - onp.asarray(want["logits"])[
            prompt_len - 1 + j]).max() < TOL
        assert int(seen["logits"].argmax()) == got["tokens"][j]
    for mine, theirs in zip(got["probe"][0]["delta_states"],
                            want["delta_states"]):
        assert onp.abs(mine[0] - onp.asarray(theirs)).max() \
            < 1e-5 * onp.abs(onp.asarray(theirs)).max()
    # a decode step's probe is its slot's row: no state comes back
    assert set(got["probe"][1]) == {"logits", "experts", "router_scores"}


def test_engine_churns_more_requests_than_slots_through_three_kinds():
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=3, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert [k for k, _s, _d in eng._ring_specs] == \
            ["k", "v"] + ["conv", "delta"] * 3
        rng = onp.random.RandomState(5)
        prompts = [rng.randint(0, 96, n).tolist()
                   for n in (5, 12, 3, 9, 14, 7, 4)]
        news = [9, 4, 13, 6, 3, 11, 8]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, news)]
        c, w = net.config, net.raw_weights()
        full = jax.jit(lambda w, t: solar.run_full(c, w, t[None])[0][0])
        for p, n, s in zip(prompts, news, streams):
            toks = list(p)
            for _ in range(n):
                padded = onp.zeros(48, "int32")
                padded[:len(toks)] = toks
                toks.append(int(full(w, jnp.asarray(padded))[
                    len(toks) - 1].argmax()))
            assert s.result(300)["tokens"] == toks[len(p):]
        st = eng.metrics.stats()
        c, g = st["counters"], st["gauges"]
        assert c["slot_allocs"] == 7 > eng.slots
        assert c["kv_ring_rebuilds"] == 0
        assert g["kv_cache_bytes_delta"] == 3 * 3 * 4 * 8 * 8 * 4
        assert g["kv_cache_bytes_conv"] == 3 * 3 * 3 * 96 * 4
        assert c["delta_state_kib"] * 1024 == \
            c["tokens_generated"] * 3 * 4 * 8 * 8 * 4 * 2
        assert c["routed_pairs"] == 4 * 2 * c["tokens_generated"]
    finally:
        eng.stop()


# -- the benchmark's check ----------------------------------------------------
@pytest.mark.parametrize("kv_dtype,state_dtype,agrees", [
    ("float32", "float32", True), ("float32", "bfloat16", False),
    ("float8_e4m3fn", "float32", False)])
def test_the_benchmarks_probed_check_holds_the_state_to_its_type(
        kv_dtype, state_dtype, agrees):
    """``chipbench/jobs/serve_solar.py``'s probed request, at the
    rehearsal's sizes: through the engine's programs it agrees with the
    reference as stated, and a delta state kept in bfloat16, or rings and
    rows in FP8, come out wrong by ``within``, the function that decides
    ``correct``; FP8 rings never cast the state."""
    from chipbench import common
    from chipbench.jobs import serve_solar as job
    from chipbench.run import merge
    cfg = common.load("configs", "solar_open2_250b_ep8_serve")
    cfg = merge(cfg, cfg["rehearse"])
    cfg["serving"]["kv_dtype"] = kv_dtype
    cfg["serving"]["state_dtype"] = state_dtype
    net, s = job.build(cfg, 5), cfg["serving"]
    eng = GenerationEngine(net, slots=s["slots"], max_len=s["max_len"],
                           prefill_buckets=tuple(s["prefill_buckets"]),
                           cache=None)
    try:
        assert {onp.dtype(d).name for k, _s, d in eng._ring_specs
                if k == "delta"} == {state_dtype}
        assert {onp.dtype(d).name for k, _s, d in eng._ring_specs
                if k != "delta"} == {kv_dtype}
        rider = eng.submit([1, 2, 3], max_new_tokens=30)
        probed = job.probed_requests(eng, cfg, 7, 300)
        assert len(rider.result(300)["tokens"]) == 30
    finally:
        eng.abort()
    limits = cfg["check"]["limits"]
    found = [job.probed_path(net, net.raw_weights(), job.reference_config(cfg),
                             *one) for one in probed]
    assert [job.within(f, dict(limits, logits_tolerance=f["logits_tolerance"]))
            for f in found] == [agrees] * len(cfg["check"]["probed"])


def test_required_solar_reckons_the_configurations_bytes():
    """The yardstick's shapes against the configuration's arithmetic, and
    a step's bytes against hand arithmetic: the state read once and written
    once."""
    from chipbench import common, required_solar
    from chipbench.jobs import serve_solar as job
    shape = job.shape_of(common.load("configs", "solar_open2_250b_ep8_serve"))
    assert required_solar.expert_params(shape) == 3 * 4096 * 1280
    assert round(required_solar.kda_params(shape) / 1e6, 1) == 137.7
    assert round(required_solar.gqa_params(shape) / 1e6, 1) == 109.1
    assert round(required_solar.weight_params(shape) / 1e9, 3) == 3.308
    outside = required_solar.outside_experts_params(shape)
    state = 3 * 64 * 128 * 128 * 4
    got = required_solar.decode_step_bytes(shape, 128, 154, 1000,
                                           128 * state * 2 // 1024)
    conv = 3 * 3 * 8192                 # three rows of q, k and v a layer
    assert got == 2 * (outside + 154 * 3 * 4096 * 1280) \
        + 2 * (1000 * 2 * 1024 + 128 * 3 * 2 * conv) + 128 * state * 2
    flops = required_solar.decode_step_flops(shape, 128, 1024, 1000)
    assert flops == 2 * (128 * outside + 1024 * 3 * 4096 * 1280
                         + 1000 * 2 * 64 * 128) \
        + 128 * 3 * 64 * 128 * 128 * 7
