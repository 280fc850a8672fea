"""LFM2-MoE at tiny sizes on the CPU, float32, seeded weights: the whole
model against the plain reference (``chipbench/reference/lfm2.py``), the
two kinds of cache a slot holds (a conv state with no position axis beside
key and value rings) through the generation engine, and the benchmark's
check."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import lfm2, tiny_lfm2
from mxnet_tpu.serving.generate import GenerationEngine

COUNTERS = [name for name, _help in lfm2.STEP_COUNTERS]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from chipbench.reference import lfm2 as ref  # noqa: E402


def _net(seed=3, **kw):
    mx.random.seed(seed)
    net = tiny_lfm2(**kw)
    net.initialize()
    return net


def _ref_cfg(net):
    c = net.config
    return dict({k: getattr(c, k) for k in lfm2.LFM2_PUBLISHED},
                layer_types=list(c.layer_types))


def _tokens(n, seed=0, batch=1, vocab=96):
    return onp.random.RandomState(seed).randint(
        0, vocab, (batch, n)).astype("int32")


def _reference(net, seq, **kw):
    return ref.forward(net.raw_weights(), jnp.asarray(seq, jnp.int32),
                       _ref_cfg(net), **kw)


def _rings(net, kvs, M, P):
    """What ``prefill`` returned as the caches ``decode_step`` takes: a
    state as it is, rows at the front of a ring of ``M`` positions."""
    caches = []
    for layer in kvs:
        if len(layer) == 1:
            caches.append(layer)
            continue
        rings = []
        for rows in layer:
            ring = onp.zeros((rows.shape[0], M, rows.shape[-1]), "float32")
            ring[:, :P] = rows.asnumpy()
            rings.append(nd.array(ring))
        caches.append(tuple(rings))
    return caches


# -- the model ----------------------------------------------------------------
def test_full_forward_is_the_reference():
    net = _net()
    toks = _tokens(24)
    got, sel = net.forward(nd.array(toks), want_selections=True)
    want = _reference(net, toks[0])
    assert onp.abs(got.asnumpy()[0] - onp.asarray(want["logits"])).max() < 2e-5
    assert len(sel["experts"]) == 4                 # layers 1..4 route
    for idx, scores, r_idx, r_scores in zip(
            sel["experts"], sel["router_scores"], want["experts"],
            want["router_scores"]):
        assert onp.abs(onp.asarray(scores) - onp.asarray(r_scores)).max() < 1e-5
        assert (onp.sort(onp.asarray(idx), -1)
                == onp.sort(onp.asarray(r_idx), -1)).all()
    # and on imposed experts the reference gives the same logits
    again = _reference(net, toks[0], selections={"experts": sel["experts"]})
    assert onp.abs(onp.asarray(again["logits"])
                   - onp.asarray(want["logits"])).max() < 1e-6


def test_the_drawn_bias_and_the_scale_of_a_tied_head():
    net = _net()
    w = net.raw_weights()
    bias = onp.asarray(w["layers.1.ffn.select_bias"])
    assert 0 < onp.abs(bias).max() < 0.1            # drawn, though named bias
    assert "head" not in w                          # the head is the embedding
    assert abs(float(onp.asarray(w["embed"]).std()) - 32 ** -0.5) < 0.02


def test_cache_spec_names_a_state_beside_rings():
    net = _net()
    spec = net.cache_spec(40)
    assert [[kind for kind, _s, _d in layer] for layer in spec] == [
        ["conv"], ["k", "v"], ["conv"], ["conv"], ["k", "v"]]
    assert spec[0][0][1] == (3, 32)                 # no position axis
    assert spec[1][0][1] == spec[1][1][1] == (40, 2 * 8)    # no head axis
    with pytest.raises(ValueError, match="layer_types"):
        tiny_lfm2(config={"num_hidden_layers": 4})


@pytest.mark.parametrize("valid", [(20, 20), (17, 11)])
def test_prefill_hands_the_state_over_at_the_valid_length(valid):
    """Prompts padded to one bucket: the conv state is that of each
    prompt's own end, and decoding on from there is the full forward."""
    net = _net()
    P, N, M = 20, 5, 32
    toks = _tokens(P + N, batch=2)
    full = net.forward(nd.array(toks)).asnumpy()
    vl = onp.asarray(valid, "int32")
    padded = toks[:, :P].copy()
    for b in range(2):
        padded[b, vl[b]:] = 0                       # what a bucket pads with
    logits, kvs = net.prefill(nd.array(padded), nd.array(vl))
    for b in range(2):
        assert onp.abs(logits.asnumpy()[b, :vl[b]]
                       - full[b, :vl[b]]).max() < 2e-5
    caches, pos = _rings(net, kvs, M, P), vl.copy()
    for _ in range(N):
        tok = onp.asarray([toks[b, pos[b]] for b in range(2)], "int32")
        lg, caches, counts = net.decode_step(nd.array(tok), caches,
                                             nd.array(pos))
        for b in range(2):
            assert onp.abs(lg.asnumpy()[b] - full[b, pos[b]]).max() < 2e-5
        pos += 1
        counts = dict(zip(COUNTERS, counts.asnumpy()))
        assert counts["routed_pairs"] == 2 * 4 * 4
        assert counts["attn_valid_positions"] == 2 * int(pos.sum())
        # on a CPU the attention is einsums over both whole rings
        assert counts["kv_rows_read"] == 2 * 2 * M
        assert 1 <= counts["expert_load_max"] <= 2
        assert 4 <= counts["experts_touched"] <= 2 * 4 * 4
        assert len(counts) == len(lfm2.STEP_COUNTERS)
        # on a CPU the product is ragged_dot: 8 pairs a layer are one row
        # tile of XLA's, which every expert with a pair would visit
        assert counts["expert_rows_computed"] == 8 * counts["experts_touched"]


def test_a_slot_that_sits_out_a_step_keeps_its_state_and_rings():
    net = _net()
    P, M = 12, 24
    toks = _tokens(P + 3, batch=2, seed=4)
    full = net.forward(nd.array(toks)).asnumpy()
    _lg, kvs = net.prefill(nd.array(toks[:, :P]))
    caches = _rings(net, kvs, M, P)
    before = [[r.asnumpy().copy() for r in layer] for layer in caches]
    # slot 0 sits out, slot 1 rides
    lg, caches, counts = net.decode_step(
        nd.array(toks[:, P]), caches, nd.array(onp.full(2, P, "int32")),
        active=nd.array(onp.asarray([0.0, 1.0], "float32")))
    for layer, was in zip(caches, before):
        for ring, old in zip(layer, was):
            assert (ring.asnumpy()[0] == old[0]).all()
            assert not (ring.asnumpy()[1] == old[1]).all()
    assert onp.abs(lg.asnumpy()[1] - full[1, P]).max() < 2e-5
    counts = dict(zip(COUNTERS, counts.asnumpy()))
    assert counts["routed_pairs"] == 4 * 4          # one rider
    assert counts["attn_valid_positions"] == 2 * (P + 1)
    # and rides the next one as if no step had passed
    lg, caches, _counts = net.decode_step(
        nd.array(onp.asarray([toks[0, P], toks[1, P + 1]], "int32")), caches,
        nd.array(onp.asarray([P, P + 1], "int32")))
    assert onp.abs(lg.asnumpy()[0] - full[0, P]).max() < 2e-5
    assert onp.abs(lg.asnumpy()[1] - full[1, P + 1]).max() < 2e-5


# -- the engine ---------------------------------------------------------------
def _greedy(net, prompt, n, pad=48):
    """``n`` greedy tokens by the full forward, no cache: one program at a
    padded length (no position sees the padding behind it)."""
    c, w = net.config, net.raw_weights()
    full = jax.jit(lambda w, t: lfm2.run_full(c, w, t[None])[0][0])
    toks = list(prompt)
    for _ in range(n):
        padded = onp.zeros(pad, "int32")
        padded[:len(toks)] = toks
        toks.append(int(full(w, jnp.asarray(padded))[len(toks) - 1].argmax()))
    return toks[len(prompt):]


@pytest.mark.parametrize("prompt_len", [5, 11, 13])
def test_through_the_engine_a_padded_prompt_is_the_references_forward(
        prompt_len):
    """Prompts shorter than their bucket (8 or 16): every emitted
    position's logits, from the prefill program and the decode program,
    against the reference's full forward over prompt + tokens."""
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=2, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert prompt_len not in eng.prefill_buckets
        prompt = _tokens(prompt_len, seed=prompt_len)[0].tolist()
        got = eng.submit(prompt, max_new_tokens=7, probe=True).result(300)
    finally:
        eng.stop()
    want = onp.asarray(_reference(net, prompt + got["tokens"][:-1])["logits"])
    assert len(got["probe"]) == 7
    for j, seen in enumerate(got["probe"]):
        assert onp.abs(seen["logits"] - want[prompt_len - 1 + j]).max() < 2e-5
        assert int(seen["logits"].argmax()) == got["tokens"][j]


def test_engine_churns_more_requests_than_slots_through_states_and_rings():
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=3, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert [k for k, _s, _d in eng._ring_specs[:3]] == ["conv", "k", "v"]
        assert [s for _k, s, _d in eng._ring_specs[:3]] == [
            (3, 3, 32), (3, 32, 16), (3, 32, 16)]
        rng = onp.random.RandomState(5)
        prompts = [rng.randint(0, 96, n).tolist()
                   for n in (5, 12, 3, 9, 14, 7, 4)]
        news = [9, 4, 13, 6, 3, 11, 8]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, news)]
        for p, n, s in zip(prompts, news, streams):
            assert s.result(300)["tokens"] == _greedy(net, p, n)
        st = eng.metrics.stats()
        c, g = st["counters"], st["gauges"]
        assert c["slot_allocs"] == 7 > eng.slots
        assert c["kv_inplace_dispatches"] == c["prefills"] + c["decode_steps"]
        assert c["kv_ring_rebuilds"] == 0
        assert g["kv_cache_bytes_conv"] == 3 * 3 * 3 * 32 * 4
        assert g["kv_cache_bytes_k"] == g["kv_cache_bytes_v"] \
            == 2 * 3 * 32 * 16 * 4
        assert g["kv_cache_bytes"] == eng.kv_cache_bytes \
            == g["kv_cache_bytes_conv"] + 2 * g["kv_cache_bytes_k"]
        # the step's counts came back with its tokens
        assert c["routed_pairs"] == 4 * 4 * c["tokens_generated"]
        assert 0 < c["experts_touched"] <= 4 * 16 * c["decode_steps"]
        assert c["decode_steps"] <= c["expert_load_max"] \
            <= 3 * c["decode_steps"]
        assert c["attn_valid_positions"] > 2 * c["tokens_generated"]
        snap = mx.telemetry.snapshot()
        assert snap["counters"]["generate/attn_valid_positions"] \
            >= c["attn_valid_positions"]
        assert snap["gauges"]["generate/kv_cache_bytes_conv"] \
            >= g["kv_cache_bytes_conv"]
    finally:
        eng.stop()


def test_a_reused_slot_shows_nothing_of_the_request_before_it():
    """One slot: a long request leaves its state and its rows behind, the
    next one's logits are those of an engine that never saw it."""
    net = _net(seed=11)
    prompt = list(range(20, 29))

    def probed(first):
        eng = GenerationEngine(net, slots=1, max_len=32,
                               prefill_buckets=(8, 16), cache=None)
        try:
            if first:
                eng.submit(first, max_new_tokens=15).result(300)
            return eng.submit(prompt, max_new_tokens=6,
                              probe=True).result(300)
        finally:
            eng.stop()
    fresh, reused = probed(None), probed(list(range(40, 54)))
    assert reused["tokens"] == fresh["tokens"]
    for a, b in zip(reused["probe"], fresh["probe"]):
        assert (a["logits"] == b["logits"]).all()
        assert (a["router_scores"][0] == b["router_scores"][0]).all()


def test_a_probed_request_beside_riders_shows_the_full_forwards_choices():
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=3, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        others = [eng.submit(p, max_new_tokens=20)
                  for p in ([3, 1, 4, 1, 5], [9, 2, 6])]
        next(iter(others[0]))
        prompt, n = list(range(10, 22)), 5
        got = eng.submit(prompt, max_new_tokens=n, probe=True).result(300)
        assert got["tokens"] == _greedy(net, prompt, n)
        seq = onp.asarray([prompt + got["tokens"][:-1]], "int32")
        full, sel = net.forward(nd.array(seq), want_selections=True)
        full, P = full.asnumpy()[0], len(prompt)
        for j, seen in enumerate(got["probe"]):
            assert onp.abs(seen["logits"] - full[P - 1 + j]).max() < 2e-5
        first, step = got["probe"][0], got["probe"][-1]
        for layer, want in enumerate(sel["experts"]):
            want = onp.sort(onp.asarray(want), -1)
            # the prefill's over its bucket of 16, a decode step's own row
            assert first["experts"][layer].shape == (16, 4)
            assert (onp.sort(first["experts"][layer][:P], -1)
                    == want[:P]).all()
            assert (onp.sort(step["experts"][layer]) == want[-1]).all()
        for layer, want in enumerate(sel["router_scores"]):
            assert onp.abs(step["router_scores"][layer]
                           - onp.asarray(want)[-1]).max() < 1e-5
        for s in others:
            assert len(s.result(300)["tokens"]) == 20
    finally:
        eng.stop()


def test_kv_budget_message_counts_every_kind(monkeypatch):
    from mxnet_tpu.serving.generate import ServingError
    net = _net(seed=13)
    need = 2 * (3 * 3 * 32 + 2 * 2 * 16 * 16) * 4
    monkeypatch.setenv("MXNET_KV_BUDGET_BYTES", str(need - 1))
    with pytest.raises(ServingError, match="MXNET_KV_BUDGET_BYTES") as e:
        GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,),
                         precompile=False)
    # every kind with its count and bytes, not the first layer's alone
    assert "3 x conv" in str(e.value) and "2 x k" in str(e.value) \
        and "2 x v" in str(e.value)
    monkeypatch.setenv("MXNET_KV_BUDGET_BYTES", str(need))
    eng = GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,),
                           precompile=False)
    assert eng.kv_cache_bytes == need
    eng.stop()


# -- the benchmark's check ----------------------------------------------------
@pytest.mark.parametrize("kv_dtype,agrees", [("float32", True),
                                             ("float8_e4m3fn", False)])
def test_the_benchmarks_probed_check_holds_states_and_rings_to_their_type(
        kv_dtype, agrees):
    """``chipbench/jobs/serve_lfm2.py``'s probed request, at the
    rehearsal's sizes: through the engine's programs it agrees with the
    reference as stated, and states and rings stored in fewer bits come
    out wrong by ``within``, the function that decides ``correct``."""
    from chipbench import common
    from chipbench.jobs import serve_lfm2 as job
    from chipbench.run import merge
    cfg = common.load("configs", "lfm2_24b_a2b_serve")
    cfg = merge(cfg, cfg["rehearse"])
    cfg["serving"]["kv_dtype"] = kv_dtype
    net, s = job.build(cfg, 5), cfg["serving"]
    eng = GenerationEngine(net, slots=s["slots"], max_len=s["max_len"],
                           prefill_buckets=tuple(s["prefill_buckets"]),
                           cache=None)
    try:
        assert {onp.dtype(d).name for _k, _s, d in eng._ring_specs} \
            == {kv_dtype}
        rider = eng.submit([1, 2, 3], max_new_tokens=40)
        probed = job.probed_requests(eng, cfg, 7, 300)
        assert len(rider.result(300)["tokens"]) == 40
    finally:
        eng.abort()
    limits = cfg["check"]["limits"]
    found = [job.probed_path(net.raw_weights(), job.model_config(cfg),
                             *one) for one in probed]
    assert [job.within(f, dict(limits, logits_tolerance=f["logits_tolerance"]))
            for f in found] == [agrees] * len(cfg["check"]["probed"])


def test_required_lfm2_reckons_the_configurations_bytes():
    """The yardstick's shapes against the issue's table, and a step's
    bytes against hand arithmetic."""
    from chipbench import common, required_lfm2
    from chipbench.jobs import serve_lfm2 as job
    shape = job.shape_of(common.load("configs", "lfm2_24b_a2b_serve"))
    assert required_lfm2.expert_params(shape) == 3 * 2048 * 1536
    assert round(required_lfm2.weight_params(shape) / 1e9, 2) == 5.18
    moe = 8 * 64 * 3 * 2048 * 1536
    outside = required_lfm2.weight_params(shape) - moe
    got = required_lfm2.decode_step_bytes(shape, 128, 512, 1000)
    assert got == 2 * (outside + moe) + 2 * (1000 * 2 * 512
                                             + 128 * 7 * 3 * 2048)
    flops = required_lfm2.decode_step_flops(shape, 128, 4096, 1000)
    assert flops == 2 * (128 * outside + 4096 * 3 * 2048 * 1536
                         + 1000 * 2 * 32 * 64)
