"""DeepSeek-V3.2-Exp at tiny sizes on the CPU, float32, seeded weights:
each layer and the whole model against the plain reference
(``chipbench/reference/deepseek_v32.py``), the expert layer's share, and
the generation engine through both rings."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import TransformerLM, tiny_v32
from mxnet_tpu.models import deepseek
from mxnet_tpu.parallel import moe
from mxnet_tpu.serving.generate import GenerationEngine

COUNTERS = [name for name, _help in deepseek.STEP_COUNTERS]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from chipbench.reference import deepseek_v32 as ref  # noqa: E402


def _net(seed=3, **kw):
    mx.random.seed(seed)
    # 16 indexer heads: with a few, every ReLU of a pair is 0 often enough
    # that index scores tie exactly at 0
    kw.setdefault("config", {"index_n_heads": 16})
    net = tiny_v32(**kw)
    net.initialize()
    return net


def _ref_cfg(net):
    c = net.config
    return dict({k: getattr(c, k) for k in deepseek.V32_PUBLISHED},
                held=c.held)


def _tokens(n, seed=0, batch=1, vocab=96):
    return onp.random.RandomState(seed).randint(
        0, vocab, (batch, n)).astype("int32")


@pytest.fixture(scope="module")
def net():
    return _net(held=(4, 8))


@pytest.fixture(scope="module")
def reference(net):
    toks = _tokens(24)
    return toks, ref.forward(net.raw_weights(), jnp.asarray(toks[0]),
                             _ref_cfg(net))


# -- parts --------------------------------------------------------------------
def test_yarn_frequencies_and_scale_agree_with_the_reference():
    c = _net().config
    cfg = {k: getattr(c, k) for k in deepseek.V32_PUBLISHED}
    onp.testing.assert_allclose(deepseek.yarn_inv_freq(c),
                                ref.rotary_frequencies(cfg), rtol=1e-6)
    assert abs(deepseek.softmax_scale(c) - ref.attention_scale(cfg)) < 1e-9
    # at the published sizes: the first frequencies stay, the last are
    # divided by the factor
    pub = type(c)(**deepseek.V32_PUBLISHED)
    got = deepseek.yarn_inv_freq(pub)
    plain = 10000.0 ** (-onp.arange(0, 64, 2) / 64)
    assert abs(got[0] - plain[0]) < 1e-6
    assert abs(got[-1] - plain[-1] / 40) < 1e-9
    assert abs(deepseek.softmax_scale(pub)
               - 192 ** -0.5 * (0.1 * onp.log(40) + 1) ** 2) < 1e-9


def test_full_forward_is_the_reference(net, reference):
    toks, want = reference
    got = net.forward(nd.array(toks)).asnumpy()[0]
    assert onp.abs(got - onp.asarray(want["logits"])).max() < 2e-5


def test_index_scores_and_selection_with_topk_below_the_context(net,
                                                                reference):
    toks, want = reference
    _logits, sel = net.forward(nd.array(toks), want_selections=True)
    L, K = toks.shape[1], net.config.index_topk
    assert K < L                                    # the selection is active
    for mask, scores, r_scores, r_mask in zip(
            sel["positions"], sel["index_scores"], want["index_scores"],
            want["positions"]):
        mask, r_scores = onp.asarray(mask[0]), onp.asarray(r_scores)
        valid = onp.isfinite(r_scores)
        assert onp.abs(onp.asarray(scores[0]) - r_scores)[valid].max() < 1e-5
        assert (mask == onp.asarray(r_mask)).all()
        assert (mask.sum(-1) == onp.minimum(K, onp.arange(L) + 1)).all()
        assert not (mask & ~valid).any()


@pytest.mark.parametrize("batch,length", [(1, 24), (2, 32)])
def test_full_forward_with_the_prefill_kernel_is_the_xla_form(
        monkeypatch, batch, length):
    """``run_full`` with ``parts.sparse_block_attend`` forced to its kernel
    (Pallas' interpreter, key blocks of 8) against the XLA form: logits
    within float32's rounding of the order of a sum, and the selections
    bit for bit: the indexer and its mask are the same code in both.  The
    first layer's index scores are the same bits; a later layer's follow
    a stream that differs in the last ones."""
    import functools
    from mxnet_tpu.ops import sparse_prefill_attention as spa
    net = _net(held=(4, 8))
    toks = jnp.asarray(_tokens(length, batch=batch))
    c, w = net.config, net.raw_weights()
    want = deepseek.run_full(c, w, toks, want_selections=True)
    with monkeypatch.context() as patch:
        patch.setattr(spa, "kernel_block", lambda *a: 8)
        patch.setattr(spa, "sparse_prefill_attention", functools.partial(
            spa.sparse_prefill_attention, interpret=True))
        got = deepseek.run_full(c, w, toks, want_selections=True)
    assert length > c.index_topk                # the selection is live
    assert float(jnp.abs(got[0] - want[0]).max()) < 2e-5
    for (latent, ki), (w_latent, w_ki) in zip(got[1], want[1]):
        assert float(jnp.abs(latent - w_latent).max()) < 2e-5
        assert float(jnp.abs(ki - w_ki).max()) < 2e-5
    for a, b in zip(got[2]["positions"], want[2]["positions"]):
        assert (onp.asarray(a) == onp.asarray(b)).all()
    first, *later = zip(got[2]["index_scores"], want[2]["index_scores"])
    assert (onp.asarray(first[0]) == onp.asarray(first[1])).all()
    for a, b in later:
        assert float(jnp.abs(a - b).max()) < 2e-5


def test_router_groups_and_gates_against_a_loop():
    rng = onp.random.RandomState(1)
    T, E, G, keep, k, scale = 40, 32, 8, 3, 4, 2.5
    scores = 1 / (1 + onp.exp(-rng.randn(T, E))).astype("float32")
    bias = (0.3 * rng.randn(E)).astype("float32")
    idx, gates = moe.noaux_route(jnp.asarray(scores), jnp.asarray(bias), k,
                                 G, keep, scale)
    idx, gates = onp.asarray(idx), onp.asarray(gates)
    for t in range(T):
        biased = scores[t] + bias
        group = [sum(sorted(biased[g * 4:(g + 1) * 4])[-2:])
                 for g in range(G)]
        kept = sorted(range(G), key=lambda g: -group[g])[:keep]
        allowed = [e for e in range(E) if e // 4 in kept]
        chosen = sorted(allowed, key=lambda e: -biased[e])[:k]
        assert sorted(idx[t]) == sorted(chosen)
        total = sum(scores[t][e] for e in chosen)
        for e, g in zip(idx[t], gates[t]):
            assert abs(g - scale * scores[t][e] / total) < 1e-6   # no bias


def test_dropless_where_the_capacity_router_drops():
    """Every token to the same experts: a capacity router drops most of
    them, the dropless layer computes every pair."""
    mx.random.seed(5)
    d, E, k, T = 16, 8, 2, 24
    layer = moe.DroplessMoE(d, 8, E, k, shared_experts=0)
    layer.initialize()
    x = jnp.asarray(onp.tile(onp.random.RandomState(2).randn(1, d), (T, 1)),
                    jnp.float32)
    y, idx, _scores = layer.apply(x)
    assert (onp.asarray(idx) == onp.asarray(idx[0])).all()   # one target
    load = moe.held_load(idx, 0, E)
    assert int(load[1]) == T * k and int(load[3]) == T       # none lost
    rows = onp.asarray(y)
    assert onp.abs(rows - rows[0]).max() < 1e-6 and onp.abs(rows[0]).max() > 0
    # the capacity router at this load keeps capacity tokens an expert
    probs = jnp.tile(jax.nn.softmax(jnp.arange(E, dtype=jnp.float32))[None],
                     (T, 1))
    cap = moe.MoE(d, 8, E, k).capacity(T)
    combine, _aux = moe.moe_dispatch(probs, k, cap)
    assert int((onp.asarray(combine).sum((1, 2)) > 0).sum()) == cap < T


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """The parts of the result from all the holders, the shared expert
    counted once, are the uncut layer's result; and that is the
    reference's for the whole layer."""
    mx.random.seed(7)
    d, f, E, k, shares = 16, 8, 16, 4, 4
    args = dict(n_group=4, topk_group=2, route_scale=2.5)
    whole = moe.DroplessMoE(d, f, E, k, held=(0, E), **args,
                            bias_initializer=mx.init.Normal(0.05))
    whole.initialize()
    w = {n: p.data()._data for n, p in whole._reg_params.items()}
    x = jnp.asarray(onp.random.RandomState(3).randn(30, d), jnp.float32)
    want, idx, _ = whole.apply(x)
    total = 0.0
    for r in range(shares):
        part = moe.DroplessMoE(d, f, E, k, held=(r * 4, 4), **args)
        part.initialize()
        for name, p in part._reg_params.items():
            src = w[name]
            p.set_data(src[r * 4:(r + 1) * 4] if name.startswith("held_")
                       else src)
        y, idx_r, _ = part.apply(x, with_shared=(r == 0))
        assert (onp.asarray(idx_r) == onp.asarray(idx)).all()
        total = total + y
    assert onp.abs(onp.asarray(total - want)).max() < 1e-5
    cfg = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": k,
           "routed_scaling_factor": 2.5, "held": (0, E)}
    y_ref, _s, idx_ref = ref.feed_forward(
        cfg, {"ffn." + n: v for n, v in w.items()}, x, None)
    assert onp.abs(onp.asarray(y_ref - want)).max() < 1e-5
    assert (onp.sort(onp.asarray(idx_ref), -1)
            == onp.sort(onp.asarray(idx), -1)).all()


def test_moe_sharding_rules_cover_the_held_stack():
    import re
    rules = moe.moe_sharding_rules("expert")
    layer = moe.DroplessMoE(8, 4, 8, 2, held=(0, 4))
    for name in layer._reg_params:
        spec = next(s for pat, s in rules if re.search(pat, name))
        assert (tuple(spec) == ("expert",)) == name.startswith("held_")


# -- the cached path ----------------------------------------------------------
@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_prefill_then_decode_is_the_full_forward(held):
    net = _net(held=held)
    P, N, M = 20, 5, 32
    toks = _tokens(P + N, batch=2)
    full = net.forward(nd.array(toks)).asnumpy()
    want = ref.forward(net.raw_weights(), jnp.asarray(toks[0]),
                       _ref_cfg(net))["logits"]
    assert onp.abs(full[0] - onp.asarray(want)).max() < 2e-5
    logits, kvs = net.prefill(nd.array(toks[:, :P]))
    assert onp.abs(logits.asnumpy() - full[:, :P]).max() < 2e-5
    caches = []
    for layer in kvs:
        rings = []
        for rows in layer:
            ring = onp.zeros((2, M, rows.shape[-1]), "float32")
            ring[:, :P] = rows.asnumpy()
            rings.append(nd.array(ring))
        caches.append(tuple(rings))
    for j in range(N):
        lg, caches, counts = net.decode_step(
            nd.array(toks[:, P + j]), caches,
            nd.array(onp.full(2, P + j, "int32")))
        assert onp.abs(lg.asnumpy() - full[:, P + j]).max() < 2e-5
        counts = dict(zip(COUNTERS, counts.asnumpy()))
        layers, moe_layers, K = 3, 2, net.config.index_topk
        assert counts["index_valid_positions"] == 2 * layers * (P + j + 1)
        assert counts["index_selected_positions"] == 2 * layers * K
        # on a CPU attention gathers the selected rows and reads no other
        assert counts["latent_rows_read"] == 2 * layers * K
        assert len(counts) == len(deepseek.STEP_COUNTERS)
        assert counts["routed_pairs"] == 2 * moe_layers * 4
        assert (counts["routed_pairs_held"] == counts["routed_pairs"]) \
            == (held == (0, 16))
        # on a CPU the product is ragged_dot: 8 pairs a layer are one row
        # tile of XLA's, which every held expert with a pair would visit
        assert counts["expert_rows_computed"] == 8 * counts["experts_touched"]
    # the step's selections, on request, are the full forward's last row
    _lg, sel = net.forward(nd.array(toks), want_selections=True)
    raw = [tuple(r._data for r in layer) for layer in caches]
    *_rest, mine = deepseek.decode(
        net.config, net.raw_weights(), jnp.asarray(toks[:, -1]), raw,
        jnp.full((2,), P + N - 1, jnp.int32), want_selections=True)
    for got, want_mask in zip(mine["positions"], sel["positions"]):
        assert (onp.asarray(got)[:, :P + N]
                == onp.asarray(want_mask)[:, -1]).all()
    for got, want_idx in zip(mine["experts"], sel["experts"]):
        assert (onp.sort(onp.asarray(got), -1) == onp.sort(onp.asarray(
            want_idx).reshape(2, P + N, -1)[:, -1], -1)).all()


def test_inactive_slots_write_nothing_and_count_nothing():
    net = _net()
    spec = net.cache_spec(16)
    caches = [tuple(nd.array(onp.ones((2,) + shape, "float32"))
                    for _kind, shape, _dt in layer) for layer in spec]
    _lg, new, counts = net.decode_step(
        nd.array(onp.asarray([5, 6], "int32")), caches,
        nd.array(onp.asarray([3, 4], "int32")),
        active=nd.array(onp.asarray([0.0, 1.0], "float32")))
    for (ring_l, ring_i) in new:
        assert (ring_l.asnumpy()[0] == 1).all() and \
            (ring_i.asnumpy()[0] == 1).all()
        assert not (ring_l.asnumpy()[1, 4] == 1).all()
        assert (ring_l.asnumpy()[1, :4] == 1).all()
    counts = dict(zip(COUNTERS, counts.asnumpy()))
    assert counts["index_valid_positions"] == 3 * 5
    assert counts["routed_pairs"] == 2 * 4


# -- the engine ---------------------------------------------------------------
def _greedy(net, prompt, n, pad=32):
    """``n`` greedy tokens by the full forward, no cache: one program at a
    padded length (under the causal mask no position sees the padding)."""
    c, w = net.config, net.raw_weights()
    full = jax.jit(lambda w, t: deepseek.run_full(c, w, t[None])[0][0])
    toks = list(prompt)
    for _ in range(n):
        padded = onp.zeros(pad, "int32")
        padded[:len(toks)] = toks
        logits = full(w, jnp.asarray(padded))
        toks.append(int(logits[len(toks) - 1].argmax()))
    return toks[len(prompt):]


def test_engine_serves_through_both_rings_in_place():
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=3, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert [k for k, _s, _d in eng._ring_specs[:2]] == ["latent",
                                                           "indexer"]
        # a latent row of 16 + 4 numbers is stored at the lane width
        assert [s for _k, s, _d in eng._ring_specs[:2]] == [(3, 32, 128),
                                                           (3, 32, 8)]
        prompts = [[3, 1, 4, 1, 5], list(range(10, 22)), [9, 2, 6]]
        streams = [eng.submit(p, max_new_tokens=9) for p in prompts]
        for p, s in zip(prompts, streams):
            assert s.result(300)["tokens"] == _greedy(net, p, 9)
        st = eng.metrics.stats()
        c, g = st["counters"], st["gauges"]
        assert c["kv_inplace_dispatches"] == c["prefills"] + c["decode_steps"]
        assert c["kv_ring_rebuilds"] == 0
        assert g["kv_cache_bytes_latent"] == 3 * 3 * 32 * 128 * 4
        assert g["kv_cache_bytes_indexer"] == 3 * 3 * 32 * 8 * 4
        assert g["kv_cache_bytes"] == eng.kv_cache_bytes \
            == g["kv_cache_bytes_latent"] + g["kv_cache_bytes_indexer"]
        # the step's counts came back with its tokens
        assert c["index_valid_positions"] > c["index_selected_positions"] > 0
        assert c["routed_pairs"] == 2 * 4 * c["tokens_generated"]
        assert 0 < c["experts_touched"] <= 2 * 16 * c["decode_steps"]
        assert c["decode_steps"] <= c["expert_load_max"] \
            <= 3 * c["decode_steps"]
        # what the model brought is declared under the collector's names
        snap = mx.telemetry.snapshot()
        assert snap["counters"]["generate/routed_pairs"] >= c["routed_pairs"]
        assert snap["gauges"]["generate/kv_cache_bytes_indexer"] \
            >= g["kv_cache_bytes_indexer"]
    finally:
        eng.stop()


def test_a_probed_request_shows_what_the_serving_programs_computed():
    """Its slot of the live rings, other requests in flight beside it: the
    logits each token is the largest of and the model's selections, equal
    to the full forward's over prompt + tokens."""
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
        assert len(got["probe"]) == n
        seq = onp.asarray([prompt + got["tokens"][:-1]], "int32")
        full, sel = net.forward(nd.array(seq), want_selections=True)
        full, P = full.asnumpy()[0], len(prompt)
        for j, seen in enumerate(got["probe"]):
            assert seen["logits"].dtype == onp.float32
            assert onp.abs(seen["logits"] - full[P - 1 + j]).max() < 2e-5
            assert int(seen["logits"].argmax()) == got["tokens"][j]
        first, step = got["probe"][0], got["probe"][-1]
        # the prefill's selections over its bucket, a decode step's over
        # the ring: the request's own row
        for layer, want in enumerate(sel["positions"]):
            want = onp.asarray(want)[0]
            assert (first["positions"][layer][0, :P, :P] == want[:P, :P]).all()
            assert (step["positions"][layer][:P + n - 1] == want[-1]).all()
            assert not step["positions"][layer][P + n - 1:].any()
        for layer, want in enumerate(sel["experts"]):
            assert (onp.sort(step["experts"][layer]) == onp.sort(
                onp.asarray(want)[-1])).all()
        for s in others:
            assert len(s.result(300)["tokens"]) == 20
        c = eng.metrics.stats()["counters"]
        assert c["kv_inplace_dispatches"] == c["prefills"] + c["decode_steps"]
        assert "probe" not in others[0].result()
    finally:
        eng.stop()


def test_the_rings_take_the_type_the_model_states():
    """``cache_dtype``: rows are stored in it by both programs and read
    back into the activations' type."""
    net = _net(seed=11, cache_dtype="float8_e4m3fn")
    eng = GenerationEngine(net, slots=2, max_len=32, prefill_buckets=(8,),
                           cache=None)
    try:
        assert {onp.dtype(d).name for _k, _s, d in eng._ring_specs} \
            == {"float8_e4m3fn"}
        assert eng.kv_cache_bytes == 3 * 2 * 32 * (128 + 8)
        got = eng.submit([3, 1, 4, 1, 5], max_new_tokens=6,
                         probe=True).result(300)
        exact = _net(seed=11)
        full = exact.forward(nd.array(onp.asarray(
            [[3, 1, 4, 1, 5] + got["tokens"][:-1]], "int32"))).asnumpy()[0]
        diff = [onp.abs(seen["logits"] - full[4 + j]).max()
                for j, seen in enumerate(got["probe"])]
        # the prefill's logits never saw a ring; a decode step's did
        assert diff[0] < 2e-5 < min(diff[1:]) and max(diff) < 0.5
    finally:
        eng.stop()


def test_engine_abort_fails_riders_and_gives_the_rings_back():
    from mxnet_tpu.serving.generate import EngineClosedError
    net = _net(seed=12)
    eng = GenerationEngine(net, slots=2, max_len=32, prefill_buckets=(8,),
                           cache=None)
    stream = eng.submit([1, 2, 3], max_new_tokens=10 ** 6)
    next(iter(stream))                      # it is decoding
    eng.abort()
    with pytest.raises(EngineClosedError):
        stream.result(60)
    assert eng._cache_flat == []


def test_kv_budget_counts_every_ring(monkeypatch):
    from mxnet_tpu.serving.generate import ServingError
    net = _net(seed=13)
    need = 3 * 2 * 16 * (128 + 8) * 4
    monkeypatch.setenv("MXNET_KV_BUDGET_BYTES", str(need - 1))
    with pytest.raises(ServingError, match="MXNET_KV_BUDGET_BYTES"):
        GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,),
                         precompile=False)
    monkeypatch.setenv("MXNET_KV_BUDGET_BYTES", str(need))
    eng = GenerationEngine(net, slots=2, max_len=16, prefill_buckets=(8,),
                           precompile=False)
    assert eng.kv_cache_bytes == need
    eng.stop()


def test_transformer_lm_programs_keep_rings_donation_and_shapes():
    mx.random.seed(2)
    lm = TransformerLM(vocab_size=64, num_layers=2, units=32, hidden_size=64,
                       num_heads=4, max_length=32)
    lm.initialize()
    lm(nd.array(onp.zeros((1, 4), "int32")),
       nd.array(onp.asarray([4], "int32")))
    assert lm.cache_spec(16) == [[("key", (4, 16, 8), "float32"),
                                  ("value", (4, 16, 8), "float32")]] * 2
    eng = GenerationEngine(lm, slots=3, max_len=16, prefill_buckets=(8,),
                           cache=None)
    try:
        assert [(s, onp.dtype(d).name) for _k, s, d in eng._ring_specs] \
            == [((3, 4, 16, 8), "float32")] * 4
        text = eng._decode_prog[0].as_text()
        # four ring arguments and the slots' last tokens, each aliased to
        # an output; tokens only (no counts) in the first output
        header = text[:text.index("\n")]
        assert header.count("may-alias") + header.count("must-alias") == 5
        assert "s32[3]" in text and "s32[9]" not in text
        got = eng.generate([5, 6, 7], max_new_tokens=6, timeout=120)
        assert len(got["tokens"]) == 6
        c = eng.metrics.stats()["counters"]
        assert c["kv_inplace_dispatches"] == c["prefills"] + c["decode_steps"]
        assert "index_valid_positions" not in c
        # probed, a model that brings nothing shows its logits alone
        got = eng.submit([5, 6, 7], max_new_tokens=3, probe=True).result(120)
        assert [sorted(seen) for seen in got["probe"]] == [["logits"]] * 3
        assert [int(seen["logits"].argmax()) for seen in got["probe"]] \
            == got["tokens"]
    finally:
        eng.stop()


# recorded on the parent commit (320c567) on the CPU, float32: seed 2,
# vocab 64, 2 layers x 32 x 64, 4 heads, prompt [5, 6, 7, 8], 3 cached
# decode steps of token 9, 10, 11 -- see _lm_logits
_PARENT_LOGITS = os.path.join(os.path.dirname(__file__), "data",
                              "transformer_lm_cached_logits_pr26.npy")


def _lm_logits():
    mx.random.seed(2)
    lm = TransformerLM(vocab_size=64, num_layers=2, units=32, hidden_size=64,
                       num_heads=4, max_length=32)
    lm.initialize()
    prompt = onp.asarray([[5, 6, 7, 8]], "int32")
    logits, kvs = lm.prefill(nd.array(prompt),
                             nd.array(onp.asarray([4], "int32")))
    rows = [logits.asnumpy()[0, 3]]
    caches = []
    for k, v in kvs:
        ring = onp.zeros((2, 1, 4, 16, 8), "float32")
        ring[0, :, :, :4], ring[1, :, :, :4] = k.asnumpy(), v.asnumpy()
        caches.append((nd.array(ring[0]), nd.array(ring[1])))
    for j, tok in enumerate((9, 10, 11)):
        lg, caches = lm.decode_step(
            nd.array(onp.asarray([tok], "int32")), caches,
            nd.array(onp.asarray([4 + j], "int32")))
        rows.append(lg.asnumpy()[0])
    return onp.stack(rows)


def test_transformer_lm_logits_are_bit_identical_to_the_parents():
    want = onp.load(_PARENT_LOGITS)
    got = _lm_logits()
    assert got.dtype == want.dtype and (got == want).all()


@pytest.mark.parametrize("kv_dtype,agrees", [("float32", True),
                                             ("float8_e4m3fn", False)])
def test_the_benchmarks_probed_check_holds_the_rings_to_their_type(
        kv_dtype, agrees):
    """``chipbench/jobs/serve_dsv32.py``'s probed requests, at the
    rehearsal's sizes: through the engine's programs they agree with the
    reference as stated, and rings stored in fewer bits come out wrong by
    ``within``, the function that decides ``correct``."""
    from chipbench import common
    from chipbench.jobs import serve_dsv32 as job
    from chipbench.run import merge
    cfg = common.load("configs", "deepseek_v32_ep16_serve")
    cfg = merge(cfg, cfg["rehearse"])
    cfg["serving"]["kv_dtype"] = kv_dtype
    net, s = job.build(cfg, 5), cfg["serving"]
    eng = GenerationEngine(net, slots=s["slots"], max_len=s["max_len"],
                           prefill_buckets=tuple(s["prefill_buckets"]),
                           cache=None)
    try:
        rider = eng.submit([1, 2, 3], max_new_tokens=40)
        probed = job.probed_requests(eng, cfg, 7, 300)
        assert len(rider.result(300)["tokens"]) == 40
    finally:
        eng.abort()
    limits = cfg["check"]["limits"]
    found = [job.probed_path(net.raw_weights(), job.reference_config(cfg),
                             *one) for one in probed]
    assert [job.within(f, dict(limits, logits_tolerance=f["logits_tolerance"]))
            for f in found] == [agrees] * len(cfg["check"]["probed"])
