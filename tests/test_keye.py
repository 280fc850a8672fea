"""Keye-VL-2.0's language model at tiny sizes on the CPU, float32, seeded
weights: the whole model against the plain reference
(``chipbench/reference/keye.py``) under and over ``index_topk`` and with
three-axis positions that differ, the three rings a slot holds through the
generation engine, and the benchmark's check."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import keye, parts, tiny_keye
from mxnet_tpu.serving.generate import GenerationEngine

COUNTERS = [name for name, _help in keye.STEP_COUNTERS]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from chipbench.reference import keye as ref  # noqa: E402

TOPK = 8            # tiny_keye's sa_config.topk


def _net(seed=3, **kw):
    mx.random.seed(seed)
    net = tiny_keye(**kw)
    net.initialize()
    return net


def _ref_cfg(net):
    return {k: getattr(net.config, k) for k in keye.KEYE_PUBLISHED}


def _tokens(n, seed=0, batch=1, vocab=96):
    return onp.random.RandomState(seed).randint(
        0, vocab, (batch, n)).astype("int32")


def _reference(net, seq, **kw):
    return ref.forward(net.raw_weights(), jnp.asarray(seq, jnp.int32),
                       _ref_cfg(net), block=8, **kw)


def _mask_of(chosen, L):
    """[L, K] indices (-1: none) as the [L, L] mask they name."""
    mask = onp.zeros((L, L), bool)
    for t, row in enumerate(onp.asarray(chosen)):
        mask[t, row[row >= 0]] = True
    return mask


def _patch_positions(L, at=8, side=4, rows=2):
    """Text, then ``rows x side`` patches of one image (one temporal
    index, a grid of heights and widths), then text that goes on after the
    image's largest index: three axes that differ."""
    n = rows * side
    p = onp.tile(onp.arange(L), (3, 1))
    p[0, at:at + n] = at
    p[1, at:at + n] = at + onp.arange(n) // side
    p[2, at:at + n] = at + onp.arange(n) % side
    p[:, at + n:] = at + side + onp.arange(L - at - n)
    return p.astype("int32")


def _rings(kvs, M, P):
    """What ``prefill`` returned as the rings ``decode_step`` takes: the
    rows at the front of a ring of ``M`` positions."""
    caches = []
    for layer in kvs:
        rings = []
        for rows in layer:
            ring = onp.zeros((rows.shape[0], M, rows.shape[-1]), "float32")
            ring[:, :P] = rows.asnumpy()[:, :P]
            rings.append(nd.array(ring))
        caches.append(tuple(rings))
    return caches


# -- the model ----------------------------------------------------------------
@pytest.mark.parametrize("length,positions", [
    (6, None), (24, None), (24, "patches")],
    ids=["under_topk", "over_topk", "three_axes"])
def test_full_forward_is_the_reference(length, positions):
    net = _net()
    toks = _tokens(length)
    p3 = _patch_positions(length) if positions else None
    got, sel = net.forward(
        nd.array(toks), want_selections=True,
        positions=None if p3 is None else nd.array(p3[:, None]))
    want = _reference(net, toks[0], positions=p3)
    assert onp.abs(got.asnumpy()[0] - onp.asarray(want["logits"])).max() < 5e-5
    for layer in range(3):
        chosen = sel["positions"][layer]
        if length <= TOPK:
            # nothing is scored below index_topk: every query sees its past
            assert chosen is None
            assert onp.asarray(want["positions"][layer]).sum() \
                == length * (length + 1) // 2
            continue
        mask = _mask_of(chosen[0], length)
        assert (mask == onp.asarray(want["positions"][layer])).all()
        # the selection discards at least half of what the last rows see
        assert mask[-1].sum() == TOPK <= length // 2
        scores = onp.asarray(want["index_scores"][layer])
        own = onp.asarray(sel["index_scores"][layer][0])
        rows, cols = onp.nonzero(onp.asarray(chosen[0]) >= 0)
        assert onp.abs(own[rows, cols] - scores[
            rows, onp.asarray(chosen[0])[rows, cols]]).max() < 1e-5
    for idx, scores, r_idx, r_scores in zip(
            sel["experts"], sel["router_scores"], want["experts"],
            want["router_scores"]):
        assert onp.abs(onp.asarray(scores) - onp.asarray(r_scores)).max() < 1e-6
        assert (onp.sort(onp.asarray(idx), -1)
                == onp.sort(onp.asarray(r_idx), -1)).all()
    if positions:
        plain = net.forward(nd.array(toks)).asnumpy()
        assert onp.abs(plain - got.asnumpy()).max() > 0.1
    if length > TOPK:
        # on imposed selections the reference gives the same logits, and
        # what a judge needs of its scores without an [L, L] array
        again = _reference(net, toks[0], positions=p3, whole=False,
                           selections={
                               "positions": [p[0] for p in sel["positions"]],
                               "experts": sel["experts"]})
        assert onp.abs(onp.asarray(again["logits"])
                       - onp.asarray(want["logits"])).max() < 1e-6
        scores = onp.asarray(want["index_scores"][0])
        kth = onp.sort(scores, -1)[:, -TOPK]
        assert (onp.asarray(again["index_kth"][0]) == kth).all()
        valid = onp.isfinite(scores)
        assert onp.allclose(onp.asarray(again["index_moments"][0]), [
            scores[valid].sum(), (scores[valid] ** 2).sum(), valid.sum()],
            rtol=1e-5)


@pytest.mark.parametrize("batch,length,positions", [
    (1, 24, None), (2, 32, None), (1, 24, "patches")],
    ids=["one", "two", "three_axes"])
def test_full_forward_with_the_prefill_kernel_is_the_xla_form(
        monkeypatch, batch, length, positions):
    """``run_full`` with ``parts.sparse_block_attend`` forced to its kernel
    (Pallas' interpreter, key blocks of 8; query blocks of 8 a key head's
    two query heads) against the XLA form: logits within float32's
    rounding of the order of a sum, and the selections bit for bit: the
    indexer and its ``top_k`` are the same code in both.  The first layer's
    index scores are the same bits; a later layer's follow a stream that
    differs in the last ones."""
    import functools
    from mxnet_tpu.ops import sparse_prefill_attention as spa
    net = _net()
    toks = jnp.asarray(_tokens(length, batch=batch))
    p3 = None if positions is None else jnp.asarray(onp.broadcast_to(
        _patch_positions(length)[:, None], (3, batch, length)))
    c, w = net.config, net.raw_weights()
    want = keye.run_full(c, w, toks, p3, want_selections=True)
    with monkeypatch.context() as patch:
        patch.setattr(spa, "kernel_block", lambda *a: 8)
        patch.setattr(spa, "sparse_prefill_attention", functools.partial(
            spa.sparse_prefill_attention, interpret=True))
        got = keye.run_full(c, w, toks, p3, want_selections=True)
    assert length > TOPK                        # the selection is live
    assert float(jnp.abs(got[0] - want[0]).max()) < 2e-5
    for layer_got, layer_want in zip(got[1], want[1]):
        for a, b in zip(layer_got, layer_want):
            assert float(jnp.abs(a - b).max()) < 2e-5
    for a, b in zip(got[2]["positions"], want[2]["positions"]):
        assert (onp.asarray(a) == onp.asarray(b)).all()
    first, *later = zip(got[2]["index_scores"], want[2]["index_scores"])
    assert (onp.asarray(first[0]) == onp.asarray(first[1])).all()
    for a, b in later:
        a, b = onp.asarray(a), onp.asarray(b)
        # -inf where a query has fewer valid positions than top_k keeps
        finite = onp.isfinite(a)
        assert (finite == onp.isfinite(b)).all()
        assert onp.abs(a[finite] - b[finite]).max() < 2e-5


def test_sectioned_rotary_with_equal_axes_is_plain_rotary():
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 7, 3, 16), jnp.float32)
    pos = jnp.asarray(rs.randint(0, 500, (2, 7)), jnp.int32)
    theta = 1e7
    ang = parts.sectioned_angles(jnp.broadcast_to(pos[None], (3, 2, 7)), 16,
                                 theta, (2, 4, 2))
    plain = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        1.0 / theta ** (onp.arange(0, 16, 2) / 16), jnp.float32)
    assert onp.abs(onp.asarray(ang - plain)).max() < 1e-3 * float(plain.max())
    got = parts.rope(x, jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None],
                     False)
    want = parts.rope(x, jnp.cos(plain)[:, :, None],
                      jnp.sin(plain)[:, :, None], False)
    assert onp.abs(onp.asarray(got - want)).max() < 1e-5
    # and an axis of its own turns its section's frequencies alone
    p3 = jnp.stack([pos, pos + 3, pos])
    moved = parts.sectioned_angles(p3, 16, theta, (2, 4, 2))
    differs = onp.abs(onp.asarray(moved - ang)).max(axis=(0, 1)) > 0
    assert differs.tolist() == [False] * 2 + [True] * 4 + [False] * 2
    with pytest.raises(ValueError, match="add up"):
        parts.sectioned_angles(p3, 16, theta, (2, 4, 4))


def test_cache_spec_names_three_rings_a_layer():
    net = _net()
    spec = net.cache_spec(40)
    assert [[kind for kind, _s, _d in layer] for layer in spec] == [
        ["k", "v", "indexer"]] * 3
    assert spec[0][0][1] == spec[0][1][1] == (40, 2 * 16)   # no head axis
    # the indexer's key of 8 numbers at the lanes' stride
    assert spec[0][2][1] == (40, 128)
    assert net.config.index_section == (1, 2, 1)
    with pytest.raises(ValueError, match="one indexer key head"):
        tiny_keye(config={"tie_word_embeddings": True})


@pytest.mark.parametrize("ring_len", [128, 48], ids=["lanes", "scatter"])
def test_a_decode_steps_selection_is_top_ks_own(ring_len):
    """A ring of whole lanes and one that is not, under the model:
    prefill, then steps whose contexts are past ``index_topk``: the
    positions each step selected (``parts.topk_mask`` over the ring) are
    the full forward's row (the same function over the sequence), and so
    are its logits."""
    net = _net()
    P, N = 20, 4
    toks = _tokens(P + N, batch=2, seed=2)
    full, sel = net.forward(nd.array(toks), want_selections=True)
    full = full.asnumpy()
    logits, kvs = net.prefill(nd.array(toks[:, :P]))
    assert logits.shape == (2, 1, 96)           # the last valid row alone
    assert onp.abs(logits.asnumpy()[:, 0] - full[:, P - 1]).max() < 5e-5
    caches = _rings(kvs, ring_len, P)
    for j in range(N):
        pos = onp.full(2, P + j, "int32")
        lg, caches, counts, seen = net.decode_step(
            nd.array(toks[:, P + j]), caches, nd.array(pos), probe=True)
        assert onp.abs(lg.asnumpy() - full[:, P + j]).max() < 5e-5
        for layer in range(3):
            got = onp.sort(onp.asarray(seen["positions"][layer]), -1)
            want = onp.sort(onp.asarray(sel["positions"][layer])[:, P + j],
                            -1)
            assert (got == want).all() and (got >= 0).all()
        counts = dict(zip(COUNTERS, counts.asnumpy()))
        assert counts["index_valid_positions"] == 3 * 2 * (P + j + 1) \
            == counts["attn_valid_positions"]
        assert counts["index_selected_positions"] == 3 * 2 * TOPK
        assert counts["kv_rows_read"] == 3 * 2 * ring_len
        assert counts["routed_pairs"] == 3 * 2 * 4
        assert 1 <= counts["expert_load_max"] <= 2
        assert len(counts) == len(keye.STEP_COUNTERS)
        # on a CPU the product is ragged_dot: 8 pairs a layer are one row
        # tile of XLA's, which every expert with a pair would visit
        assert counts["expert_rows_computed"] == 8 * counts["experts_touched"]


def test_a_slot_that_sits_out_a_step_keeps_its_three_rings():
    net = _net()
    P, M = 12, 24
    toks = _tokens(P + 3, batch=2, seed=4)
    full = net.forward(nd.array(toks)).asnumpy()
    _lg, kvs = net.prefill(nd.array(toks[:, :P]))
    caches = _rings(kvs, M, P)
    before = [[r.asnumpy().copy() for r in layer] for layer in caches]
    # slot 0 sits out, slot 1 rides
    lg, caches, counts = net.decode_step(
        nd.array(toks[:, P]), caches, nd.array(onp.full(2, P, "int32")),
        active=nd.array(onp.asarray([0.0, 1.0], "float32")))
    for layer, was in zip(caches, before):
        assert len(layer) == 3
        for ring, old in zip(layer, was):
            assert (ring.asnumpy()[0] == old[0]).all()
            assert not (ring.asnumpy()[1] == old[1]).all()
    assert onp.abs(lg.asnumpy()[1] - full[1, P]).max() < 5e-5
    counts = dict(zip(COUNTERS, counts.asnumpy()))
    assert counts["routed_pairs"] == 3 * 4          # one rider
    assert counts["index_valid_positions"] == 3 * (P + 1)
    # and rides the next one as if no step had passed
    lg, caches, _counts = net.decode_step(
        nd.array(onp.asarray([toks[0, P], toks[1, P + 1]], "int32")), caches,
        nd.array(onp.asarray([P, P + 1], "int32")))
    assert onp.abs(lg.asnumpy()[0] - full[0, P]).max() < 5e-5
    assert onp.abs(lg.asnumpy()[1] - full[1, P + 1]).max() < 5e-5


# -- the engine ---------------------------------------------------------------
def _greedy(net, prompt, n, pad=48):
    """``n`` greedy tokens by the full forward, no cache: one program at a
    padded length (no position sees the padding behind it)."""
    c, w = net.config, net.raw_weights()
    full = jax.jit(lambda w, t: keye.run_full(c, w, t[None])[0][0])
    toks = list(prompt)
    for _ in range(n):
        padded = onp.zeros(pad, "int32")
        padded[:len(toks)] = toks
        toks.append(int(full(w, jnp.asarray(padded))[len(toks) - 1].argmax()))
    return toks[len(prompt):]


@pytest.mark.parametrize("prompt_len", [5, 7, 11])
def test_through_the_engine_a_padded_prompt_is_the_references_forward(
        prompt_len):
    """Prompts shorter than their bucket (8 or 16), contexts that cross
    ``index_topk`` (8) while decoding: every emitted position's logits,
    from the prefill program and the decode program, against the
    reference's full forward over prompt + tokens."""
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=2, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert prompt_len not in eng.prefill_buckets
        prompt = _tokens(prompt_len, seed=prompt_len)[0].tolist()
        got = eng.submit(prompt, max_new_tokens=7, probe=True).result(300)
    finally:
        eng.stop()
    assert prompt_len + 7 > TOPK
    want = onp.asarray(_reference(net, prompt + got["tokens"][:-1])["logits"])
    assert len(got["probe"]) == 7
    for j, seen in enumerate(got["probe"]):
        assert onp.abs(seen["logits"] - want[prompt_len - 1 + j]).max() < 5e-5
        assert int(seen["logits"].argmax()) == got["tokens"][j]


def test_engine_churns_more_requests_than_slots_through_three_rings():
    net = _net(seed=11)
    eng = GenerationEngine(net, slots=3, max_len=32, prefill_buckets=(8, 16),
                           cache=None)
    try:
        assert [k for k, _s, _d in eng._ring_specs[:3]] == ["k", "v",
                                                            "indexer"]
        assert [s for _k, s, _d in eng._ring_specs[:3]] == [
            (3, 32, 32), (3, 32, 32), (3, 32, 128)]
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
        assert g["kv_cache_bytes_k"] == g["kv_cache_bytes_v"] \
            == 3 * 3 * 32 * 32 * 4
        assert g["kv_cache_bytes_indexer"] == 3 * 3 * 32 * 128 * 4
        assert g["kv_cache_bytes"] == eng.kv_cache_bytes \
            == 2 * g["kv_cache_bytes_k"] + g["kv_cache_bytes_indexer"]
        # the step's counts came back with its tokens
        assert c["routed_pairs"] == 3 * 4 * c["tokens_generated"]
        assert 0 < c["experts_touched"] <= 3 * 16 * c["decode_steps"]
        assert c["decode_steps"] <= c["expert_load_max"] \
            <= 3 * c["decode_steps"]
        assert c["index_valid_positions"] == c["attn_valid_positions"] \
            > 3 * c["tokens_generated"]
        assert 0 < c["index_selected_positions"] <= c["index_valid_positions"]
        assert c["kv_rows_read"] == 3 * 32 * c["tokens_generated"]
        snap = mx.telemetry.snapshot()
        assert snap["counters"]["generate/kv_rows_read"] >= c["kv_rows_read"]
        assert snap["gauges"]["generate/kv_cache_bytes_indexer"] \
            >= g["kv_cache_bytes_indexer"]
    finally:
        eng.stop()


def test_a_reused_slot_shows_nothing_of_the_request_before_it():
    """One slot: a long request leaves its rows behind in all three rings,
    the next one's logits and selections are those of an engine that never
    saw it."""
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
        assert (a["positions"][0] == b["positions"][0]).all()
        assert (a["index_scores"][0] == b["index_scores"][0]).all()


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
            assert onp.abs(seen["logits"] - full[P - 1 + j]).max() < 5e-5
        first, step = got["probe"][0], got["probe"][-1]
        for layer in range(3):
            chosen = onp.asarray(sel["positions"][layer][0])
            scores = onp.asarray(sel["index_scores"][layer][0])
            # the prefill's over its bucket of 16, a decode step's own row
            assert first["positions"][layer].shape == (1, 16, TOPK)
            assert (onp.sort(first["positions"][layer][0, :P], -1)
                    == onp.sort(chosen[:P], -1)).all()
            assert (onp.sort(step["positions"][layer])
                    == onp.sort(chosen[-1])).all()
            assert onp.abs(onp.sort(step["index_scores"][layer])
                           - onp.sort(scores[-1])).max() < 1e-5
            want = onp.sort(onp.asarray(sel["experts"][layer]), -1)
            assert first["experts"][layer].shape == (16, 4)
            assert (onp.sort(first["experts"][layer][:P], -1)
                    == want[:P]).all()
            assert (onp.sort(step["experts"][layer]) == want[-1]).all()
            assert onp.abs(step["router_scores"][layer] - onp.asarray(
                sel["router_scores"][layer])[-1]).max() < 1e-6
        for s in others:
            assert len(s.result(300)["tokens"]) == 20
    finally:
        eng.stop()


# -- the benchmark's check ----------------------------------------------------
@pytest.mark.parametrize("kv_dtype,agrees", [("float32", True),
                                             ("float8_e4m3fn", False)])
def test_the_benchmarks_probed_check_holds_the_three_rings_to_their_type(
        kv_dtype, agrees):
    """``chipbench/jobs/serve_keye.py``'s probed request, at the
    rehearsal's sizes: through the engine's programs it agrees with the
    reference as stated, and rings stored in fewer bits come out wrong by
    ``within``, the function that decides ``correct``."""
    from chipbench import common
    from chipbench.jobs import serve_keye as job
    from chipbench.run import merge
    cfg = common.load("configs", "keye_vl2_30b_a3b_serve")
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
    found = [job.probed_path(net, net.raw_weights(), job.model_config(cfg),
                             *one) for one in probed]
    assert [job.within(f, dict(limits, logits_tolerance=f["logits_tolerance"]))
            for f in found] == [agrees] * len(cfg["check"]["probed"])
    assert all(f["selected_count_wrong"] == 0 for f in found)


def test_required_keye_reckons_the_configurations_bytes():
    """The yardstick's shapes against the issue's table, and a step's
    bytes against hand arithmetic."""
    from chipbench import common, required_keye
    from chipbench.jobs import serve_keye as job
    shape = job.shape_of(common.load("configs", "keye_vl2_30b_a3b_serve"))
    assert required_keye.expert_params(shape) == 3 * 2048 * 768
    assert required_keye.attention_params(shape) == 18_874_368 + 2_260_992
    assert round(required_keye.weight_params(shape) / 1e9, 3) == 3.124
    moe = 4 * 128 * 3 * 2048 * 768
    outside = required_keye.weight_params(shape) - moe - 151936 * 2048
    got = required_keye.decode_step_bytes(shape, 470, 1000, 300)
    assert got == 2 * (outside + 470 * 3 * 2048 * 768) \
        + 2 * (1000 * 64 + 300 * 2 * 512)
    flops = required_keye.decode_step_flops(shape, 40, 320, 1000, 300)
    assert flops == 2 * (40 * outside + 320 * 3 * 2048 * 768
                         + 1000 * 16 * 64 + 300 * 2 * 32 * 128)
