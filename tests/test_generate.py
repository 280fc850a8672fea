"""Generative serving: KV-cached incremental decode + continuous batching.

Covers the ``mxnet_tpu.serving.generate`` subsystem end to end (all CPU):

* prefill + ring-buffer decode vs a full re-forward — exact greedy-token
  parity across prompt lengths (incl. the valid_length < bucket edges);
* continuous batching: slot churn never recompiles (one prefill program
  per bucket + ONE fixed-shape decode program, distinct cache labels);
* slot reuse after free, cache wraparound (sliding-window semantics),
  EOS / length completion, streaming order;
* the ``generate.decode`` chaos lever (docs/RESILIENCE.md) — transient
  faults retry in place, a permanent fault fails one request honestly;
* beam_search_translate's incremental path vs the legacy full-prefix
  referee;
* the autoscaler's ``generate/free_kv_slots`` leg, the HTTP ``/generate``
  endpoint (streaming + non-streaming), and the router's
  prefill-only-re-route / typed-mid-stream-break policy.
"""
import json
import socket
import struct
import threading
import time
import urllib.request

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import serving
from mxnet_tpu import telemetry
from mxnet_tpu import faults
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving.generate import GenerationEngine


# -- shared tiny LM ---------------------------------------------------------

def _lm(vocab=64, layers=2, units=32, heads=2, max_length=256, seed=7):
    from mxnet_tpu.models.lm import tiny_lm
    mx.random.seed(seed)
    net = tiny_lm(vocab_size=vocab, num_layers=layers, units=units,
                  hidden_size=2 * units, num_heads=heads,
                  max_length=max_length)
    net.initialize()
    net(nd.array(onp.zeros((1, 4), onp.int32)),
        nd.array(onp.asarray([4], onp.int32)))       # materialize params
    return net


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _full_forward_greedy(net, prompt, n_new, eos_id=None):
    """Parity referee: re-run the FULL forward per emitted token."""
    toks = list(int(t) for t in prompt)
    out = []
    for _ in range(n_new):
        x = nd.array(onp.asarray([toks], onp.int32))
        vl = nd.array(onp.asarray([len(toks)], onp.int32))
        logits = net(x, vl).asnumpy()
        t = int(logits[0, len(toks) - 1].argmax())
        out.append(t)
        toks.append(t)
        if eos_id is not None and t == eos_id:
            break
    return out


def _engine(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    return GenerationEngine(lm, **kw)


# -- decode parity ----------------------------------------------------------

def test_incremental_decode_matches_full_forward(lm):
    # prompt lengths hit the valid_length edges: 1 (minimum), mid-bucket,
    # and exactly the bucket boundary (no padding at all)
    eng = _engine(lm)
    try:
        for plen in (1, 5, 8, 11, 16):
            prompt = [(3 * i + 1) % 60 for i in range(plen)]
            ref = _full_forward_greedy(lm, prompt, 6)
            got = eng.generate(prompt, max_new_tokens=6, timeout=120)
            assert got["tokens"] == ref, (plen, got["tokens"], ref)
            assert got["finish_reason"] == "length"
            assert got["ttft_ms"] >= 0.0 and got["tokens_per_s"] > 0.0
    finally:
        eng.stop()


def test_concurrent_churn_compiles_once_and_keeps_parity(lm):
    # 7 concurrent requests over 4 slots: requests join/leave the decode
    # batch at token boundaries, slots get reused, and through ALL the
    # churn exactly one prefill program (per bucket) + one decode
    # program exist — the continuous-batching acceptance claim
    eng = _engine(lm)
    try:
        prompts = [[(5 * i + j) % 60 for j in range(3 + i)]
                   for i in range(7)]
        lens = [4, 6, 8, 3, 5, 7, 6]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, lens)]
        for p, n, s in zip(prompts, lens, streams):
            got = s.result(timeout=120)
            assert got["tokens"] == _full_forward_greedy(lm, p, n)
        labels = eng.program_labels()
        assert labels == {"prefill:L8": "generate:prefill:L8",
                          "prefill:L16": "generate:prefill:L16",
                          "decode": "generate:decode"}
        c = eng.metrics.stats()["counters"]
        # one prefill entry PER BUCKET + one decode entry (all traced at
        # construction), compile or warm load — NEVER one per request
        assert c["prefill_compiles"] + c["prefill_cache_hits"] == 2
        assert c["decode_compiles"] + c["decode_cache_hits"] == 1
        assert c["slot_allocs"] == 7 and c["slot_frees"] == 7
        # every dispatch consumed the rings it was given (they are updated
        # in place) and none was lost
        assert c["prefills"] == 7 and c["decode_steps"] > 0
        assert c["kv_inplace_dispatches"] == c["decode_steps"] + c["prefills"]
        assert c["kv_ring_rebuilds"] == 0
    finally:
        eng.stop()


def test_slot_reuse_after_free_stays_clean(lm):
    # one slot, sequential generations: the second rides the SAME slot
    # the first freed — stale cache contents must not leak across
    eng = _engine(lm, slots=1)
    try:
        a = eng.generate([9, 2, 7], max_new_tokens=5, timeout=120)
        b = eng.generate([4, 4, 1, 8], max_new_tokens=5, timeout=120)
        assert a["tokens"] == _full_forward_greedy(lm, [9, 2, 7], 5)
        assert b["tokens"] == _full_forward_greedy(lm, [4, 4, 1, 8], 5)
        c = eng.metrics.stats()["counters"]
        assert c["slot_allocs"] == 2 and c["slot_frees"] == 2
    finally:
        eng.stop()


def test_cache_wraparound_is_a_sliding_window():
    # 1-layer model: each cached K/V row depends only on (token,
    # position), so once the ring evicts position 0 two teacher-forced
    # sequences differing ONLY in token 0 must produce identical logits
    # — the window truly slid.  Before eviction they must differ (the
    # test has teeth).
    from mxnet_tpu.ndarray.ndarray import NDArray
    net = _lm(layers=1, units=16, heads=2, max_length=64, seed=11)
    M, steps = 4, 9
    H, D = 2, 8

    def run(first_tok):
        seq = [first_tok] + [(7 * j + 3) % 50 for j in range(1, steps)]
        caches = [(NDArray(onp.zeros((1, H, M, D), onp.float32)),
                   NDArray(onp.zeros((1, H, M, D), onp.float32)))
                  for _ in range(net.num_layers)]
        outs = []
        for p, t in enumerate(seq):
            logits, caches = net.decode_step(
                nd.array(onp.asarray([t], onp.int32)), caches,
                nd.array(onp.asarray([p], onp.int32)))
            outs.append(logits.asnumpy()[0])
        return outs
    a, b = run(5), run(41)
    assert not onp.allclose(a[0], b[0])       # differing token 0 matters...
    assert not onp.allclose(a[M - 1], b[M - 1])
    for p in range(M, steps):                 # ...until the ring evicts it
        onp.testing.assert_allclose(a[p], b[p], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("active", [None, "ones", "mixed", "zeros"])
def test_decode_step_writes_one_row_a_sequence(active):
    # the ring-write contract, whatever writes it: a writing sequence gets
    # its new key and value at position % M (past M: the ring wraps) and
    # no other entry of any ring moves; a gated sequence changes nothing
    from mxnet_tpu.models.bert import MultiHeadAttention
    from mxnet_tpu.ndarray.ndarray import NDArray
    mx.random.seed(13)
    B, H, M, D = 5, 2, 6, 4
    att = MultiHeadAttention(H * D, H, causal=True)
    att.initialize()
    rng = onp.random.RandomState(3)
    x = rng.randn(B, 1, H * D).astype("float32")
    k0 = rng.randn(B, H, M, D).astype("float32")
    v0 = rng.randn(B, H, M, D).astype("float32")
    pos = onp.asarray([0, 5, 6, 3, 2 * M + 1], onp.int32)  # 6, 13: wrapped
    gate = {None: None, "ones": onp.ones(B, "float32"),
            "mixed": onp.asarray([1, 0, 1, 0, 1], "float32"),
            "zeros": onp.zeros(B, "float32")}[active]
    _out, k1, v1 = att.decode_step(
        NDArray(x), NDArray(k0), NDArray(v0), NDArray(pos),
        active=None if gate is None else NDArray(gate))
    qkv = att.qkv(NDArray(x)).asnumpy().reshape(B, 3, H, D)
    want_k, want_v = k0.copy(), v0.copy()
    for b in range(B):
        if gate is None or gate[b] > 0:
            want_k[b, :, pos[b] % M] = qkv[b, 1]
            want_v[b, :, pos[b] % M] = qkv[b, 2]
    onp.testing.assert_array_equal(k1.asnumpy(), want_k)
    onp.testing.assert_array_equal(v1.asnumpy(), want_v)


def test_engine_wraparound_counts_and_stays_deterministic(lm):
    eng = _engine(lm, max_len=8, prefill_buckets=(8,))
    try:
        r1 = eng.generate([2, 9, 4], max_new_tokens=16, timeout=120)
        r2 = eng.generate([2, 9, 4], max_new_tokens=16, timeout=120)
        assert r1["tokens"] == r2["tokens"] and len(r1["tokens"]) == 16
        c = eng.metrics.stats()["counters"]
        assert c["cache_wraps"] == 2          # both rode past max_len=8
    finally:
        eng.stop()


@pytest.mark.slow
def test_long_sequence_parity(lm):
    # deep decode chain (100 steps, no wrap): parity must hold the whole
    # way — position handling, ring writes and the fp32 softmax don't
    # drift over a long generation
    eng = _engine(lm, max_len=256, prefill_buckets=(32,))
    try:
        prompt = [(11 * i + 2) % 60 for i in range(20)]
        got = eng.generate(prompt, max_new_tokens=100, timeout=600)
        assert got["tokens"] == _full_forward_greedy(lm, prompt, 100)
    finally:
        eng.stop()


# -- completion + streaming -------------------------------------------------

def test_eos_completion(lm):
    prompt = [7, 3, 5]
    ref = _full_forward_greedy(lm, prompt, 8)
    eos = ref[3]
    # generation stops at the FIRST eos — which the random weights may
    # emit before index 3 (they do under jax 0.9.0's initial draws)
    want = ref[:ref.index(eos) + 1]
    eng = _engine(lm)
    try:
        got = eng.generate(prompt, max_new_tokens=8, eos_id=eos,
                           timeout=120)
        assert got["finish_reason"] == "eos"
        assert got["tokens"] == want
    finally:
        eng.stop()


def test_streaming_tokens_arrive_in_order(lm):
    eng = _engine(lm)
    try:
        stream = eng.submit([1, 2, 3], max_new_tokens=6)
        seen = [t for t in stream.tokens(timeout=120)]
        res = stream.result(timeout=5)
        assert seen == res["tokens"] == _full_forward_greedy(lm, [1, 2, 3], 6)
        assert stream.done
    finally:
        eng.stop()


def test_admission_rejects_and_closed_engine(lm):
    eng = _engine(lm, slots=1, max_queue=1)
    try:
        with pytest.raises(serving.ServingError):
            eng.submit(list(range(40)))       # above the top bucket (16)
        s1 = eng.submit([5, 6], max_new_tokens=60)
        next(iter(s1.tokens(timeout=120)))    # s1 holds the only slot
        s2 = eng.submit([7, 8], max_new_tokens=3)     # fills the queue
        with pytest.raises(serving.QueueFullError):
            eng.submit([9, 1], max_new_tokens=3)
        assert eng.metrics.stats()["counters"]["rejected_queue_full"] == 1
        assert len(s1.result(timeout=240)["tokens"]) == 60
        assert s2.result(timeout=240)["tokens"] == \
            _full_forward_greedy(lm, [7, 8], 3)
    finally:
        eng.stop()
    with pytest.raises(serving.EngineClosedError):
        eng.submit([1, 2])


def test_kv_budget_enforced(lm, monkeypatch):
    monkeypatch.setenv("MXNET_KV_BUDGET_BYTES", "1024")
    with pytest.raises(serving.ServingError, match="KV cache needs"):
        _engine(lm)


# -- chaos: the generate.decode fault point ---------------------------------

def test_generate_decode_transient_fault_retries_in_place(lm):
    eng = _engine(lm)
    try:
        ref = _full_forward_greedy(lm, [3, 1, 4], 5)
        with faults.inject("generate.decode@1:transient"):
            got = eng.generate([3, 1, 4], max_new_tokens=5, timeout=120)
        assert got["tokens"] == ref           # retried, nothing lost
        assert eng.metrics.stats()["counters"]["dispatch_retries"] >= 1
    finally:
        eng.stop()


def test_generate_decode_permanent_fault_fails_one_request(lm):
    eng = _engine(lm)
    try:
        with faults.inject("generate.decode@1:permanent"):
            stream = eng.submit([3, 1, 4], max_new_tokens=5)
            with pytest.raises(Exception):
                stream.result(timeout=120)
        assert eng.metrics.stats()["counters"]["errors"] == 1
        # the engine keeps serving after failing that one request
        got = eng.generate([3, 1, 4], max_new_tokens=3, timeout=120)
        assert got["tokens"] == _full_forward_greedy(lm, [3, 1, 4], 3)
    finally:
        eng.stop()


# -- the rings are donated: updated in place, lost only with a failure ------

def _ring_buffers(eng):
    import jax
    return [a for a in jax.live_arrays()
            if a.shape == eng._ring_specs[0][1] and not a.is_deleted()]


def test_rings_are_consumed_and_no_second_copy_is_kept():
    # a model of its own: the module's engines must not share this shape
    net = _lm(units=48, heads=3, seed=5)
    eng = _engine(net, slots=3, max_len=32, prefill_buckets=(8,))
    try:
        n = 2 * net.num_layers
        before = list(eng._cache_flat)
        assert len(_ring_buffers(eng)) == n
        got = eng.generate([3, 1, 4, 1], max_new_tokens=5, timeout=120)
        assert got["tokens"] == _full_forward_greedy(net, [3, 1, 4, 1], 5)
        assert all(r.is_deleted() for r in before)
        assert all(not r.is_deleted() for r in eng._cache_flat)
        assert len(_ring_buffers(eng)) == n
    finally:
        eng.stop()


@pytest.mark.parametrize("where", ["decode", "prefill"])
def test_failure_that_consumes_the_rings_fails_riders_and_rebuilds(lm, where):
    # a program that dies after it has taken its donated inputs: the keys
    # and values of every slot are gone, so every rider fails, the engine
    # allocates fresh rings and the next request is served correctly
    eng = _engine(lm)
    try:
        rider = eng.submit([5, 6, 7], max_new_tokens=40)
        next(iter(rider.tokens(timeout=120)))         # rider holds a slot
        real = eng._decode_prog if where == "decode" \
            else eng._prefill_progs[8]

        def dies(raws, *args):
            for ring in args[3:]:
                ring.delete()
            raise RuntimeError("device fell over mid-program")

        if where == "decode":
            eng._decode_prog = (dies, real[1])
        else:
            eng._prefill_progs[8] = (dies, real[1])
            victim = eng.submit([1, 2], max_new_tokens=3)
            with pytest.raises(RuntimeError, match="fell over"):
                victim.result(timeout=120)
        with pytest.raises(RuntimeError, match="fell over"):
            rider.result(timeout=120)
        if where == "decode":
            eng._decode_prog = real
        else:
            eng._prefill_progs[8] = real
        c = eng.metrics.stats()["counters"]
        assert c["kv_ring_rebuilds"] == 1
        assert c["errors"] == (1 if where == "decode" else 2)
        assert c["slot_allocs"] == c["slot_frees"]
        assert all(not r.is_deleted() for r in eng._cache_flat)
        got = eng.generate([3, 1, 4], max_new_tokens=4, timeout=120)
        assert got["tokens"] == _full_forward_greedy(lm, [3, 1, 4], 4)
        assert eng.metrics.stats()["counters"]["kv_ring_rebuilds"] == 1
    finally:
        eng.stop()


def test_failure_before_the_call_keeps_the_rings(lm):
    # the permanent injected fault fires before the program runs: the
    # rider fails, the rings live on and nothing is rebuilt
    eng = _engine(lm)
    try:
        before = list(eng._cache_flat)
        with faults.inject("generate.decode@1:permanent"):
            with pytest.raises(Exception):
                eng.generate([3, 1, 4], max_new_tokens=5, timeout=120)
        c = eng.metrics.stats()["counters"]
        assert c["kv_ring_rebuilds"] == 0
        # the prefill consumed the first rings; its outputs are the ones
        # the failed step left alone
        assert all(r.is_deleted() for r in before)
        assert all(not r.is_deleted() for r in eng._cache_flat)
    finally:
        eng.stop()


# -- one decode step in flight -----------------------------------------------

def _drains(kind="generate"):
    """Loop steps in the flight recorder that read and dispatched nothing."""
    steps = {}
    for s in telemetry.flight_recorder():
        if s["kind"] == kind:
            steps.setdefault(s["step"], set()).add(s["phase"])
    return sum("readback" in ph and "stage" not in ph
               for ph in steps.values())


def test_pipelined_loop_keeps_parity_through_churn(lm):
    # the prompt lengths of test_incremental_decode_matches_full_forward,
    # nine requests over three slots, outputs from one token (the
    # prefill's alone) up: joins, leaves and refills all land while a
    # decode step is in flight, and every token is the reference's
    eng = _engine(lm, slots=3)
    try:
        plens = (1, 5, 8, 11, 16, 3, 9, 2, 14)
        lens = (6, 1, 9, 2, 7, 12, 3, 5, 8)
        prompts = [[(3 * i + 1 + k) % 60 for i in range(n)]
                   for k, n in enumerate(plens)]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, lens)]
        for p, n, s in zip(prompts, lens, streams):
            got = s.result(timeout=120)
            assert got["tokens"] == _full_forward_greedy(lm, p, n), (p, n)
            assert got["finish_reason"] == "length"
        eng.stop()      # the loop counts a step's tokens after it emits them
        c = eng.metrics.stats()["counters"]
        assert c["tokens_generated"] == sum(lens) - len(lens)
        assert c["slot_steps_discarded"] == 0
        assert 0 < c["decode_steps_overlapped"] < c["decode_steps"]
        assert c["kv_inplace_dispatches"] == c["decode_steps"] + c["prefills"]
        assert eng._unread == [] and len(eng._free) == 3
    finally:
        eng.stop()


def test_eos_mid_batch_is_found_a_step_late_and_the_slot_serves_on(lm):
    prompt, other, nxt = [7, 3, 5], [2, 9, 4, 1], [6, 6, 2]
    ref = _full_forward_greedy(lm, prompt, 12)
    eos = ref[3]
    want = ref[:ref.index(eos) + 1]
    assert len(want) < 11       # its next step is dispatched by then
    eng = _engine(lm, slots=2)
    try:
        long = eng.submit(other, max_new_tokens=30)
        next(iter(long.tokens(timeout=120)))         # rides beside it
        got = eng.submit(prompt, max_new_tokens=12,
                         eos_id=eos).result(timeout=120)
        assert got["finish_reason"] == "eos" and got["tokens"] == want
        # the slot it left, with the dead row in it, serves the next
        again = eng.generate(nxt, max_new_tokens=9, timeout=120)
        assert again["tokens"] == _full_forward_greedy(lm, nxt, 9)
        assert long.result(timeout=120)["tokens"] == \
            _full_forward_greedy(lm, other, 30)
        # the step dispatched before the eos was read rode for nothing:
        # counted when that step is read, one loop step after the result
        eng.stop()
        c = eng.metrics.stats()["counters"]
        assert c["slot_steps_discarded"] == 1
        assert c["slot_allocs"] == c["slot_frees"] == 3
        # a discarded token is not a generated one
        assert c["tokens_generated"] == len(want) - 1 + 8 + 29
    finally:
        eng.stop()


def test_a_lone_request_gets_every_token_without_another_arrival(lm):
    # nothing else arrives: the last step's tokens are drained, never held
    # back for a next dispatch to read them
    eng = _engine(lm)
    try:
        stream = eng.submit([1, 2, 3], max_new_tokens=5)
        seen = [t for t in stream.tokens(timeout=30)]
        assert seen == _full_forward_greedy(lm, [1, 2, 3], 5)
        assert stream.done and stream.result(0)["finish_reason"] == "length"
        eng.stop()
        assert eng._unread == [] and len(eng._free) == eng.slots
        c = eng.metrics.stats()["counters"]
        assert c["decode_steps"] == 4 and c["decode_steps_overlapped"] == 3
    finally:
        eng.stop()


def test_every_step_but_the_first_after_a_drain_is_overlapped(lm):
    # two slots always taken: four requests of one length, so both leave
    # at once, the loop drains, and the next two start it again
    eng = _engine(lm, slots=2)
    telemetry.reset()
    try:
        prompts = [[(7 * i + j) % 60 for j in range(4)] for i in range(4)]
        first = [eng.submit(p, max_new_tokens=10) for p in prompts[:2]]
        for s in first:
            next(iter(s.tokens(timeout=120)))
        rest = [eng.submit(p, max_new_tokens=10) for p in prompts[2:]]
        for p, s in zip(prompts, first + rest):
            assert s.result(timeout=120)["tokens"] == \
                _full_forward_greedy(lm, p, 10)
        eng.stop()
        c = eng.metrics.stats()["counters"]
        drains = _drains()
        assert drains >= 1 and c["decode_steps"] >= 18
        assert c["decode_steps_overlapped"] == c["decode_steps"] - drains
        assert c["tokens_generated"] == 4 * 9
    finally:
        eng.stop()
        telemetry.reset()


class _Unreadable:
    """A program's tokens that fail when the host reads them."""

    def __array__(self, *a, **kw):
        raise RuntimeError("device fell over mid-step")


@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_failure_with_a_step_in_flight_fails_riders_and_serves_on(lm, where):
    # both riders have a decode step dispatched and unread when the next
    # dispatch fails before its call (the injected fault: the rings live
    # on), or when a step's tokens cannot be read (what the engine holds
    # then descends from the failed program: rebuilt)
    eng = _engine(lm, slots=2)
    try:
        riders = [eng.submit([5, 6, 7], max_new_tokens=40),
                  eng.submit([8, 1], max_new_tokens=40)]
        for r in riders:
            next(iter(r.tokens(timeout=120)))
        if where == "dispatch":
            with faults.inject("generate.decode@1:permanent"):
                for r in riders:
                    with pytest.raises(faults.PermanentFault):
                        r.result(timeout=120)
        else:
            real = eng._decode_prog

            def unreadable(*args):
                out = real[0](*args)
                return (_Unreadable(),) + tuple(out[1:])

            eng._decode_prog = (unreadable, real[1])
            for r in riders:
                with pytest.raises(RuntimeError, match="fell over"):
                    r.result(timeout=120)
            eng._decode_prog = real
        c = eng.metrics.stats()["counters"]
        assert c["errors"] == 2 and c["slot_allocs"] == c["slot_frees"] == 2
        assert c["kv_ring_rebuilds"] == (0 if where == "dispatch" else 1)
        assert eng._unread == []
        assert all(not r.is_deleted() for r in eng._cache_flat)
        assert not eng._last_tok.is_deleted()
        got = eng.generate([3, 1, 4], max_new_tokens=6, timeout=120)
        assert got["tokens"] == _full_forward_greedy(lm, [3, 1, 4], 6)
        c = eng.metrics.stats()["counters"]
        assert c["kv_ring_rebuilds"] == (0 if where == "dispatch" else 1)
    finally:
        eng.stop()


# -- beam search: incremental vs legacy referee -----------------------------

@pytest.mark.slow
def test_beam_search_incremental_matches_legacy_referee():
    from mxnet_tpu.models import Transformer
    from mxnet_tpu.models.transformer import beam_search_translate
    mx.random.seed(3)
    V, L = 17, 6
    net = Transformer(src_vocab_size=V, tgt_vocab_size=V, num_layers=1,
                      units=16, hidden_size=32, num_heads=2,
                      max_length=2 * L, dropout=0.0)
    net.initialize()
    rng = onp.random.RandomState(0)
    src = nd.array(rng.randint(2, V, (3, L)).astype("int32"))
    vl = nd.array(onp.asarray([L, L - 2, L - 1], onp.int32))
    for svl in (None, vl):
        toks_inc, sc_inc = beam_search_translate(
            net, src, src_valid_length=svl, beam_size=2, max_length=L,
            bos=1, eos=0, incremental=True)
        toks_ref, sc_ref = beam_search_translate(
            net, src, src_valid_length=svl, beam_size=2, max_length=L,
            bos=1, eos=0, incremental=False)
        assert (toks_inc.asnumpy() == toks_ref.asnumpy()).all()
        onp.testing.assert_allclose(sc_inc.asnumpy(), sc_ref.asnumpy(),
                                    rtol=2e-5, atol=2e-5)


# -- autoscaler: KV-slot pressure leg ---------------------------------------

class _FakeSup:
    def __init__(self, n=2):
        self.n = n
        self.gauges = {}

    def status(self):
        return {i: {"state": "up"} for i in range(self.n)}

    def federated(self):
        return {"summed": {"counters": {}, "gauges": dict(self.gauges),
                           "histograms": {}}}

    def _list(self):
        return list(range(self.n))

    def add_replica(self, timeout_s=None):
        self.n += 1
        return self.n - 1

    def remove_replica(self, idx):
        self.n -= 1


class _FakeRouter:
    def __init__(self, sup):
        self._sup = sup
        self.outstanding = 0

    def status(self):
        return {"draining": []}

    def drain(self, key, timeout=None):
        pass

    def admit(self, key):
        pass

    def forget(self, key):
        pass


def _kv_autoscaler(sup, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("interval_s", 3600.0)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("queue_high", 10.0)
    kw.setdefault("queue_low", 1.0)
    kw.setdefault("up_ticks", 1)
    kw.setdefault("down_ticks", 1)
    return serving.Autoscaler(sup, _FakeRouter(sup), **kw)


def test_autoscaler_scales_up_on_kv_slot_pressure():
    sup = _FakeSup(n=2)
    auto = _kv_autoscaler(sup, kv_slot_low=2.0, kv_slot_high=6.0)
    # fleet-wide 2 free slots over 2 replicas = 1/replica < low=2: the
    # queue is empty but generations are about to stall on KV capacity
    sup.gauges = {"generate/free_kv_slots": 2.0, "serving/queue_depth": 0.0}
    rec = auto._tick()
    assert rec["action"] == "up" and "free KV slots" in rec["reason"]
    assert sup.n == 3

    # plenty of free slots per replica (> high) + empty queue: calm on
    # BOTH legs, scale-down proceeds
    sup.gauges = {"generate/free_kv_slots": 24.0, "serving/queue_depth": 0.0}
    rec = auto._tick()
    assert rec["action"] == "down"
    assert sup.n == 2

    # in the hysteresis band (low < free/replica < high): quiet queue
    # alone must NOT shrink a fleet whose KV occupancy is still real
    sup.gauges = {"generate/free_kv_slots": 8.0, "serving/queue_depth": 0.0}
    assert auto._tick() is None
    assert sup.n == 2


def test_autoscaler_kv_leg_disabled_when_gauge_absent():
    sup = _FakeSup(n=2)
    auto = _kv_autoscaler(sup, kv_slot_low=2.0, kv_slot_high=6.0)
    # no replica serves /generate: the gauge is absent (None, not 0 —
    # 0 would read as saturation) and the legs must not fire
    sup.gauges = {"serving/queue_depth": 0.0}
    rec = auto._tick()
    assert rec["action"] == "down"            # plain queue underload
    assert sup.n == 1


def test_autoscaler_kv_band_validated():
    sup = _FakeSup(n=2)
    with pytest.raises(MXNetError, match="kv_slot_low"):
        _kv_autoscaler(sup, kv_slot_low=6.0, kv_slot_high=2.0)


# -- HTTP endpoint + router policy ------------------------------------------

def _serving_stack(lm, **gen_kw):
    engine = serving.InferenceEngine(lambda x: (onp.asarray(x) * 2.0,),
                                     batch_buckets=(1, 2))
    batcher = serving.DynamicBatcher(engine, max_batch_size=2,
                                     max_delay_ms=0.5)
    gen = _engine(lm, **gen_kw)
    return serving.ModelServer(batcher, port=0, generator=gen)


def test_http_generate_stream_and_nonstream(lm):
    prompt = [11, 5, 2]
    ref = _full_forward_greedy(lm, prompt, 5)
    with _serving_stack(lm) as srv:
        client = serving.ServingClient(srv.url)
        got = client.generate(prompt, max_new_tokens=5)
        assert got["tokens"] == ref
        assert got["finish_reason"] == "length"
        toks = []
        it = client.generate_stream(prompt, max_new_tokens=5)
        while True:
            try:
                toks.append(next(it))
            except StopIteration as stop:
                final = stop.value
                break
        assert toks == ref and final["tokens"] == ref
        stats = client.stats()
        assert stats["generate"]["counters"]["completed"] == 2


def test_http_generate_404_without_generator():
    engine = serving.InferenceEngine(lambda x: (onp.asarray(x) * 2.0,),
                                     batch_buckets=(1, 2))
    batcher = serving.DynamicBatcher(engine, max_batch_size=2,
                                     max_delay_ms=0.5)
    with serving.ModelServer(batcher, port=0) as srv:
        with pytest.raises(serving.ServingError,
                           match="generation_not_enabled"):
            serving.ServingClient(srv.url).generate([1, 2])


def test_router_reroutes_prefill_but_not_midstream(lm):
    # replica 0 is a dead port: the prefill-side failure (connection
    # refused, nothing consumed) re-routes transparently to replica 1
    prompt = [8, 1, 6]
    ref = _full_forward_greedy(lm, prompt, 4)
    from mxnet_tpu.serving.fleet import _fleet_counters
    with _serving_stack(lm) as srv:
        with serving.Router(["http://127.0.0.1:9/", srv.url]) as router:
            r0 = _fleet_counters["gen_reroutes"]
            got = router.generate(prompt, max_new_tokens=4)
            assert got["tokens"] == ref
            assert _fleet_counters["gen_reroutes"] > r0
            toks = []
            it = router.generate_stream(prompt, max_new_tokens=4)
            while True:
                try:
                    toks.append(next(it))
                except StopIteration as stop:
                    assert stop.value["tokens"] == ref
                    break
            assert toks == ref


def test_router_generate_rejects_bad_midstream_policy(lm):
    with _serving_stack(lm) as srv:
        with serving.Router([srv.url]) as router:
            with pytest.raises(ValueError, match="midstream"):
                router.generate([1, 2], midstream="retry")


# -- one writer for every token stream (serving/stream_writer.py) -----------

def _open_stream(srv, prompt, max_new):
    """A streaming /generate sent on a raw socket: the reply is read (or
    not) by the test, byte for byte."""
    sock = socket.create_connection((srv.host, srv.port), timeout=120)
    body = json.dumps({"tokens": prompt, "max_new_tokens": max_new,
                       "stream": True}).encode()
    sock.sendall(b"POST /generate HTTP/1.1\r\nHost: test\r\n"
                 b"Content-Type: application/json\r\n"
                 b"Content-Length: %d\r\n\r\n" % len(body) + body)
    return sock


def _read_to_close(sock):
    """The body of a close-delimited reply, split into its lines."""
    data = b""
    while True:
        chunk = sock.recv(65536)
        if not chunk:
            break
        data += chunk
    sock.close()
    head, _, body = data.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200"), head
    assert b"Connection: close" in head
    assert body.endswith(b"\n")
    return body[:-1].split(b"\n")


def _token_lines(tokens):
    """What the per-token handler loop wrote for these tokens."""
    return [json.dumps({"token": int(t), "index": i}).encode()
            for i, t in enumerate(tokens)]


def _in_threads(fn, args_list, timeout=180):
    """``fn(*args)`` for every args at once, a thread each; the results
    (or the exception raised) in order."""
    out = [None] * len(args_list)

    def run(k, args):
        try:
            out[k] = fn(*args)
        except Exception as e:          # noqa: BLE001 — the test reads it
            out[k] = e
    threads = [threading.Thread(target=run, args=(k, a))
               for k, a in enumerate(args_list)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads)
    return out


def _drain_stream(it):
    """A client's streamed tokens and what ended them: the final record,
    or the exception."""
    seen = []
    try:
        while True:
            seen.append(next(it))
    except StopIteration as stop:
        return seen, stop.value
    except Exception as e:              # noqa: BLE001 — the test reads it
        return seen, e


def test_sixteen_streams_read_the_bytes_the_handler_loop_wrote(lm):
    # four slots, sixteen clients: streams join and leave the batch while
    # one thread writes them all
    cases = [([1 + k, 7, 3 + (k % 5)][:1 + k % 3], 3 + (5 * k) % 11)
             for k in range(16)]
    with _serving_stack(lm) as srv:
        client = serving.ServingClient(srv.url)
        plain = [client.generate(p, max_new_tokens=n)["tokens"]
                 for p, n in cases]
        got = _in_threads(
            lambda p, n: _read_to_close(_open_stream(srv, p, n)), cases)
    for (prompt, n), ref, lines in zip(cases, plain, got):
        assert not isinstance(lines, Exception), lines
        assert len(ref) == n
        # indices 0..n-1 in order, then the final line, and nothing else
        assert lines[:-1] == _token_lines(ref)
        final = json.loads(lines[-1])
        assert final["done"] is True and final["tokens"] == ref
        assert final["finish_reason"] == "length"


def test_a_client_that_reads_nothing_delays_nobody(lm):
    ref = _full_forward_greedy(lm, [5, 9], 40)
    with _serving_stack(lm) as srv:
        stalled = _open_stream(srv, [5, 9], 40)      # sent, never read
        try:
            client = serving.ServingClient(srv.url, timeout_s=60)
            others = _in_threads(
                lambda k: _drain_stream(client.generate_stream(
                    [2 + k, 4], max_new_tokens=20)),
                [(k,) for k in range(8)])
            for seen, final in others:
                assert len(seen) == 20 and final["tokens"] == seen
            # its generation went on without it: every line is there
            lines = _read_to_close(stalled)
        finally:
            stalled.close()
    assert lines[:-1] == _token_lines(ref)
    assert json.loads(lines[-1])["tokens"] == ref


def test_a_socket_that_takes_no_bytes_keeps_its_remainder_in_order():
    # more than a socket's buffers hold: the writer keeps what is left for
    # that stream and goes on with the other one
    from mxnet_tpu.serving.generate import GenerationMetrics, \
        GenerationStream
    from mxnet_tpu.serving.stream_writer import StreamWriter
    metrics = GenerationMetrics()
    writer = StreamWriter(metrics).start()
    slow_srv, slow_cli = socket.socketpair()
    fast_srv, fast_cli = socket.socketpair()
    # a socket pair holds a few hundred small sends, whatever their bytes
    n, per = 6000, 300
    try:
        slow, fast = GenerationStream(sink=writer), \
            GenerationStream(sink=writer)
        w_slow = writer.attach(slow, slow_srv)
        w_fast = writer.attach(fast, fast_srv)
        now = time.perf_counter_ns()
        for k in range(0, n, per):
            writer.take([(slow, t, now) for t in range(k, k + per)]
                        + [(fast, k // per, now)])
        writer.take([(slow, None, now), (fast, None, now)])
        assert w_fast.released.wait(60) and w_fast.outcome == "done"
        assert not w_slow.released.is_set()     # most of it is still owed
        fast_cli.settimeout(60)
        want = b"".join(ln + b"\n" for ln in _token_lines(range(n // per)))
        got = b""
        while len(got) < len(want):
            got += fast_cli.recv(65536)
        assert got == want
        slow_cli.settimeout(60)
        want = b"".join(ln + b"\n" for ln in _token_lines(range(n)))
        got = b""
        while len(got) < len(want):
            got += slow_cli.recv(1 << 20)
        assert got == want
        assert w_slow.released.wait(60) and w_slow.outcome == "done"
        c = metrics.stats()["counters"]
        assert c["stream_tokens_written"] == n + n // per
        assert c["stream_writer_wakes"] <= n // per + 1
    finally:
        writer.close()
        for s in (slow_srv, slow_cli, fast_srv, fast_cli):
            s.close()
    assert not any(t.name == "mxnet-tpu-stream-writer"
                   for t in threading.enumerate())


def test_a_failure_mid_stream_ends_every_attached_stream_typed(lm):
    cases = [([3 + k, 1, 4], 12) for k in range(3)]
    refs = [_full_forward_greedy(lm, p, n) for p, n in cases]
    with _serving_stack(lm) as srv:
        client = serving.ServingClient(srv.url, timeout_s=60)
        with faults.inject("generate.decode@4:permanent"):
            got = _in_threads(
                lambda p, n: _drain_stream(
                    client.generate_stream(p, max_new_tokens=n)), cases)
        broken = 0
        for (seen, end), ref in zip(got, refs):
            if isinstance(end, serving.GenerationStreamBroken):
                # the typed line came after the tokens already sent
                broken += 1
                assert end.tokens == seen and 0 < len(seen) < 12
            else:       # admitted after the failed step
                assert end["tokens"] == seen and len(seen) == 12
            assert seen == ref[:len(seen)]
        assert broken >= 1
        assert client.generate([3, 1, 4], max_new_tokens=3)["tokens"] \
            == refs[0][:3]


def test_a_client_that_hangs_up_costs_one_stream(lm):
    with _serving_stack(lm) as srv:
        gen = srv.generator
        sock = _open_stream(srv, [6, 2], 50)
        data = b""
        while b'"index": 0}' not in data:
            data += sock.recv(4096)
        # a reset, not a polite close: the next send to it fails
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        sock.close()
        client = serving.ServingClient(srv.url, timeout_s=60)
        seen, final = _drain_stream(
            client.generate_stream([6, 2], max_new_tokens=8))
        assert final["tokens"] == seen == _full_forward_greedy(
            lm, [6, 2], 8)
        # the engine finished the abandoned generation on its own
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                gen.metrics.stats()["counters"]["completed"] < 2:
            time.sleep(0.01)
        c = gen.metrics.stats()["counters"]
        assert c["completed"] == 2 and c["errors"] == 0
        assert 8 < c["stream_tokens_written"] < 58


def test_the_writer_counts_what_the_clients_read(lm):
    cases = [([4 + k, 8], 6 + k) for k in range(4)]
    with _serving_stack(lm) as srv:
        before = srv.generator.metrics.stats()["counters"]
        client = serving.ServingClient(srv.url, timeout_s=60)
        got = _in_threads(
            lambda p, n: _drain_stream(
                client.generate_stream(p, max_new_tokens=n)), cases)
        # a final line is written only once the counters hold its stream
        after = srv.generator.metrics.stats()["counters"]
    d = {k: after[k] - before[k] for k in after}
    assert all(isinstance(end, dict) for _seen, end in got)
    assert d["stream_tokens_written"] == sum(len(seen) for seen, _ in got) \
        == sum(n for _p, n in cases)
    # at most one wake a program read: a batch a step, never one a token
    assert 0 < d["stream_writer_wakes"] <= d["prefills"] + d["decode_steps"]
    assert d["emit_to_wire_us"] >= d["stream_write_us"] > 0


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_stop_joins_the_writer_and_a_dead_writer_fails_typed(lm):
    def writers():
        return [t for t in threading.enumerate()
                if t.name == "mxnet-tpu-stream-writer"]

    srv = _serving_stack(lm)
    assert not writers()            # none before start()
    srv.start()
    try:
        assert len(writers()) == 1
        client = serving.ServingClient(srv.url, timeout_s=60)
        seen, final = _drain_stream(
            client.generate_stream([1, 2], max_new_tokens=4))
        assert final["tokens"] == seen
    finally:
        srv.stop()
    assert not writers()            # joined, not left to die with the process

    # a writer that dies takes no stream down in silence
    srv = _serving_stack(lm).start()
    try:
        client = serving.ServingClient(srv.url, timeout_s=60)
        writer = srv._writer
        calls = []

        def dies_at_the_third(stream, token, t_emit, _item=writer._item):
            calls.append(token)
            if len(calls) == 3:
                raise RuntimeError("injected: the writer dies")
            _item(stream, token, t_emit)
        writer._item = dies_at_the_third
        t0 = time.monotonic()
        seen, end = _drain_stream(
            client.generate_stream([1, 2], max_new_tokens=30))
        assert isinstance(end, serving.GenerationStreamBroken)
        assert "stream writer" in str(end) and len(seen) == 2
        deadline = time.monotonic() + 30
        while writers() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not writers()
        # a later stream is refused typed, at once; the rest serves on
        seen, end = _drain_stream(
            client.generate_stream([1, 2], max_new_tokens=4))
        assert isinstance(end, serving.GenerationStreamBroken) and not seen
        assert time.monotonic() - t0 < 30
        assert len(client.generate([1, 2], max_new_tokens=4)["tokens"]) == 4
    finally:
        srv.stop()
    # a server that never started has no thread to leave behind
    _serving_stack(lm).stop()
    assert not writers()


# -- fleet chaos: mid-generation replica death ------------------------------

def _gen_fleet_model():
    # seeded so every worker process builds IDENTICAL weights — the
    # restart path must produce the same tokens on another replica
    return _lm(vocab=32, layers=1, units=16, heads=2, max_length=64,
               seed=123)


def _gen_fleet_factory():
    from mxnet_tpu.serving.generate import GenerationEngine
    return GenerationEngine(_gen_fleet_model(), slots=2, max_len=32,
                            prefill_buckets=(8,))


def _predict_factory():
    class _Echo:
        def __call__(self, x):
            return (onp.asarray(x) * 2.0,)
    return _Echo()


@pytest.mark.slow
def test_fleet_midstream_replica_death_fails_typed_then_restart():
    # replica 0 hard-crashes on its 3rd decode step (the generate.decode
    # chaos lever) mid-generation; the consumed-tokens stream must fail
    # TYPED — GenerationStreamBroken with trace id + tokens so far,
    # never a silent re-route — while midstream="restart" resubmits the
    # whole generation to the surviving replica
    telemetry.set_trace_sample(1.0)
    try:
        spec = serving.ReplicaSpec(
            _predict_factory, batch_buckets=(1, 2), max_batch_size=2,
            max_delay_ms=0.5, heartbeat_s=0.2,
            generate_factory=_gen_fleet_factory,
            per_replica_env={0: {"MXNET_FAULT_PLAN":
                                 "generate.decode@3:crash"}},
            restart_env={"MXNET_FAULT_PLAN": ""})
        prompt = [3, 1, 4, 1, 5]
        from mxnet_tpu.serving.fleet import _fleet_counters
        with serving.ReplicaSupervisor(spec, n_replicas=2, backoff_s=0.5,
                                       federate_s=0.2) as sup:
            with serving.Router(sup) as router:
                b0 = _fleet_counters["gen_broken"]
                it = router.generate_stream(prompt, max_new_tokens=12)
                seen = []
                with pytest.raises(serving.GenerationStreamBroken) as ei:
                    while True:
                        seen.append(next(it))
                assert seen, "tokens must flow before the injected crash"
                assert ei.value.tokens == seen
                assert ei.value.trace_id
                assert _fleet_counters["gen_broken"] > b0
                # the decode engine's KV-cached tokens on the SURVIVING
                # replica: whole-generation restart completes there
                got = router.generate(prompt, max_new_tokens=12,
                                      midstream="fail")
                assert len(got["tokens"]) == 12
                # federation: the worker-side generate collector reaches
                # the supervisor's summed gauges (autoscaler food)
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline:
                    g = sup.federated()["summed"]["gauges"]
                    if g.get("generate/free_kv_slots"):
                        break
                    time.sleep(0.3)
                assert g.get("generate/free_kv_slots")
    finally:
        telemetry.set_trace_sample(None)


@pytest.mark.slow
def test_fleet_generate_restart_policy_completes_after_break():
    # midstream="restart": the caller opted into a whole-stream retry —
    # the broken generation resubmits from the prompt and completes on
    # the healthy replica with identical tokens (seeded weights)
    telemetry.set_trace_sample(1.0)
    try:
        spec = serving.ReplicaSpec(
            _predict_factory, batch_buckets=(1, 2), max_batch_size=2,
            max_delay_ms=0.5, heartbeat_s=0.2,
            generate_factory=_gen_fleet_factory,
            per_replica_env={0: {"MXNET_FAULT_PLAN":
                                 "generate.decode@2:crash"}},
            restart_env={"MXNET_FAULT_PLAN": ""})
        prompt = [7, 2, 9]
        from mxnet_tpu.serving.fleet import _fleet_counters
        with serving.ReplicaSupervisor(spec, n_replicas=2, backoff_s=0.5,
                                       federate_s=0.5) as sup:
            with serving.Router(sup) as router:
                r0 = _fleet_counters["gen_restarts"]
                got = router.generate(prompt, max_new_tokens=8,
                                      midstream="restart")
                assert len(got["tokens"]) == 8
                assert got.get("restarts", 0) >= 1
                assert _fleet_counters["gen_restarts"] > r0
    finally:
        telemetry.set_trace_sample(None)


# -- metrics federation surface ---------------------------------------------

def test_generate_metrics_reach_telemetry_snapshot(lm):
    eng = _engine(lm)
    try:
        eng.generate([1, 2, 3], max_new_tokens=3, timeout=120)
    finally:
        eng.stop()
    snap = telemetry.snapshot()
    assert snap["counters"]["generate/completed"] >= 1
    assert snap["counters"]["generate/tokens_generated"] >= 3
    assert "generate/free_kv_slots" in snap["gauges"]
    assert snap["histograms"]["generate/ttft_ms"]["count"] >= 1
