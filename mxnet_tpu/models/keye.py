"""Keye-VL-2.0 (Kwai-Keye, ``model_type`` ``KeyeVL2``), the language model:
grouped-query attention that attends only where a learned indexer points,
and softmax top-k routing over experts with no shared one.  The vision
tower is not here: what the language model owes a multimodal prompt,
three-axis rotary positions, is.

The block, every layer alike: ``h += Attn(RMSNorm(h))``, ``h +=
MoE(RMSNorm(h))``; a final RMSNorm and the head (not the embedding).  No
bias but the indexer's LayerNorm.  Weights are stored [in, out].

* **Sectioned rotary.**  A position is three numbers ``(p_t, p_h, p_w)``;
  of a head's ``D / 2`` frequencies the first ``mrope_section[0]`` turn by
  ``p_t``, the next by ``p_h``, the rest by ``p_w``
  (:func:`mxnet_tpu.models.parts.sectioned_angles`), halves paired.  A
  text token at index ``t`` has ``(t, t, t)``: plain rotary.
* **Grouped-query attention.**  ``H`` query heads over ``KV`` key/value
  heads of ``D``, RMS norm on q and k per head, then rotated.  **A
  position's cache rows are k and v with the heads side by side** (``KV *
  D`` numbers each, no head axis).
* **The indexer**, on the same normed input: ``I(t, s) = sum_j w_j(t)
  ReLU(q^I_j(t) . k^I(s))`` over ``indexer_num_heads`` small heads and one
  key head, both rotated over the whole head with the sections halved; a
  query attends only to the ``topk`` positions ``s <= t`` of largest
  ``I``, one set for all heads, as a mask
  (:func:`mxnet_tpu.models.parts.topk_mask`: ``top_k``'s set but for
  which of scores exactly equal to the k-th it keeps, the lowest
  positions) in the full-sequence form and the one-step form alike.
  **The indexer's cache row is ``k^I``.**  So a slot holds three rings a
  layer.
* **Experts.**  :func:`mxnet_tpu.parallel.moe.dropless_moe`, the layer
  ``DeepSeekV32LM`` and ``LFM2MoeLM`` run, with ``scoring="softmax"``, one
  group, no selection bias and no shared expert.

The mathematics is in pure functions of a dict of raw weights
(:func:`trunk`, :func:`head`, :func:`run_full`, :func:`decode`), which
:class:`KeyeVL2LM` calls with its own parameters; ``cache_spec`` tells the
generation engine what each layer keeps.  The publisher's FP8 storage or
Hadamard rotation of the indexer's vectors, if it has them, are left out
as in ``deepseek.py``.
"""
from __future__ import annotations

import math
import types

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.parameter import Parameter
from .. import initializer as init
from ..base import np_dtype
from ..ndarray.ndarray import NDArray, unwrap
from ..parallel import moe as _moe
from .parts import (LANES, DrawnBias as _DrawnBias, FanInNormal,
                    grouped_ring_attend, index_scores,
                    layer_norm as _layernorm, matmul as _mm, part,
                    rms_norm as _rms, rope as _rope, sectioned_angles,
                    sparse_block_attend, sub_weights as _sub, topk_mask)

__all__ = ["KeyeVL2LM", "KEYE_PUBLISHED", "tiny_keye", "trunk", "head",
           "run_full", "decode", "STEP_COUNTERS"]

# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
# (the language model's keys).  ``intermediate_size`` belongs to no layer:
# ``decoder_sparse_step`` 1 and ``mlp_only_layers`` [] leave no dense one
KEYE_PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "num_experts": 128,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "num_hidden_layers": 48, "vocab_size": 151936, "rms_norm_eps": 1e-6,
    "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "tie_word_embeddings": False,
}

# the indexer's LayerNorm bias of a model built from a seed: drawn at this
# scale so that it is exercised (a trained checkpoint carries its own)
INDEX_BIAS_SIGMA = 0.1

# what a decode step counts on the device, over the active slots: (name,
# help), in the order of :func:`decode`'s counts
STEP_COUNTERS = (
    ("index_valid_positions", "positions the indexer scored, summed over "
                              "slots and layers"),
    ("index_selected_positions", "positions attended after the top-k, "
                                 "summed likewise"),
    ("kv_rows_read", "rows of the k ring (and as many of the v ring) the "
                     "attention read, summed over slots and layers, the "
                     "selection being a mask over them: whole blocks up to "
                     "each slot's valid positions where the kernel ran, "
                     "the whole ring where the einsums did"),
    ("attn_valid_positions", "cached positions of the k and v rings that "
                             "were valid, summed likewise"),
    ("routed_pairs", "(token, expert) pairs the routers chose"),
    ("experts_touched", "held experts with a token, summed over expert "
                        "layers and steps"),
    ("expert_load_max", "largest load of a held expert in a step (over "
                        "the layers), summed over steps"),
    ("expert_rows_computed", "rows one grouped product over the held "
                             "experts multiplied (row tiles visited x tile "
                             "rows), summed over the expert layers: over "
                             "the held pairs, the product's redundancy"),
    ("index_tie_rows", "rows (slot x layer) in which scores equal to the "
                       "k-th straddled the cut, so that the lowest "
                       "positions were kept: where the selection can "
                       "differ from top_k's own choice"),
)


def _jnp():
    import jax.numpy as jnp
    return jnp


def _text_positions(pos):
    """A text token's three axes are its index."""
    jnp = _jnp()
    return jnp.broadcast_to(pos[None], (3,) + pos.shape)


def _inputs(c, w, h, pos3):
    """Everything attention derives from the stream ``h`` [..., d] (its
    pre-norm is here) at three-axis positions ``pos3`` [3, ...]: ``(q
    [..., H, D], k and v [..., KV, D], q^I [..., Hi, Di], k^I [..., Di], w
    [..., Hi] float32)``; q and k normed per head, then rotated; the
    indexer's pair rotated over the whole head with the sections halved.
    The first three are attention's projections, the last three the
    indexer's."""
    jnp = _jnp()
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    Hi, Di = c.index_n_heads, c.index_head_dim
    eps = c.rms_norm_eps
    lead = h.shape[:-1]
    with part("attention"), part("project"):
        u = _rms(h, w["attn_norm"], eps)
        ang = sectioned_angles(pos3, D, c.rope_theta, c.mrope_section)
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        q = _rms(_mm(u, w["wq"]).reshape(lead + (H, D)), w["q_norm"], eps)
        k = _rms(_mm(u, w["wk"]).reshape(lead + (KV, D)), w["k_norm"], eps)
        v = _mm(u, w["wv"]).reshape(lead + (KV, D))
        q, k = _rope(q, cos, sin, False), _rope(k, cos, sin, False)
    with part("indexer"), part("project"):
        iang = sectioned_angles(pos3, Di, c.rope_theta, c.index_section)
        icos, isin = jnp.cos(iang), jnp.sin(iang)
        qi = _rope(_mm(u, w["idx_wq"]).reshape(lead + (Hi, Di)),
                   icos[..., None, :], isin[..., None, :], False)
        ki = _rope(_layernorm(_mm(u, w["idx_wk"]), w["idx_knorm_w"],
                              w["idx_knorm_b"], eps), icos, isin, False)
        wi = jnp.dot(u, w["idx_w"], preferred_element_type=jnp.float32) \
            * (Hi ** -0.5 * Di ** -0.5)
    return q, k, v, qi, ki, wi


def _attn_full(c, w, h, pos3, index_topk, want_sel):
    """Attention over a whole sequence [B, L, d], in blocks of
    ``q_chunk_size`` queries so that neither the heads' scores nor the
    indexer's are ever whole; a block's heads attend under its selection
    through :func:`parts.sparse_block_attend` (on one TPU a kernel that
    keeps the scores in VMEM, else einsums and a masked softmax).  Returns
    ``(the stream ``h`` with its output added, k rows [B, L, KV * D], v
    rows, k^I [B, L, Di], positions, index scores)``: the last two on
    request and only where the sequence is longer than
    ``index_topk`` (below it nothing is scored), the selection
    :func:`parts.topk_mask` made, as ``[B, L, K]`` indices by descending
    score (-1 where a query has fewer valid positions) and their
    scores."""
    import jax
    jnp = _jnp()
    B, L, _ = h.shape
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    G = H // KV
    q, k, v, qi, ki, wi = _inputs(c, w, h, pos3)
    with part("attention"), part("project"):
        kh, vh = jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1)  # [B, KV, L, D]
    bq = math.gcd(L, c.query_block)
    K = index_topk
    sparse = L > K

    def block(i):
        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, i * bq, bq, axis=1)
        with part("indexer"):
            causal = jnp.arange(L)[None, :] \
                <= (i * bq + jnp.arange(bq))[:, None]
            mask = jnp.broadcast_to(causal[None], (B, bq, L))
            chosen = vals = None
            if sparse:
                scores = index_scores(rows(qi), rows(wi), ki)   # [B, bq, L]
                mask, _tie = topk_mask(scores, mask, K)
                if want_sel:
                    chosen, vals = _as_indices(scores, mask, K)
        with part("attention"), part("attend"):
            # [B, KV, G * bq, D]: a key head's query heads one after another
            qb = jnp.transpose(rows(q).reshape(B, bq, KV, G, D),
                               (0, 2, 3, 1, 4)).reshape(B, KV, G * bq, D)
            o = sparse_block_attend(qb, kh, vh, mask, i * bq, D ** -0.5)
            o = o.astype(h.dtype)
        return (o, chosen, vals) if want_sel and sparse else (o, None, None)

    def whole(a):
        return None if a is None else jnp.moveaxis(a, 0, 1).reshape(B, L, K)
    # the loop itself is attention's: its body names its own parts
    with part("attention"), part("attend"):
        o, chosen, vals = jax.lax.map(block, jnp.arange(L // bq))
    with part("attention"), part("project"):
        o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * D)
        h = h + _mm(o, w["wo"])
    with part("indexer"):
        chosen, vals = whole(chosen), whole(vals)
    return h, k.reshape(B, L, KV * D), v.reshape(B, L, KV * D), ki, \
        chosen, vals


def _as_indices(scores, mask, K):
    """The positions ``mask`` [..., N] keeps as ``top_k`` would list them
    (``[..., K]`` indices by descending score, -1 past the kept) and
    their scores: the probed programs' form of the selection."""
    import jax
    jnp = _jnp()
    with part("top_k"):
        vals, chosen = jax.lax.top_k(jnp.where(mask, scores, -jnp.inf), K)
        return jnp.where(vals > -jnp.inf, chosen, -1), vals


def _ffn(c, w, h, weight=None):
    """``(h + y, idx, scores, load)`` of a layer's experts on the stream
    ``h`` [..., d], their pre-norm and their residual add with them."""
    first, count = c.held
    with part("experts"):
        x = _rms(h, w["ffn_norm"], c.rms_norm_eps)
        y, idx, _gates, scores = _moe.dropless_moe(
            x.reshape(-1, x.shape[-1]), _sub(w, "ffn."),
            k=c.num_experts_per_tok, first=first, scoring="softmax")
        load = _jnp().append(
            _moe.held_load(idx, first, count, weight),
            _moe.rows_computed(idx, first, w["ffn.held_w1"]))
        return h + y.astype(h.dtype).reshape(h.shape), idx, scores, load


def _index_row(c, ki):
    """An indexer key as its ring stores it: padded with zeros to
    ``c.index_stride`` numbers."""
    jnp = _jnp()
    pad = c.index_stride - ki.shape[-1]
    return ki if pad == 0 else jnp.pad(
        ki, [(0, 0)] * (ki.ndim - 1) + [(0, pad)])


def trunk(c, w, tokens, positions=None, index_topk=None,
          want_selections=False):
    """The layers over ``tokens`` [B, L] at ``positions`` [3, B, L] (a
    text token's, if None), no cache.  Returns ``(h [B, L, d] before the
    final norm, [(k rows, v rows, k^I rows) a layer, as the rings store
    them], selections or None)``; selections are ``{"positions": [[B, L,
    K] indices or None a layer], "index_scores": [their scores [B, L, K]
    or None], "experts": [idx [B*L, k] a layer], "router_scores": [[B*L,
    E] a layer]}``."""
    jnp = _jnp()
    index_topk = c.index_topk if index_topk is None else index_topk
    B, L = tokens.shape
    with part("embed"):
        pos3 = _text_positions(jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None], (B, L))) \
            if positions is None else positions.astype(jnp.int32)
        x = w["embed"][tokens]
    caches, sel = [], {"positions": [], "index_scores": [], "experts": [],
                       "router_scores": []}
    for i in range(c.num_hidden_layers):
        lw = _sub(w, f"layers.{i}.")
        x, k, v, ki, chosen, vals = _attn_full(
            c, lw, x, pos3, index_topk, want_selections)
        x, idx, scores, _load = _ffn(c, lw, x)
        with part("attention"), part("ring_write"):
            k, v = k.astype(c.cache_dtype), v.astype(c.cache_dtype)
        with part("indexer"), part("ring_write"):
            ki = _index_row(c, ki).astype(c.cache_dtype)
        caches.append((k, v, ki))
        sel["positions"].append(chosen)
        sel["index_scores"].append(vals)
        sel["experts"].append(idx)
        sel["router_scores"].append(scores)
    return x, caches, (sel if want_selections else None)


def head(c, w, x):
    """Logits [..., V] float32 of the stream ``x`` [..., d]."""
    jnp = _jnp()
    with part("head"):
        return jnp.dot(_rms(x, w["norm"], c.rms_norm_eps), w["head"],
                       preferred_element_type=jnp.float32)


def run_full(c, w, tokens, positions=None, index_topk=None,
             want_selections=False, last=None):
    """The full causal forward, :func:`trunk` then :func:`head`: ``(logits
    [B, L, V] float32, caches, selections or None)``.  With ``last`` [B]
    the logits are [B, 1, V], those of position ``last - 1`` alone: at
    8,192 positions the whole array is 5 GB in float32."""
    jnp = _jnp()
    x, caches, sel = trunk(c, w, tokens, positions, index_topk,
                           want_selections)
    if last is not None:
        with part("head"):
            x = jnp.take_along_axis(
                x, (last.reshape(-1, 1, 1) - 1).astype(jnp.int32), axis=1)
    return head(c, w, x), caches, sel


def decode(c, w, tok, caches, pos, active=None, index_topk=None,
           want_selections=False):
    """One token a slot, ``tok`` [S] at ``pos`` [S] (text: the three axes
    equal), through ``caches`` = [(k ring [S, M, KV * D], v ring, indexer
    ring [S, M, stride]) a layer].  The new rows land at ``pos % M`` of
    the active slots (one scatter a ring); the indexer scores the slot's
    valid positions, :func:`parts.topk_mask` keeps ``index_topk`` of them
    as a mask, and every head attends over the rings under it
    (:func:`parts.grouped_ring_attend`: on one TPU a kernel over the
    slot's valid blocks, else einsums over the whole rings).  At 40 slots
    x 12,288 with contexts of 6.4-9.9 k on a v5e the masked einsums took
    2.10 ms a layer where a gather of the 2,048 selected rows from both
    rings and products over them took 2.93 (a whole step 19.35 against
    22.70 ms: PERF.md, PR 35).  Returns ``(logits [S, V] float32,
    rings, counts [len(STEP_COUNTERS)] int32)``, and with
    ``want_selections`` a fourth: :func:`trunk`'s selections for this one
    position a slot (indices into the ring and their scores [S, K])."""
    import jax
    jnp = _jnp()
    index_topk = c.index_topk if index_topk is None else index_topk
    S = tok.shape[0]
    Di = c.index_head_dim
    pos = pos.astype(jnp.int32)
    pos3 = _text_positions(pos)
    act = jnp.ones((S,), jnp.int32) if active is None \
        else (active > 0).astype(jnp.int32)
    with part("embed"):
        x = w["embed"][tok]                                  # [S, d]
    new, slots = [], jnp.arange(S)
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    sel = {"positions": [], "index_scores": [], "experts": [],
           "router_scores": []}
    for i in range(c.num_hidden_layers):
        lw = _sub(w, f"layers.{i}.")
        q, k, v, qi, ki, wi = _inputs(c, lw, x, pos3)
        ring_k, ring_v, ring_i = caches[i]
        M, W = ring_k.shape[1:]
        at = jnp.where(act > 0, pos % M, M)      # M: out of range, dropped
        with part("attention"), part("ring_write"):
            ring_k = ring_k.at[slots, at].set(
                k.reshape(S, W).astype(ring_k.dtype), mode="drop")
            ring_v = ring_v.at[slots, at].set(
                v.reshape(S, W).astype(ring_v.dtype), mode="drop")
        with part("indexer"):
            with part("ring_write"):
                ring_i = ring_i.at[slots, at].set(
                    _index_row(c, ki).astype(ring_i.dtype), mode="drop")
            n_valid = jnp.minimum(pos + 1, M)
            valid = jnp.arange(M)[None, :] < n_valid[:, None]
            # the query's heads padded as the keys are: products over
            # whole rows
            qi = jnp.pad(qi, ((0, 0), (0, 0), (0, c.index_stride - Di)))
            scores = index_scores(qi[:, None], wi[:, None],
                                  ring_i.astype(x.dtype))[:, 0]  # [S, M]
            K = min(index_topk, M)
            mask, tie = topk_mask(scores, valid, K)
        with part("attention"):
            with part("attend"):
                o, rows_read = grouped_ring_attend(q, ring_k, ring_v,
                                                   n_valid, mask)
            with part("project"):
                x = x + _mm(o.astype(x.dtype), lw["wo"])
        x, idx, router_scores, load = _ffn(c, lw, x, weight=act)
        new.append((ring_k, ring_v, ring_i))
        if want_selections:
            with part("indexer"):
                chosen, vals = _as_indices(scores, mask, K)
            sel["positions"].append(chosen)
            sel["index_scores"].append(vals)
            sel["experts"].append(idx)
            sel["router_scores"].append(router_scores)
        with part("indexer"):
            seen = (act * jnp.stack([n_valid, jnp.minimum(n_valid, K),
                                     rows_read, n_valid, tie])).sum(axis=1)
            seen = seen.astype(jnp.int32)
            counts = counts.at[:4].add(seen[:4]).at[8].add(seen[4])
        with part("experts"):
            counts = counts.at[4].add(load[0]).at[5].add(load[2])
            counts = counts.at[6].max(load[3]).at[7].add(load[4])
    logits = head(c, w, x)
    if want_selections:
        return logits, new, counts, sel
    return logits, new, counts


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
class _KeyeBlock(HybridBlock):
    def __init__(self, c, dtype, grad_req):
        super().__init__()
        d, D = c.hidden_size, c.head_dim
        H, KV = c.num_attention_heads, c.num_key_value_heads
        Hi, Di = c.index_n_heads, c.index_head_dim
        winit = FanInNormal()

        def par(name, shape, pinit=winit):
            setattr(self, name, Parameter(name, shape=shape, dtype=dtype,
                                          init=pinit, grad_req=grad_req))
        par("attn_norm", (d,), init.One())
        par("wq", (d, H * D))
        par("wk", (d, KV * D))
        par("wv", (d, KV * D))
        par("wo", (H * D, d))
        par("q_norm", (D,), init.One())
        par("k_norm", (D,), init.One())
        par("idx_wq", (d, Hi * Di))
        par("idx_wk", (d, Di))
        par("idx_knorm_w", (Di,), init.One())
        par("idx_knorm_b", (Di,), _DrawnBias(INDEX_BIAS_SIGMA))
        par("idx_w", (d, Hi))
        par("ffn_norm", (d,), init.One())
        self.ffn = _moe.DroplessMoE(
            d, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held=c.held, shared_experts=0,
            scoring="softmax", select_bias=False, dtype=dtype,
            weight_initializer=winit, grad_req=grad_req)

    hybrid_forward = None


class KeyeVL2LM(HybridBlock):
    """Keye-VL-2.0's language model as the generation engine serves it.

    ``config`` holds the published keys (:data:`KEYE_PUBLISHED`; what is
    given overrides), with ``num_experts`` the router's width whatever is
    held.  ``held=(first, count)`` are the routed experts this chip
    computes (all, if None).  ``dtype`` is the type of the weights and the
    activations, ``cache_dtype`` that of the three rings (``dtype`` if
    None); norms, the router, index scores and softmax are float32 inside
    whatever they are.  Parameters take no gradient: a served model of
    billions of parameters must not allocate them."""

    def __init__(self, config=None, held=None, dtype="bfloat16",
                 cache_dtype=None, **kwargs):
        super().__init__(**kwargs)
        merged = dict(KEYE_PUBLISHED)
        merged.update(config or {})
        c = self._cfg = types.SimpleNamespace(**{
            k: merged[k] for k in KEYE_PUBLISHED})
        sa = c.sa_config
        if sa["indexer_num_kv_heads"] != 1 or not c.norm_topk_prob \
                or c.tie_word_embeddings:
            raise ValueError(
                "KeyeVL2LM is written for one indexer key head, gates "
                "renormalised over the chosen and a head of its own")
        c.index_n_heads, c.index_head_dim = sa["indexer_num_heads"], \
            sa["indexer_head_dim"]
        c.index_topk, c.query_block = sa["topk"], sa["q_chunk_size"]
        c.mrope_section = tuple(c.rope_scaling["mrope_section"])
        if any(n % 2 for n in c.mrope_section):
            raise ValueError(f"mrope_section {c.mrope_section} cannot be "
                             f"halved for the indexer's head")
        # the indexer's head turns over all its numbers, a share each axis
        # as in a head of the attention
        scale = c.head_dim // c.index_head_dim
        c.index_section = tuple(n // scale for n in c.mrope_section)
        # a key of 64 numbers is stored at a stride of 128: compiled for a
        # v5e a ring of 64 lies with the positions on the lanes and is
        # copied whole twice a layer a step (PERF.md, PR 35)
        c.index_stride = -(-c.index_head_dim // LANES) * LANES
        c.held = tuple(held) if held is not None else (0, c.num_experts)
        c.cache_dtype = np_dtype(dtype if cache_dtype is None
                                 else cache_dtype)
        grad_req = "null"
        self.embed = Parameter("embed", shape=(c.vocab_size, c.hidden_size),
                               dtype=dtype, init=FanInNormal(1.0),
                               grad_req=grad_req)
        self.layers = nn.HybridSequential()
        for _ in range(c.num_hidden_layers):
            self.layers.add(_KeyeBlock(c, dtype, grad_req))
        self.norm = Parameter("norm", shape=(c.hidden_size,), dtype=dtype,
                              init=init.One(), grad_req=grad_req)
        self.head = Parameter("head", shape=(c.hidden_size, c.vocab_size),
                              dtype=dtype, init=FanInNormal(),
                              grad_req=grad_req)

    # -- what the engine asks ------------------------------------------------
    @property
    def config(self):
        return self._cfg

    @property
    def num_layers(self):
        return self._cfg.num_hidden_layers

    step_counters = STEP_COUNTERS
    # prefill and decode_step take probe=True: what they chose, besides
    probes = True

    def cache_spec(self, max_len):
        """For each layer the ``(kind, trailing shape, dtype)`` of its
        three rings: a key row and a value row a position, the heads side
        by side, and the indexer's key."""
        c = self._cfg
        row = c.num_key_value_heads * c.head_dim
        return [[("k", (max_len, row), c.cache_dtype),
                 ("v", (max_len, row), c.cache_dtype),
                 ("indexer", (max_len, c.index_stride), c.cache_dtype)]
                for _ in range(c.num_hidden_layers)]

    def raw_weights(self):
        """{dotted name: raw array} of the live parameters (tracers while
        a program is traced)."""
        return {name: unwrap(p.data())
                for name, p in self._collect_params_with_prefix().items()}

    # -- the three entry points ---------------------------------------------
    def forward(self, tokens, valid_length=None, positions=None,
                index_topk=None, want_selections=False):
        """(B, L) ids -> (B, L, vocab) float32 logits, causal; with
        ``want_selections`` also what the indexer and the routers chose.
        ``positions`` is [3, B, L] (a text token's three axes are its
        index, if None).  ``valid_length`` is accepted for the protocol:
        under a causal mask no valid position sees a padded one."""
        jnp = _jnp()
        logits, _caches, sel = run_full(
            self._cfg, self.raw_weights(), unwrap(tokens).astype(jnp.int32),
            None if positions is None else unwrap(positions), index_topk,
            want_selections)
        return (NDArray(logits), sel) if want_selections else NDArray(logits)

    hybrid_forward = None

    def prefill(self, tokens, valid_length=None, positions=None,
                index_topk=None, probe=False):
        """Prompt pass: ``(logits (B, 1, vocab) of position
        ``valid_length - 1`` alone (the last, if None), [(k rows, v rows,
        indexer keys) a layer])``, and with ``probe`` :func:`trunk`'s
        selections."""
        jnp = _jnp()
        toks = unwrap(tokens).astype(jnp.int32)
        last = jnp.full((toks.shape[0],), toks.shape[1], jnp.int32) \
            if valid_length is None else unwrap(valid_length)
        logits, caches, sel = run_full(
            self._cfg, self.raw_weights(), toks,
            None if positions is None else unwrap(positions), index_topk,
            probe, last)
        out = (NDArray(logits), [tuple(NDArray(a) for a in layer)
                                 for layer in caches])
        return out + (sel,) if probe else out

    def decode_step(self, tokens, caches, position, active=None,
                    index_topk=None, probe=False):
        """One token a slot against the rings: ``(logits (S, vocab),
        rings', counts)``, the counts in :data:`STEP_COUNTERS`' order, and
        with ``probe`` :func:`decode`'s selections, a row a slot.  The
        engine passes its one position a slot: a generated token is
        text."""
        jnp = _jnp()
        logits, new, counts, *sel = decode(
            self._cfg, self.raw_weights(),
            unwrap(tokens).reshape(-1).astype(jnp.int32),
            [tuple(unwrap(r) for r in layer) for layer in caches],
            unwrap(position), None if active is None else unwrap(active),
            index_topk, probe)
        return (NDArray(logits), [tuple(NDArray(r) for r in layer)
                                  for layer in new], NDArray(counts), *sel)


def tiny_keye(vocab_size=96, dtype="float32", **kwargs):
    """A CPU-sized Keye for tests: every mechanism present (grouped heads,
    sectioned rotary, an indexer whose top-k is below the test contexts,
    more experts than a token takes), no width as published."""
    cfg = {"hidden_size": 32, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "moe_intermediate_size": 16, "num_experts": 16,
           "num_experts_per_tok": 4, "num_hidden_layers": 3,
           "rope_scaling": {"mrope_section": [2, 4, 2],
                            "rope_type": "default", "type": "default"},
           "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 16,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                         "q_chunk_size": 8, "topk": 8},
           "vocab_size": vocab_size}
    cfg.update(kwargs.pop("config", {}))
    return KeyeVL2LM(cfg, dtype=dtype, **kwargs)
