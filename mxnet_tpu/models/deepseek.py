"""DeepSeek-V3.2-Exp: latent attention with a lightning indexer, and
group-limited sigmoid routing over experts of which this chip holds some.

The block, every layer: ``h += Attn(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``;
a final RMSNorm and the head.  No bias anywhere but the indexer's
LayerNorm.  Weights are stored [in, out].

* **Latent attention (MLA).**  ``c_q = RMSNorm(x W_qa)``; per head
  ``[q_nope; q_rope] = c_q W_qb``; ``[c_kv; k_rope] = x W_kva`` with
  ``c_kv`` normed and ``k_rope`` (one for all heads) rotated.  **A token's
  cache row is ``(c_kv, k_rope)``**: ``kv_lora_rank + qk_rope_head_dim``
  numbers.  Keys and values are ``c_kv W_kvb``; the full forward and the
  prefill expand them, the decode step absorbs ``W_kvb`` into the query
  and the output instead and attends over latent rows.
* **Lightning indexer.**  ``I(t, s) = sum_j w_j(t) ReLU(q^I_j(t) . k^I(s))``
  over ``index_n_heads`` small heads; a query attends only to the
  ``index_topk`` positions ``s <= t`` of largest ``I``.  **The indexer's
  cache row is ``k^I``**: ``index_head_dim`` numbers.
* **Experts.**  :func:`mxnet_tpu.parallel.moe.dropless_moe`: the router
  and the selection over all ``n_routed_experts``, the product over the
  ``held`` ones, a shared expert.

The mathematics is in pure functions of a dict of raw weights
(:func:`run_full`, :func:`decode`), which :class:`DeepSeekV32LM` calls
with its own parameters; ``cache_spec`` tells the generation engine which
rings a layer keeps.  Rotary pairs are interleaved in MLA and halves in
the indexer, as the publisher's code has them; its Hadamard rotation and
FP8 storage of the indexer's vectors are left out (the rotation is
orthogonal and changes no product; FP8 is a storage choice).
"""
from __future__ import annotations

import math
import types

import numpy as onp

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.parameter import Parameter
from .. import initializer as init
from ..base import np_dtype
from ..ndarray.ndarray import NDArray, unwrap
from ..parallel import moe as _moe
from ..ops import latent_ring_attention as _lra
from .parts import (LANES, FanInNormal as _FanInNormal, index_scores,
                    layer_norm as _layernorm, mask_positions,
                    matmul as _mm, part, rms_norm as _rms, rope as _rope,
                    sparse_block_attend, sub_weights as _sub, topk_mask)

__all__ = ["DeepSeekV32LM", "V32_PUBLISHED", "tiny_v32", "run_full", "decode",
           "yarn_inv_freq", "softmax_scale", "STEP_COUNTERS"]

# https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json
V32_PUBLISHED = {
    "hidden_size": 7168, "num_attention_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
    "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "num_experts_per_tok": 8, "n_shared_experts": 1,
    "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
    "num_hidden_layers": 61, "first_k_dense_replace": 3,
    "vocab_size": 129280, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}

# queries a block of the full forward's attention: at 3,072 positions the
# scores of 128 heads are 4.8 GB in float32 if materialised whole
QUERY_BLOCK = 512

# a latent row is stored at the next multiple of the chip's lane width
# (576 numbers at 640, see _ring_row)
LATENT_ALIGN = LANES

# the noaux_tc selection bias of a model built from a seed: drawn at this
# scale so that it is exercised (a trained checkpoint carries its own)
SELECT_BIAS_SIGMA = 0.01

# what a decode step counts on the device, over the active slots: (name,
# help), in the order of :func:`decode`'s counts.  The engine reads them
# back with the step's tokens and declares them under ``generate/``.
STEP_COUNTERS = (
    ("index_valid_positions", "positions the indexer scored, summed over "
                              "slots and layers"),
    ("index_selected_positions", "positions attended after the top-k, "
                                 "summed likewise"),
    ("routed_pairs", "(token, expert) pairs the routers chose"),
    ("routed_pairs_held", "of those, pairs whose expert is held here"),
    ("experts_touched", "held experts with a token, summed over expert "
                        "layers and steps"),
    ("expert_load_max", "largest load of a held expert in a step (over "
                        "the layers), summed over steps"),
    ("latent_rows_read", "rows of the latent ring the attention read, "
                         "summed over slots and layers: whole blocks of "
                         "valid positions where the kernel ran, the "
                         "selected rows where the gather did"),
    ("expert_rows_computed", "rows one grouped product over the held "
                             "experts multiplied (row tiles visited x tile "
                             "rows), summed over the expert layers: over "
                             "the held pairs, the product's redundancy"),
    ("index_tie_rows", "rows (slot x layer) in which scores equal to the "
                       "k-th straddled the cut, so that the lowest "
                       "positions were kept: where the selection can "
                       "differ from top_k's own choice"),
)


# ---------------------------------------------------------------------------
# parts
# ---------------------------------------------------------------------------
def yarn_inv_freq(c):
    """The rotary frequencies [qk_rope_head_dim / 2] under YaRN: those
    that turn fewer than ``beta_slow`` times over the original context are
    divided by ``factor``, those that turn more than ``beta_fast`` times
    stay, with a linear ramp between (contexts beyond the original)."""
    dim, base = c.qk_rope_head_dim, float(c.rope_theta)
    rs = c.rope_scaling
    freqs = 1.0 / base ** (onp.arange(0, dim, 2, dtype=onp.float64) / dim)
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = onp.clip((onp.arange(dim // 2) - low) / (high - low), 0, 1)
    smooth = 1 - ramp
    return (freqs / rs["factor"] * (1 - smooth)
            + freqs * smooth).astype(onp.float32)


def softmax_scale(c):
    rs = c.rope_scaling
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * m * m


def _jnp():
    import jax.numpy as jnp
    return jnp


def _ring_row(c, latent):
    """A latent row as the ring stores it: padded with zeros to
    ``c.latent_stride`` numbers (:data:`LATENT_ALIGN`).  At 576 numbers a row the chip lays a
    ring out with the positions on the lanes (576 is no multiple of its
    128), and then neither one row's write nor a gather of rows is a
    contiguous access: each costs a relayout of the whole ring (compiled
    for a v5e, PR 28); at 640 the rows are contiguous."""
    jnp = _jnp()
    pad = c.latent_stride - latent.shape[-1]
    return latent if pad == 0 else jnp.pad(
        latent, [(0, 0)] * (latent.ndim - 1) + [(0, pad)])


def _attn_inputs(c, w, h, pos):
    """Everything attention derives from the stream ``h`` [B, L, d] (its
    pre-norm is here) at positions ``pos`` [B, L]: ``(q_nope [B,L,H,n],
    q_rope [B,L,H,r], latent row [B,L,kv+r], q^I [B,L,Hi,Di], k^I
    [B,L,Di], w [B,L,Hi] float32)``: the first three are attention's
    projections, the last three the indexer's."""
    jnp = _jnp()
    f32 = jnp.float32
    B, L, _ = h.shape
    H, n, r = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    kvr, Hi, Di = c.kv_lora_rank, c.index_n_heads, c.index_head_dim
    eps = c.rms_norm_eps
    with part("attention"), part("project"):
        x = _rms(h, w["attn_norm"], eps)
        ang = pos.astype(f32)[..., None] * jnp.asarray(yarn_inv_freq(c))
        cos, sin = jnp.cos(ang), jnp.sin(ang)               # [B, L, r/2]
        hcos, hsin = cos[:, :, None], sin[:, :, None]
        c_q = _rms(_mm(x, w["wq_a"]), w["q_norm"], eps)
        q = _mm(c_q, w["wq_b"]).reshape(B, L, H, n + r)
        q_nope, q_rope = q[..., :n], _rope(q[..., n:], hcos, hsin, True)
        kv = _mm(x, w["wkv_a"])
        latent = jnp.concatenate(
            [_rms(kv[..., :kvr], w["kv_norm"], eps),
             _rope(kv[..., kvr:], cos, sin, True)], axis=-1)
    with part("indexer"), part("project"):
        qi = _mm(c_q, w["idx_wq_b"]).reshape(B, L, Hi, Di)
        qi = jnp.concatenate([_rope(qi[..., :r], hcos, hsin, False),
                              qi[..., r:]], axis=-1)
        ki = _layernorm(_mm(x, w["idx_wk"]), w["idx_knorm_w"],
                        w["idx_knorm_b"], eps)
        ki = jnp.concatenate([_rope(ki[..., :r], cos, sin, False),
                              ki[..., r:]], axis=-1)
        wi = jnp.dot(x, w["idx_w"], preferred_element_type=f32) \
            * (Hi ** -0.5 * Di ** -0.5)
    return q_nope, q_rope, latent, qi, ki, wi


def _attn_full(c, w, h, pos, index_topk, want_mask):
    """Attention over a whole sequence in the expanded form, in blocks of
    queries so that neither the heads' scores nor the indexer's are ever
    whole; a block's heads attend under its selection through
    :func:`parts.sparse_block_attend` (on one TPU a kernel that keeps the
    scores in VMEM, else einsums and a masked softmax).  Returns ``(the
    stream ``h`` [B,L,d] with attention's output added, latent rows, k^I,
    mask or None, index scores or None)``: the last two on request, the
    scores only where the sequence is longer than ``index_topk`` (below it
    nothing is scored)."""
    import jax
    jnp = _jnp()
    f32 = jnp.float32
    B, L, _ = h.shape
    H, n, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
    kvr, r = c.kv_lora_rank, c.qk_rope_head_dim
    q_nope, q_rope, latent, qi, ki, wi = _attn_inputs(c, w, h, pos)
    with part("attention"), part("project"):
        # keys and values head-major [B, H, L, .] out of the product itself:
        # no transpose of them; k_rope is every head's
        kvb = jnp.einsum("blc,che->bhle", latent[..., :kvr],
                         w["wkv_b"].reshape(kvr, H, n + dv),
                         preferred_element_type=f32).astype(h.dtype)
        k = jnp.concatenate([kvb[..., :n], jnp.broadcast_to(
            latent[:, None, :, kvr:], (B, H, L, r))], axis=-1)
        v = kvb[..., n:]
    scale = softmax_scale(c)
    bq = math.gcd(L, QUERY_BLOCK)
    sparse = L > index_topk

    def block(i):
        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, i * bq, bq, axis=1)
        with part("indexer"):
            causal = jnp.arange(L)[None, :] \
                <= (i * bq + jnp.arange(bq))[:, None]
            mask = jnp.broadcast_to(causal[None], (B, bq, L))
            scores = None
            if sparse:
                scores = index_scores(rows(qi), rows(wi), ki)
                mask, _tie = topk_mask(scores, mask, index_topk)
        with part("attention"), part("attend"):
            q = jnp.moveaxis(jnp.concatenate([rows(q_nope), rows(q_rope)],
                                             axis=-1), 2, 1)  # [B, H, bq, .]
            o = sparse_block_attend(q, k, v, mask, i * bq, scale)
            o = o.astype(h.dtype)
        return (o, mask, scores) if want_mask else (o, None, None)

    def whole(a):
        return None if a is None else jnp.moveaxis(a, 0, 1).reshape(B, L, L)
    # the loop itself is attention's: its body names its own parts
    with part("attention"), part("attend"):
        o, mask, scores = jax.lax.map(block, jnp.arange(L // bq))
    with part("attention"), part("project"):
        o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * dv)
        h = h + _mm(o, w["wo"])
    with part("indexer"):
        mask, scores = whole(mask), whole(scores)
    return h, latent, ki, mask, scores


def _ffn(c, w, i, h, weight=None):
    """``(h + y, idx, scores, load)`` of layer ``i``'s feed-forward on the
    stream ``h`` [B, L, d], its pre-norm and its residual add with it: the
    last three None in a dense layer."""
    dense = i < c.first_k_dense_replace
    with part("ffn" if dense else "experts"):
        x2d = _rms(h, w["ffn_norm"], c.rms_norm_eps).reshape(-1, h.shape[-1])
        if dense:
            y = _moe.swiglu(x2d, w["ffn_w1"], w["ffn_w3"], w["ffn_w2"])
            return h + y.astype(h.dtype).reshape(h.shape), None, None, None
        first, count = c.held
        y, idx, _gates, scores = _moe.dropless_moe(
            x2d, _sub(w, "ffn."), k=c.num_experts_per_tok, first=first,
            n_group=c.n_group, topk_group=c.topk_group,
            route_scale=c.routed_scaling_factor)
        load = _jnp().append(
            _moe.held_load(idx, first, count, weight),
            _moe.rows_computed(idx, first, w["ffn.held_w1"]))
        return h + y.astype(h.dtype).reshape(h.shape), idx, scores, load


def run_full(c, w, tokens, index_topk=None, want_selections=False):
    """The full causal forward over ``tokens`` [B, L], no cache.  Returns
    ``(logits [B, L, V] float32, [(latent rows, k^I) a layer, as the rings
    store them], selections or None)``; selections are ``{"positions": [mask [B, L, L] a layer],
    "index_scores": [[B, L, L] or None a layer], "experts": [idx [B*L, k]
    an expert layer], "router_scores": [[B*L, E] an expert layer]}``.
    """
    jnp = _jnp()
    index_topk = c.index_topk if index_topk is None else index_topk
    B, L = tokens.shape
    with part("embed"):
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
        x = w["embed"][tokens]
    caches, sel = [], {"positions": [], "index_scores": [], "experts": [],
                       "router_scores": []}
    for i in range(c.num_hidden_layers):
        lw = _sub(w, f"layers.{i}.")
        x, latent, ki, mask, scores_i = _attn_full(
            c, lw, x, pos, index_topk, want_selections)
        x, idx, scores, _load = _ffn(c, lw, i, x)
        with part("attention"), part("ring_write"):
            latent = _ring_row(c, latent).astype(c.cache_dtype)
        with part("indexer"), part("ring_write"):
            ki = ki.astype(c.cache_dtype)
        caches.append((latent, ki))
        sel["positions"].append(mask)
        sel["index_scores"].append(scores_i)
        if idx is not None:
            sel["experts"].append(idx)
            sel["router_scores"].append(scores)
    with part("head"):
        logits = jnp.dot(_rms(x, w["norm"], c.rms_norm_eps), w["head"],
                         preferred_element_type=jnp.float32)
    return logits, caches, (sel if want_selections else None)


def decode(c, w, tok, caches, pos, active=None, index_topk=None,
           want_selections=False):
    """One token a slot, ``tok`` [S] at ``pos`` [S], through the rings
    ``caches`` = [(latent [S, M, stride], indexer [S, M, Di]) a layer].
    The new rows land at ``pos % M`` of the active slots (one scatter a
    ring); the indexer scores the slot's valid positions,
    :func:`parts.topk_mask` keeps ``index_topk`` of them as a mask, and
    attention runs in the absorbed form over
    the selected latent rows alone, in one of two forms that share no
    line and are chosen by what the code sees, with no switch:

    * on one TPU, for a ring a block divides,
      :func:`mxnet_tpu.ops.latent_ring_attention.latent_ring_attention`
      reads the donated ring where it lies, the selection as a mask: it
      skips the blocks past a slot's valid positions and writes neither
      rows, scores nor probabilities to memory;
    * on a CPU, under a mesh, or where the compiler refuses the kernel, a
      gather of the rows the mask keeps and plain einsums: the kernel's
      tested reference.

    At 64 slots x 6,144 on a v5e masked dense attention over the whole
    ring took 2.5 times as long as the gather form (PERF.md, PR 28); the
    kernel took the step from 41.5 ms in the gather form to 27 (PERF.md,
    PR 31 and 34).  Returns ``(logits [S, V] float32, rings,
    counts [len(STEP_COUNTERS)] int32)``, and with ``want_selections`` a
    fourth: :func:`run_full`'s selections for this one position a slot
    (masks and index scores [S, M] over the ring)."""
    import jax
    jnp = _jnp()
    f32 = jnp.float32
    index_topk = c.index_topk if index_topk is None else index_topk
    S = tok.shape[0]
    H, n, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
    kvr = c.kv_lora_rank
    row = kvr + c.qk_rope_head_dim
    scale = softmax_scale(c)
    pos = pos.astype(jnp.int32)
    act = jnp.ones((S,), jnp.int32) if active is None \
        else (active > 0).astype(jnp.int32)
    with part("embed"):
        x = w["embed"][tok][:, None]                         # [S, 1, d]
    new, slots = [], jnp.arange(S)
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    sel = {"positions": [], "index_scores": [], "experts": [],
           "router_scores": []}
    for i in range(c.num_hidden_layers):
        lw = _sub(w, f"layers.{i}.")
        q_nope, q_rope, latent, qi, ki, wi = _attn_inputs(
            c, lw, x, pos[:, None])
        ring_l, ring_i = caches[i]
        M = ring_l.shape[1]
        at = jnp.where(act > 0, pos % M, M)      # M: out of range, dropped
        with part("attention"), part("ring_write"):
            ring_l = ring_l.at[slots, at].set(
                _ring_row(c, latent[:, 0]).astype(ring_l.dtype), mode="drop")
        with part("indexer"):
            with part("ring_write"):
                ring_i = ring_i.at[slots, at].set(
                    ki[:, 0].astype(ring_i.dtype), mode="drop")
            n_valid = jnp.minimum(pos + 1, M)
            valid = jnp.arange(M)[None, :] < n_valid[:, None]
            scores = index_scores(qi, wi, ring_i.astype(x.dtype))[:, 0]
            K = min(index_topk, M)
            mask, tie = topk_mask(scores, valid, K)
            block = _lra.kernel_block(S, H, kvr, c.qk_rope_head_dim, M,
                                      ring_l.shape[2], x.dtype, ring_l.dtype)
        if want_selections:
            sel["positions"].append(mask)
        sel["index_scores"].append(scores)
        with part("attention"):
            with part("project"):
                wkb = lw["wkv_b"].reshape(kvr, H, n + dv)
                q_abs = jnp.einsum("shn,chn->shc", q_nope[:, 0], wkb[..., :n],
                                   preferred_element_type=f32).astype(x.dtype)
            with part("attend"):
                if block is not None:
                    o = _lra.latent_ring_attention(
                        q_abs, q_rope[:, 0], ring_l, mask, n_valid, scale,
                        block=block)
                    rows_read = _lra.rows_visited(n_valid, block)
                else:
                    chosen, keep = mask_positions(mask, K)
                    rows = jnp.take_along_axis(ring_l, chosen[:, :, None],
                                               axis=1).astype(x.dtype)
                    s = jnp.einsum("shc,skc->shk", q_abs, rows[..., :kvr],
                                   preferred_element_type=f32) \
                        + jnp.einsum("shr,skr->shk", q_rope[:, 0],
                                     rows[..., kvr:row],
                                     preferred_element_type=f32)
                    s = jnp.where(keep[:, None], s * scale, -1e30)
                    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
                    o = jnp.einsum("shk,skc->shc", p, rows[..., :kvr],
                                   preferred_element_type=f32).astype(x.dtype)
                    rows_read = jnp.minimum(n_valid, K)
            with part("project"):
                o = jnp.einsum("shc,chv->shv", o, wkb[..., n:],
                               preferred_element_type=f32).astype(x.dtype)
                x = x + _mm(o.reshape(S, 1, H * dv), lw["wo"])
            seen = (act * jnp.stack([n_valid, jnp.minimum(n_valid, K),
                                     rows_read, tie])).sum(axis=1).astype(
                jnp.int32)
            counts = counts.at[:2].add(seen[:2]).at[6].add(seen[2]) \
                .at[8].add(seen[3])
        x, idx, router_scores, load = _ffn(c, lw, i, x, weight=act)
        new.append((ring_l, ring_i))
        if idx is not None:
            sel["experts"].append(idx)
            sel["router_scores"].append(router_scores)
        if load is not None:
            with part("experts"):
                counts = counts.at[2:5].add(load[:3])
                counts = counts.at[5].max(load[3]).at[7].add(load[4])
    with part("head"):
        logits = jnp.dot(_rms(x[:, 0], w["norm"], c.rms_norm_eps), w["head"],
                         preferred_element_type=f32)
    if want_selections:
        return logits, new, counts, sel
    return logits, new, counts


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
class _V32Block(HybridBlock):
    def __init__(self, c, index, dtype, grad_req):
        super().__init__()
        d, H = c.hidden_size, c.num_attention_heads
        n, r, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        Hi, Di = c.index_n_heads, c.index_head_dim
        winit = _FanInNormal()

        def par(name, shape, pinit=winit, ptype=dtype):
            setattr(self, name, Parameter(name, shape=shape, dtype=ptype,
                                          init=pinit, grad_req=grad_req))
        par("attn_norm", (d,), init.One())
        par("wq_a", (d, c.q_lora_rank))
        par("q_norm", (c.q_lora_rank,), init.One())
        par("wq_b", (c.q_lora_rank, H * (n + r)))
        par("wkv_a", (d, c.kv_lora_rank + r))
        par("kv_norm", (c.kv_lora_rank,), init.One())
        par("wkv_b", (c.kv_lora_rank, H * (n + dv)))
        par("wo", (H * dv, d))
        par("idx_wq_b", (c.q_lora_rank, Hi * Di))
        par("idx_wk", (d, Di))
        par("idx_knorm_w", (Di,), init.One())
        par("idx_knorm_b", (Di,), _FanInNormal(0.1))
        par("idx_w", (d, Hi))
        par("ffn_norm", (d,), init.One())
        if index < c.first_k_dense_replace:
            f = c.intermediate_size
            par("ffn_w1", (d, f))
            par("ffn_w3", (d, f))
            par("ffn_w2", (f, d))
        else:
            self.ffn = _moe.DroplessMoE(
                d, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, held=c.held, n_group=c.n_group,
                topk_group=c.topk_group,
                route_scale=c.routed_scaling_factor,
                shared_experts=c.n_shared_experts, dtype=dtype,
                weight_initializer=winit,
                bias_initializer=_FanInNormal(SELECT_BIAS_SIGMA),
                grad_req=grad_req)

    hybrid_forward = None


class DeepSeekV32LM(HybridBlock):
    """DeepSeek-V3.2-Exp as the generation engine serves it.

    ``config`` holds the published keys (:data:`V32_PUBLISHED`; what is
    given overrides), with ``n_routed_experts`` the router's width whatever
    is held.  ``held=(first, count)`` are the routed experts this chip
    computes (all, if None).  ``dtype`` is the type of the weights and
    the activations, ``cache_dtype`` that of both rings (``dtype`` if
    None); norms, the router, index scores and softmax are float32 inside
    whatever they are.  Parameters take no gradient: a served model of
    billions of parameters must not allocate them."""

    def __init__(self, config=None, held=None, dtype="bfloat16",
                 cache_dtype=None, **kwargs):
        super().__init__(**kwargs)
        merged = dict(V32_PUBLISHED)
        merged.update(config or {})
        c = self._cfg = types.SimpleNamespace(**{
            k: merged[k] for k in V32_PUBLISHED})
        c.held = tuple(held) if held is not None else (0, c.n_routed_experts)
        row = c.kv_lora_rank + c.qk_rope_head_dim
        c.latent_stride = -(-row // LATENT_ALIGN) * LATENT_ALIGN
        c.cache_dtype = np_dtype(dtype if cache_dtype is None
                                 else cache_dtype)
        self._dtype = dtype
        grad_req = "null"
        self.embed = Parameter("embed", shape=(c.vocab_size, c.hidden_size),
                               dtype=dtype, init=_FanInNormal(1.0),
                               grad_req=grad_req)
        self.layers = nn.HybridSequential()
        for i in range(c.num_hidden_layers):
            self.layers.add(_V32Block(c, i, dtype, grad_req))
        self.norm = Parameter("norm", shape=(c.hidden_size,), dtype=dtype,
                              init=init.One(), grad_req=grad_req)
        self.head = Parameter("head", shape=(c.hidden_size, c.vocab_size),
                              dtype=dtype, init=_FanInNormal(),
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
        rings: a latent row (at its stride, see :func:`_ring_row`) and an
        indexer key a position, no head axis."""
        c = self._cfg
        return [[("latent", (max_len, c.latent_stride), c.cache_dtype),
                 ("indexer", (max_len, c.index_head_dim), c.cache_dtype)]
                for _ in range(c.num_hidden_layers)]

    def raw_weights(self):
        """{dotted name: raw array} of the live parameters (tracers while
        a program is traced)."""
        return {name: unwrap(p.data())
                for name, p in self._collect_params_with_prefix().items()}

    # -- the three entry points ---------------------------------------------
    def forward(self, tokens, valid_length=None, index_topk=None,
                want_selections=False):
        """(B, L) ids -> (B, L, vocab) float32 logits, causal; with
        ``want_selections`` also what the indexer and the routers chose.
        ``valid_length`` is accepted for the protocol: under a causal mask
        no valid position sees a padded one."""
        jnp = _jnp()
        logits, _caches, sel = run_full(
            self._cfg, self.raw_weights(),
            unwrap(tokens).astype(jnp.int32), index_topk, want_selections)
        return (NDArray(logits), sel) if want_selections else NDArray(logits)

    hybrid_forward = None

    def prefill(self, tokens, valid_length=None, index_topk=None,
                probe=False):
        """Prompt pass: ``(logits (B, L, vocab), [(latent rows (B, L,
        stride), indexer keys (B, L, Di)) a layer])``, and with ``probe``
        :func:`run_full`'s selections."""
        jnp = _jnp()
        logits, caches, sel = run_full(
            self._cfg, self.raw_weights(),
            unwrap(tokens).astype(jnp.int32), index_topk, probe)
        out = (NDArray(logits), [tuple(NDArray(a) for a in layer)
                                 for layer in caches])
        return out + (sel,) if probe else out

    def decode_step(self, tokens, caches, position, active=None,
                    index_topk=None, probe=False):
        """One token a slot against the rings: ``(logits (S, vocab),
        rings', counts)``, the counts in :data:`STEP_COUNTERS`' order, and
        with ``probe`` :func:`decode`'s selections, a row a slot."""
        jnp = _jnp()
        logits, new, counts, *sel = decode(
            self._cfg, self.raw_weights(),
            unwrap(tokens).reshape(-1).astype(jnp.int32),
            [tuple(unwrap(r) for r in layer) for layer in caches],
            unwrap(position), None if active is None else unwrap(active),
            index_topk, probe)
        return (NDArray(logits), [tuple(NDArray(r) for r in layer)
                                  for layer in new], NDArray(counts), *sel)


def tiny_v32(vocab_size=96, dtype="float32", **kwargs):
    """A CPU-sized V3.2 for tests: every mechanism present (two kinds of
    layer, groups, held experts, an indexer whose top-k is below the test
    contexts), no width as published."""
    cfg = {"hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 24,
           "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
           "v_head_dim": 8, "index_n_heads": 4, "index_head_dim": 8,
           "index_topk": 8, "intermediate_size": 48,
           "moe_intermediate_size": 16, "n_routed_experts": 16,
           "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "vocab_size": vocab_size}
    cfg.update(kwargs.pop("config", {}))
    kwargs.setdefault("held", (0, 16))
    return DeepSeekV32LM(cfg, dtype=dtype, **kwargs)
