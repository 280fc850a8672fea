"""Solar Open 2 (Upstage, ``model_type`` ``solar_open2``): gated delta-rule
mixers (KDA, the "Kimi delta attention" of Kimi Linear, arXiv 2510.26692)
beside gated softmax attention with no position signal, and sigmoid top-k
routing over experts with a shared one, in every layer.

The block, every layer: ``h += Mixer(RMSNorm(h))``, ``h += MoE(RMSNorm(h))``;
a final RMSNorm and a head of its own.  No bias anywhere.  Weights are
stored [in, out].  The layers named in ``gqa_layers`` attend; the others
are KDA.

* **KDA** (``x`` the normed stream, ``H`` heads of ``K = V`` numbers).
  ``q, k, v = SiLU(causal depthwise conv4(x W_qkv))``; q and k L2-normed per
  head, q scaled by ``K ** -0.5``.  The log decay ``g = -exp(A_log) *
  softplus(x W_f_down W_f_up + dt_bias)`` a head and key channel; ``beta =
  2 sigmoid(x W_b)`` a head (``kda_allow_neg_eigval``: in (0, 2), so the
  transition may have negative eigenvalues).  A head's state ``S`` [K, V],
  float32: ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t`` (:func:`parts.delta_rule_chunked` over a
  prompt, :func:`parts.delta_rule_step` a decode step); ``y = (RMSNorm_head(o)
  * sigmoid(x W_g_down W_g_up)) W_o``.  **A slot keeps two states with no
  position axis**: the last three rows of ``x W_qkv`` (the convolution's)
  and ``S`` a head.  A prefill hands both over *as of the prompt's valid
  length* (past it ``beta = 0`` and ``g = 0``, so the state passes through
  the bucket's padding unchanged); a slot that sits out a step keeps them.
* **Gated GQA** (``gqa_layers``).  ``H`` query heads over ``KV`` key/value
  heads of ``D``, no rotation and no norm on q or k; ``y = (attn * sigmoid(x
  W_gate)) W_o``.  A position's cache rows are k and v with the heads side
  by side, as in ``lfm2.py``.
* **Experts.**  :func:`mxnet_tpu.parallel.moe.dropless_moe` with a shared
  expert: sigmoid scores over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of ``score + bias``, gates ``s / sum s``;
  this chip computes the experts it holds (``held``).

The mathematics is in pure functions of a dict of raw weights
(:func:`trunk`, :func:`decode`), which :class:`SolarOpen2LM` calls with its
own parameters; ``cache_spec`` tells the generation engine what each layer
keeps.
"""
from __future__ import annotations

import math
import types

import numpy as onp

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.parameter import Parameter
from .. import initializer as init
from .. import random as _random
from ..base import np_dtype
from ..ndarray.ndarray import NDArray, unwrap
from ..parallel import moe as _moe
from .parts import (DrawnBias as _DrawnBias, FanInNormal,
                    delta_rule_chunked, delta_rule_step, grouped_ring_attend,
                    l2_norm as _l2, matmul as _mm, output_gate, part,
                    rms_norm as _rms, short_conv, short_conv_step,
                    sub_weights as _sub)

__all__ = ["SolarOpen2LM", "SOLAR_OPEN2_PUBLISHED", "tiny_solar", "trunk",
           "head", "run_full", "decode", "STEP_COUNTERS", "DELTA_CHUNK"]

# https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json
SOLAR_OPEN2_PUBLISHED = {
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "gqa_layers": list(range(0, 48, 4)), "use_rope": False,
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "moe_intermediate_size": 1280,
    "n_routed_experts": 320, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "first_k_dense_replace": 0,
    "vocab_size": 196608, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
}

# positions a chunk of the prefill's delta-rule scan: its pairwise decay
# is [chunk, chunk, K] a head, its triangular system chunk x chunk
DELTA_CHUNK = 32

# the guard of the L2 norm of q and k (the fla library's l2norm)
L2_EPS = 1e-6

# the selection bias of a model built from a seed: drawn at this scale so
# that it is exercised (a trained checkpoint carries its own)
EXPERT_BIAS_SIGMA = 0.01

# queries a block of the full forward's attention, so that the scores of
# 64 heads over a served sequence of thousands are never whole
QUERY_BLOCK = 512

# what a decode step counts on the device, over the active slots: (name,
# help), in the order of :func:`decode`'s counts
STEP_COUNTERS = (
    ("routed_pairs", "(token, expert) pairs the routers chose"),
    ("routed_pairs_held", "of those, pairs whose expert is held here"),
    ("experts_touched", "held experts with a token, summed over expert "
                        "layers and steps"),
    ("expert_load_max", "largest load of a held expert in a step (over "
                        "the layers), summed over steps"),
    ("expert_rows_computed", "rows one grouped product over the held "
                             "experts multiplied (row tiles visited x tile "
                             "rows), summed over the expert layers"),
    ("attn_valid_positions", "cached positions the attention layers read, "
                             "summed over slots and layers"),
    ("kv_rows_read", "rows of the k ring (and as many of the v ring) the "
                     "attention read, summed over slots and layers: whole "
                     "blocks up to each slot's valid positions where the "
                     "kernel ran, the whole ring where the einsums did"),
    ("delta_state_kib", "KiB of delta-rule state the step read and wrote "
                        "(each once), summed over slots and KDA layers"),
    ("delta_state_kib_moved", "KiB of delta-rule state the step actually "
                              "read and wrote, summed over slots and KDA "
                              "layers: twice the state where the kernel "
                              "ran, three times where XLA's two fusions "
                              "did"),
)


def _jnp():
    import jax.numpy as jnp
    return jnp


def _kda_inputs(c, w, x, conv_rows):
    """What a KDA layer computes from its normed input ``x`` [..., d] and
    its convolved projection ``conv_rows`` [..., 3 H K] float32 (before the
    SiLU): q and k [..., H, K] normed (q scaled), v [..., H, V], the log
    decay [..., H, K] and beta [..., H], all float32."""
    import jax
    jnp = _jnp()
    f32 = jnp.float32
    H, K = c.kda_heads, c.kda_head_dim
    lead = x.shape[:-1]
    with part("short_conv"):
        qkv = jax.nn.silu(conv_rows)
    with part("scan"):
        q, k, v = (a.reshape(lead + (H, K))
                   for a in jnp.split(qkv, 3, axis=-1))
        q = _l2(q, L2_EPS) * K ** -0.5
        k = _l2(k, L2_EPS)
        f = jnp.dot(_mm(x, w["f_down"]), w["f_up"],
                    preferred_element_type=f32)
        g = -jnp.exp(w["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            f.reshape(lead + (H, K)) + w["dt_bias"].astype(f32).reshape(H, K))
        beta = jax.nn.sigmoid(jnp.dot(x, w["b_proj"],
                                      preferred_element_type=f32))
        if c.kda_allow_neg_eigval:
            beta = 2.0 * beta
        return q, k, v, g, beta


def _kda_output(c, w, h, x, o):
    """``h`` + the KDA layer's output from the rule's ``o`` [..., H, V]
    float32 and the normed input ``x``."""
    jnp = _jnp()
    lead = o.shape[:-2]
    with part("gate"):
        gate = _mm(_mm(x, w["g_down"]), w["g_up"])
        o = _rms(o, w["o_norm"], c.rms_norm_eps)
        o = output_gate(o.reshape(lead + (-1,)), gate)
    with part("project"):
        return h + _mm(o, w["wo"]).astype(h.dtype)


def _kda_full(c, w, h, valid_length):
    """A KDA layer over a whole sequence [B, L, d].  Returns ``(h + out,
    conv state [B, 3 * 3HK], delta state [B, H, K, V] float32, the rule's
    inputs)``, both states as of ``valid_length`` [B]; the inputs are
    ``(q, k, v, g, beta)`` [B, L, ...] float32 as the scan took them."""
    jnp = _jnp()
    f32 = jnp.float32
    B, L, _ = h.shape
    H, K = c.kda_heads, c.kda_head_dim
    with part("attention"):
        with part("project"):
            x = _rms(h, w["op_norm"], c.rms_norm_eps)
            proj = _mm(x, w["wqkv"])
        with part("short_conv"):
            z, conv_state = short_conv(proj, w["conv_w"], valid_length)
        q, k, v, g, beta = _kda_inputs(c, w, x, z)
        with part("scan"):
            # past the valid length the state passes through: beta = 0
            # (nothing written), g = 0 (nothing decays)
            valid = jnp.arange(L)[None, :] < valid_length[:, None]
            beta = jnp.where(valid[..., None], beta, 0.0)
            g = jnp.where(valid[..., None, None], g, 0.0)
            inputs = (q, k, v, g, beta)
            pad = -L % DELTA_CHUNK
            if pad:
                q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                              for a in (q, k, v, g))
                beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
            o, state = delta_rule_chunked(
                q, k, v, g, beta, jnp.zeros((B, H, K, K), f32), DELTA_CHUNK)
            o = o[:, :L]
        return _kda_output(c, w, h, x, o), conv_state, state, inputs


def _kda_step(c, w, h, conv_state, state, act):
    """One position a slot of the stream ``h`` [S, d] against its conv
    state [S, 3 * 3HK] and delta state [S, H, K, V]: both move on in the
    active slots, the others keep theirs.  Returns ``(h + out, conv state,
    delta state, passes over the delta state)``
    (:func:`parts.delta_rule_step`)."""
    with part("attention"):
        with part("project"):
            x = _rms(h, w["op_norm"], c.rms_norm_eps)
            proj = _mm(x, w["wqkv"])
        with part("short_conv"):
            z, conv_state = short_conv_step(proj, conv_state, w["conv_w"],
                                            act)
        q, k, v, g, beta = _kda_inputs(c, w, x, z)
        with part("scan"):
            o, state, passes = delta_rule_step(q, k, v, g, beta, state, act)
        return _kda_output(c, w, h, x, o), conv_state, state, passes


def _gqa_qkv(c, w, h):
    """The normed input ``x``, q [..., H, D], k and v [..., KV, D] of the
    stream ``h`` [..., d]: no rotation, no norm on q or k."""
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lead = h.shape[:-1]
    with part("project"):
        x = _rms(h, w["op_norm"], c.rms_norm_eps)
        return x, _mm(x, w["wq"]).reshape(lead + (H, D)), \
            _mm(x, w["wk"]).reshape(lead + (KV, D)), \
            _mm(x, w["wv"]).reshape(lead + (KV, D))


def _gqa_output(c, w, h, x, o):
    with part("gate"):
        o = output_gate(o, _mm(x, w["w_gate"]))
    with part("project"):
        return h + _mm(o, w["wo"]).astype(h.dtype)


def _gqa_full(c, w, h):
    """Causal gated attention over a whole sequence [B, L, d], in blocks
    of queries.  Returns ``(h + out, k rows [B, L, KV * D], v rows)``: the
    rows as the rings store them."""
    import jax
    jnp = _jnp()
    f32 = jnp.float32
    B, L, _ = h.shape
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    G = H // KV
    with part("attention"):
        x, q, k, v = _gqa_qkv(c, w, h)
        q = q.reshape(B, L, KV, G, D)
        bq = math.gcd(L, QUERY_BLOCK)

        def block(i):
            rows = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
            causal = jnp.arange(L)[None, :] \
                <= (i * bq + jnp.arange(bq))[:, None]
            s = jnp.einsum("bqkgd,bmkd->bkgqm", rows, k,
                           preferred_element_type=f32) * D ** -0.5
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            o = jnp.einsum("bkgqm,bmkd->bqkgd", p.astype(v.dtype), v,
                           preferred_element_type=f32)
            return o.astype(h.dtype).reshape(B, bq, H * D)

        with part("attend"):
            o = jax.lax.map(block, jnp.arange(L // bq))
            o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * D)
        return _gqa_output(c, w, h, x, o), k.reshape(B, L, KV * D), \
            v.reshape(B, L, KV * D)


def _gqa_step(c, w, h, ring_k, ring_v, pos, act):
    """One position a slot of the stream ``h`` [S, d] against the rings
    [S, M, KV * D]: the new rows land at ``pos % M`` of the active slots
    (one scatter a ring), and every query head attends over its slot's
    valid positions (:func:`parts.grouped_ring_attend`).  Returns ``(h +
    out, rings, positions valid [S], ring rows read [S])``."""
    jnp = _jnp()
    S, M, W = ring_k.shape
    with part("attention"):
        x, q, k, v = _gqa_qkv(c, w, h)
        with part("ring_write"):
            at = jnp.where(act > 0, pos % M, M)  # M: out of range, dropped
            slots = jnp.arange(S)
            ring_k = ring_k.at[slots, at].set(
                k.reshape(S, W).astype(ring_k.dtype), mode="drop")
            ring_v = ring_v.at[slots, at].set(
                v.reshape(S, W).astype(ring_v.dtype), mode="drop")
        with part("attend"):
            n_valid = jnp.minimum(pos + 1, M)
            o, rows_read = grouped_ring_attend(q, ring_k, ring_v, n_valid)
        return _gqa_output(c, w, h, x, o.astype(h.dtype)), ring_k, ring_v, \
            n_valid, rows_read


def _ffn(c, w, h, weight=None):
    """``(h + y, idx, scores, load)`` of a layer's experts on the stream
    ``h`` [..., d], their pre-norm and their residual add with them."""
    first, count = c.held
    with part("experts"):
        x2d = _rms(h, w["ffn_norm"], c.rms_norm_eps).reshape(-1, h.shape[-1])
        y, idx, _gates, scores = _moe.dropless_moe(
            x2d, _sub(w, "ffn."), k=c.num_experts_per_tok, first=first,
            route_scale=c.routed_scaling_factor)
        load = _jnp().append(
            _moe.held_load(idx, first, count, weight),
            _moe.rows_computed(idx, first, w["ffn.held_w1"]))
        return h + y.astype(h.dtype).reshape(h.shape), idx, scores, load


def trunk(c, w, tokens, valid_length=None, want_selections=False):
    """The layers over ``tokens`` [B, L], no cache.  Returns ``(h [B, L, d]
    before the final norm, [what each layer's cache holds of it],
    selections or None)``: a KDA layer's ``(conv state [B, 3 * 3HK], delta
    state [B, H, K, V] float32)`` as of ``valid_length`` [B] (the whole
    length, if None), an attention layer's ``(k rows, v rows)`` [B, L, KV *
    D]; selections are ``{"experts": [idx [B*L, k] a layer],
    "router_scores": [[B*L, E] a layer], "delta_states": [[B, H, K, V] a
    KDA layer, as the slot keeps it], "delta_inputs": [(q, k, v, g, beta)
    [B, L, ...] float32 a KDA layer, as the scan took them]}``: a caller
    can hold the scan alone to the recurrence on the same inputs."""
    jnp = _jnp()
    B, L = tokens.shape
    with part("embed"):
        vl = jnp.full((B,), L, jnp.int32) if valid_length is None \
            else valid_length.reshape(B).astype(jnp.int32)
        x = w["embed"][tokens]
    caches = []
    sel = {"experts": [], "router_scores": [], "delta_states": [],
           "delta_inputs": []}
    for i, kind in enumerate(c.layer_types):
        lw = _sub(w, f"layers.{i}.")
        if kind == "kda":
            x, conv_state, state, inputs = _kda_full(c, lw, x, vl)
            with part("attention"), part("ring_write"):
                caches.append((conv_state.astype(c.cache_dtype),
                               state.astype(c.state_dtype)))
            # what the slot will hold, in its type
            sel["delta_states"].append(caches[-1][1])
            sel["delta_inputs"].append(inputs)
        else:
            x, k, v = _gqa_full(c, lw, x)
            with part("attention"), part("ring_write"):
                caches.append((k.astype(c.cache_dtype),
                               v.astype(c.cache_dtype)))
        x, idx, scores, _load = _ffn(c, lw, x)
        sel["experts"].append(idx)
        sel["router_scores"].append(scores)
    return x, caches, (sel if want_selections else None)


def head(c, w, x):
    """Logits [..., V] float32 of the stream ``x`` [..., d]."""
    jnp = _jnp()
    with part("head"):
        return jnp.dot(_rms(x, w["norm"], c.rms_norm_eps), w["head"],
                       preferred_element_type=jnp.float32)


def run_full(c, w, tokens, valid_length=None, want_selections=False,
             last=None):
    """The full causal forward, :func:`trunk` then :func:`head`: ``(logits
    [B, L, V] float32, caches, selections or None)``.  With ``last`` [B]
    the logits are [B, 1, V], those of position ``last - 1`` alone."""
    jnp = _jnp()
    x, caches, sel = trunk(c, w, tokens, valid_length, want_selections)
    if last is not None:
        with part("head"):
            x = jnp.take_along_axis(
                x, (last.reshape(-1, 1, 1) - 1).astype(jnp.int32), axis=1)
    return head(c, w, x), caches, sel


def decode(c, w, tok, caches, pos, active=None, want_selections=False):
    """One token a slot, ``tok`` [S] at ``pos`` [S], through ``caches`` =
    [(conv state [S, 3 * 3HK], delta state [S, H, K, V]) or (k ring [S, M,
    KV * D], v ring) a layer].  Returns ``(logits [S, V] float32, caches,
    counts [len(STEP_COUNTERS)] int32)``, and with ``want_selections`` a
    fourth: the experts and router scores of :func:`trunk`'s selections for
    this one position a slot."""
    jnp = _jnp()
    S = tok.shape[0]
    pos = pos.astype(jnp.int32)
    act = jnp.ones((S,), jnp.int32) if active is None \
        else (active > 0).astype(jnp.int32)
    with part("embed"):
        x = w["embed"][tok]                                  # [S, d]
    new = []
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    sel = {"experts": [], "router_scores": []}
    for i, kind in enumerate(c.layer_types):
        lw = _sub(w, f"layers.{i}.")
        if kind == "kda":
            x, conv_state, state, passes = _kda_step(c, lw, x, *caches[i],
                                                     act)
            new.append((conv_state, state))
            with part("attention"):
                kib = onp.prod(state.shape[1:]) * state.dtype.itemsize // 1024
                counts = counts.at[7].add(act.sum() * int(2 * kib))
                counts = counts.at[8].add(act.sum() * int(passes * kib))
        else:
            x, ring_k, ring_v, n_valid, rows_read = _gqa_step(
                c, lw, x, *caches[i], pos, act)
            new.append((ring_k, ring_v))
            with part("attention"):
                counts = counts.at[5].add((act * n_valid).sum())
                counts = counts.at[6].add((act * rows_read).sum())
        x, idx, scores, load = _ffn(c, lw, x, weight=act)
        sel["experts"].append(idx)
        sel["router_scores"].append(scores)
        with part("experts"):
            counts = counts.at[:3].add(load[:3])
            counts = counts.at[3].max(load[3]).at[4].add(load[4])
    logits = head(c, w, x)
    if want_selections:
        return logits, new, counts, sel
    return logits, new, counts


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
class _Drawn(init.Initializer):
    """A parameter drawn from the seed by ``make(key, shape)`` float32, in
    one jitted program, whatever its name (the base class reads a name
    that ends in ``bias`` as a zero)."""

    def __init__(self, make):
        super().__init__(make=make.__name__)
        self._make = make

    def init_array(self, name, shape, dtype):
        import jax
        return jax.jit(lambda key: self._make(key, tuple(shape)).astype(
            dtype))(_random.next_key())

    _init_weight = init_array


def _a_log(key, shape):
    """``log A``, ``A`` uniform in [1, 16] a head (fla's initialisation)."""
    import jax
    jnp = _jnp()
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):
    """softplus^-1 of ``dt``, log-uniform in [0.001, 0.1] (fla's
    initialisation): at a projection of 0 a channel keeps ``exp(-A dt)`` of
    its state a step."""
    import jax
    jnp = _jnp()
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class _SolarBlock(HybridBlock):
    def __init__(self, c, index, dtype, grad_req):
        super().__init__()
        d = c.hidden_size
        winit = FanInNormal()

        def par(name, shape, pinit=winit, pdtype=dtype):
            setattr(self, name, Parameter(name, shape=shape, dtype=pdtype,
                                          init=pinit, grad_req=grad_req))
        par("op_norm", (d,), init.One())
        if c.layer_types[index] == "kda":
            H, K = c.kda_heads, c.kda_head_dim
            par("wqkv", (d, 3 * H * K))
            # taps oldest first, a row each: [4, 3HK] keeps the channels on
            # the lanes
            par("conv_w", (c.conv_size, 3 * H * K),
                FanInNormal(c.conv_size ** -0.5))
            # the decay's and the gate's low-rank projections, rank K
            par("f_down", (d, K))
            par("f_up", (K, H * K))
            par("dt_bias", (H * K,), _Drawn(_dt_bias), "float32")
            par("A_log", (H,), _Drawn(_a_log), "float32")
            par("b_proj", (d, H))
            par("g_down", (d, K))
            par("g_up", (K, H * K))
            par("o_norm", (K,), init.One())
            par("wo", (H * K, d))
        else:
            H, KV, D = c.num_attention_heads, c.num_key_value_heads, \
                c.head_dim
            par("wq", (d, H * D))
            par("wk", (d, KV * D))
            par("wv", (d, KV * D))
            par("w_gate", (d, H * D))
            par("wo", (H * D, d))
        par("ffn_norm", (d,), init.One())
        self.ffn = _moe.DroplessMoE(
            d, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, held=c.held,
            route_scale=c.routed_scaling_factor,
            shared_experts=c.n_shared_experts, dtype=dtype,
            weight_initializer=winit,
            bias_initializer=_DrawnBias(EXPERT_BIAS_SIGMA),
            grad_req=grad_req)

    hybrid_forward = None


class SolarOpen2LM(HybridBlock):
    """Solar Open 2 as the generation engine serves it.

    ``config`` holds the published keys (:data:`SOLAR_OPEN2_PUBLISHED`;
    what is given overrides), with ``n_routed_experts`` the router's width
    whatever is held.  ``held=(first, count)`` are the routed experts this
    chip computes (all, if None).  ``dtype`` is the type of the weights and
    the activations, ``cache_dtype`` that of the key/value rings and the
    convolution's rows (``dtype`` if None), ``state_dtype`` that of the
    delta-rule state (float32; a ``cache_dtype`` of fewer bits never casts
    it).  Norms, the router, the decay, the rule and softmax are float32
    inside whatever they are.  Parameters take no gradient: a served model
    of billions of parameters must not allocate them."""

    def __init__(self, config=None, held=None, dtype="bfloat16",
                 cache_dtype=None, state_dtype="float32", **kwargs):
        super().__init__(**kwargs)
        merged = dict(SOLAR_OPEN2_PUBLISHED)
        merged.update(config or {})
        c = self._cfg = types.SimpleNamespace(**{
            k: merged[k] for k in SOLAR_OPEN2_PUBLISHED})
        la = c.linear_attn_config
        if c.use_rope or not c.use_gqa_gate or c.kda_use_full_proj \
                or c.first_k_dense_replace or c.tie_word_embeddings \
                or not c.norm_topk_prob or la["num_kv_heads"] is not None:
            raise ValueError(
                "SolarOpen2LM is written for NoPE gated attention, low-rank "
                "KDA gates, KDA heads of their own, experts in every layer, "
                "gates renormalised over the chosen and a head of its own")
        c.gqa_layers = tuple(c.gqa_layers)
        if set(c.gqa_layers) - set(range(c.num_hidden_layers)):
            raise ValueError(f"gqa_layers {c.gqa_layers} must lie in the "
                             f"{c.num_hidden_layers} layers")
        c.layer_types = tuple("gqa" if i in c.gqa_layers else "kda"
                              for i in range(c.num_hidden_layers))
        c.kda_heads, c.kda_head_dim = la["num_heads"], la["head_dim"]
        c.conv_size = la["short_conv_kernel_size"]
        c.held = tuple(held) if held is not None else (0, c.n_routed_experts)
        c.cache_dtype = np_dtype(dtype if cache_dtype is None
                                 else cache_dtype)
        c.state_dtype = np_dtype(state_dtype)
        grad_req = "null"
        self.embed = Parameter("embed", shape=(c.vocab_size, c.hidden_size),
                               dtype=dtype, init=FanInNormal(1.0),
                               grad_req=grad_req)
        self.layers = nn.HybridSequential()
        for i in range(c.num_hidden_layers):
            self.layers.add(_SolarBlock(c, i, dtype, grad_req))
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
        """For each layer the ``(kind, trailing shape, dtype)`` of what it
        keeps a slot: a KDA layer the convolution's last rows side by side
        and its delta-rule state a head, neither with a position axis; an
        attention layer a key ring and a value ring of ``max_len``
        positions, a row the heads side by side."""
        c = self._cfg
        H, K = c.kda_heads, c.kda_head_dim
        row = c.num_key_value_heads * c.head_dim
        return [[("conv", ((c.conv_size - 1) * 3 * H * K,), c.cache_dtype),
                 ("delta", (H, K, K), c.state_dtype)]
                if kind == "kda" else
                [("k", (max_len, row), c.cache_dtype),
                 ("v", (max_len, row), c.cache_dtype)]
                for kind in c.layer_types]

    def raw_weights(self):
        """{dotted name: raw array} of the live parameters (tracers while
        a program is traced)."""
        return {name: unwrap(p.data())
                for name, p in self._collect_params_with_prefix().items()}

    # -- the three entry points ---------------------------------------------
    def forward(self, tokens, valid_length=None, want_selections=False):
        """(B, L) ids -> (B, L, vocab) float32 logits, causal; with
        ``want_selections`` also what the routers chose and the delta
        states at the end.  ``valid_length`` is accepted for the protocol:
        no valid position sees a padded one."""
        jnp = _jnp()
        logits, _caches, sel = run_full(
            self._cfg, self.raw_weights(), unwrap(tokens).astype(jnp.int32),
            want_selections=want_selections)
        return (NDArray(logits), sel) if want_selections else NDArray(logits)

    hybrid_forward = None

    def prefill(self, tokens, valid_length=None, probe=False):
        """Prompt pass: ``(logits (B, 1, vocab) of position
        ``valid_length - 1`` alone (the last, if None), [(conv state, delta
        state) or (k rows, v rows) a layer])``, the states as of
        ``valid_length``; with ``probe`` :func:`trunk`'s selections."""
        jnp = _jnp()
        toks = unwrap(tokens).astype(jnp.int32)
        last = jnp.full((toks.shape[0],), toks.shape[1], jnp.int32) \
            if valid_length is None else unwrap(valid_length)
        logits, caches, sel = run_full(self._cfg, self.raw_weights(), toks,
                                       last, probe, last)
        out = (NDArray(logits), [tuple(NDArray(a) for a in layer)
                                 for layer in caches])
        return out + (sel,) if probe else out

    def decode_step(self, tokens, caches, position, active=None,
                    probe=False):
        """One token a slot against the caches: ``(logits (S, vocab),
        caches', counts)``, the counts in :data:`STEP_COUNTERS`' order, and
        with ``probe`` :func:`decode`'s selections, a row a slot."""
        jnp = _jnp()
        logits, new, counts, *sel = decode(
            self._cfg, self.raw_weights(),
            unwrap(tokens).reshape(-1).astype(jnp.int32),
            [tuple(unwrap(r) for r in layer) for layer in caches],
            unwrap(position), None if active is None else unwrap(active),
            probe)
        return (NDArray(logits), [tuple(NDArray(r) for r in layer)
                                  for layer in new], NDArray(counts), *sel)


def tiny_solar(vocab_size=96, dtype="float32", **kwargs):
    """A CPU-sized Solar Open 2 for tests: one whole period (attention,
    then three KDA layers), grouped heads, more experts than a token takes
    and fewer held than routed, a shared expert; no width as published."""
    cfg = {"hidden_size": 32, "num_hidden_layers": 4,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
           "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                                  "num_heads": 4, "num_kv_heads": None},
           "gqa_layers": [0], "moe_intermediate_size": 16,
           "n_routed_experts": 8, "num_experts_per_tok": 2,
           "vocab_size": vocab_size}
    cfg.update(kwargs.pop("config", {}))
    kwargs.setdefault("held", (0, 2))
    return SolarOpen2LM(cfg, dtype=dtype, **kwargs)
