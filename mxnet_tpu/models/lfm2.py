"""LFM2-MoE (LiquidAI, ``model_type`` ``lfm2_moe``): gated short
convolutions beside grouped-query attention, and sigmoid top-k routing
over experts with no shared one.

The block, every layer: ``h += Op(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``;
a final RMSNorm and the head, which is the embedding (tied).  No bias
anywhere.  Weights are stored [in, out].

* **Short convolution** (``layer_types[i] == "conv"``).  ``[B, C, x] =
  split3(u W_in)``; ``z_t = sum_j w[j] (B * x)_{t - (K-1) + j}`` over
  ``K = conv_L_cache`` taps, depthwise and causal; ``Op(u) = (C * z)
  W_out``.  **A slot's cache is the last ``K`` rows of ``B * x``**: a
  state with no position axis.  A decode step shifts it and appends its
  row; a prefill hands it over *as of the prompt's valid length*, whatever
  the bucket it was padded to; a slot that sits out a step keeps it.
* **Grouped-query attention** (``"full_attention"``).  ``H`` query heads
  over ``KV`` key/value heads of ``D``, RMS norm on q and k per head,
  rotary halves on all of ``D``.  **A position's cache rows are k and v
  with the heads side by side** (``KV * D`` numbers each, no head axis: at
  8 x 64 four full lane tiles, so a row is written in place).
* **Experts.**  :func:`mxnet_tpu.parallel.moe.dropless_moe`, the layer
  ``DeepSeekV32LM`` runs, with one group and no shared expert; the leading
  ``num_dense_layers`` carry a dense SwiGLU instead.

The mathematics is in pure functions of a dict of raw weights
(:func:`run_full`, :func:`decode`), which :class:`LFM2MoeLM` calls with its
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
from ..base import np_dtype
from ..ndarray.ndarray import NDArray, unwrap
from ..parallel import moe as _moe
from .parts import (DrawnBias as _DrawnBias, FanInNormal,
                    grouped_ring_attend, matmul as _mm, part,
                    rms_norm as _rms, rope as _rope, sub_weights as _sub)

__all__ = ["LFM2MoeLM", "LFM2_PUBLISHED", "tiny_lfm2", "run_full", "decode",
           "STEP_COUNTERS"]

# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json
LFM2_PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "conv_L_cache": 3, "intermediate_size": 11776,
    "moe_intermediate_size": 1536, "num_experts": 64,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1,
    "num_hidden_layers": 40, "num_dense_layers": 2,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "vocab_size": 65536, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
}

# the publisher's guard of the gates' normaliser: s / (sum s + 1e-6)
GATE_NORM_EPS = 1e-6

# the selection bias (``expert_bias``) of a model built from a seed: drawn
# at this scale so that it is exercised (a trained checkpoint carries its
# own)
EXPERT_BIAS_SIGMA = 0.01

# queries a block of the full forward's attention, so that the scores of
# 32 heads over a served sequence of thousands are never whole
QUERY_BLOCK = 512

# what a decode step counts on the device, over the active slots: (name,
# help), in the order of :func:`decode`'s counts
STEP_COUNTERS = (
    ("routed_pairs", "(token, expert) pairs the routers chose"),
    ("experts_touched", "held experts with a token, summed over expert "
                        "layers and steps"),
    ("expert_load_max", "largest load of a held expert in a step (over "
                        "the layers), summed over steps"),
    ("attn_valid_positions", "cached positions the attention layers read, "
                             "summed over slots and layers"),
    ("expert_rows_computed", "rows one grouped product over the held "
                             "experts multiplied (row tiles visited x tile "
                             "rows), summed over the expert layers: over "
                             "the held pairs, the product's redundancy"),
    ("kv_rows_read", "rows of the k ring (and as many of the v ring) the "
                     "attention read, summed over slots and layers: whole "
                     "blocks up to each slot's valid positions where the "
                     "kernel ran, the whole ring where the einsums did"),
)


def _jnp():
    import jax.numpy as jnp
    return jnp


def _angles(c, pos):
    """cos and sin [..., 1, D / 2] of positions ``pos`` [...], against
    [..., heads, D]."""
    jnp = _jnp()
    D = c.head_dim
    inv = 1.0 / float(c.rope_theta) ** (
        onp.arange(0, D, 2, dtype=onp.float64) / D)
    ang = pos.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv.astype(onp.float32))
    return jnp.cos(ang), jnp.sin(ang)


def _qkv(c, w, h, pos):
    """q [..., H, D], k and v [..., KV, D] of the stream ``h`` [..., d]
    (its pre-norm is here) at ``pos`` [...]: q and k normed per head, then
    rotated."""
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lead = h.shape[:-1]
    with part("project"):
        x = _rms(h, w["op_norm"], c.norm_eps)
        cos, sin = _angles(c, pos)
        q = _rms(_mm(x, w["wq"]).reshape(lead + (H, D)), w["q_norm"],
                 c.norm_eps)
        k = _rms(_mm(x, w["wk"]).reshape(lead + (KV, D)), w["k_norm"],
                 c.norm_eps)
        v = _mm(x, w["wv"]).reshape(lead + (KV, D))
        return _rope(q, cos, sin, False), _rope(k, cos, sin, False), v


def _attn_full(c, w, h, pos):
    """Causal attention over a whole sequence [B, L, d], in blocks of
    queries.  Returns ``(the stream ``h`` with its output added, k rows
    [B, L, KV * D], v rows)``: the rows as the rings store them."""
    import jax
    jnp = _jnp()
    f32 = jnp.float32
    B, L, _ = h.shape
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    G = H // KV
    with part("attention"):
        q, k, v = _qkv(c, w, h, pos)
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
        with part("project"):
            o = jnp.moveaxis(o, 0, 1).reshape(B, L, H * D)
            return h + _mm(o, w["wo"]), k.reshape(B, L, KV * D), \
                v.reshape(B, L, KV * D)


def _conv_taps(w, rows):
    """The tap-weighted sum of ``rows`` (one array a tap, oldest first,
    each [..., d]) in float32."""
    jnp = _jnp()
    taps = w["conv_w"].astype(jnp.float32)
    z = rows[0].astype(jnp.float32) * taps[0]
    for j in range(1, len(rows)):
        z = z + rows[j].astype(jnp.float32) * taps[j]
    return z


def _conv_full(c, w, h, valid_length):
    """The gated short convolution over a whole sequence [B, L, d].
    Returns ``(the stream ``h`` with its output added, state [B, K, d])``:
    the last ``K`` rows of ``B * x`` before position ``valid_length`` [B]
    (zeros before the first)."""
    import jax
    jnp = _jnp()
    K = c.conv_L_cache
    with part("conv"):
        x = _rms(h, w["op_norm"], c.norm_eps)
        b, gate, xx = jnp.split(_mm(x, w["conv_in"]), 3, axis=-1)
        bx = b * xx
        L = bx.shape[1]
        # K zeros in front: row t of bx is row K + t
        padded = jnp.pad(bx, ((0, 0), (K, 0), (0, 0)))
        z = _conv_taps(w, [padded[:, 1 + j:1 + j + L] for j in range(K)])
        out = _mm(gate * z.astype(x.dtype), w["conv_out"])
        state = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, K, axis=0))(padded, valid_length)
        return h + out, state


def _ffn(c, w, i, h, weight=None):
    """``(h + y, idx, scores, load)`` of layer ``i``'s feed-forward on the
    stream ``h`` [..., d], its pre-norm and its residual add with it: the
    last three None in a dense layer."""
    dense = i < c.num_dense_layers
    with part("ffn" if dense else "experts"):
        x2d = _rms(h, w["ffn_norm"], c.norm_eps).reshape(-1, h.shape[-1])
        if dense:
            y = _moe.swiglu(x2d, w["ffn_w1"], w["ffn_w3"], w["ffn_w2"])
            return h + y.astype(h.dtype).reshape(h.shape), None, None, None
        first, count = c.held
        y, idx, _gates, scores = _moe.dropless_moe(
            x2d, _sub(w, "ffn."), k=c.num_experts_per_tok, first=first,
            route_scale=c.routed_scaling_factor, norm_eps=GATE_NORM_EPS)
        load = _jnp().append(
            _moe.held_load(idx, first, count, weight),
            _moe.rows_computed(idx, first, w["ffn.held_w1"]))
        return h + y.astype(h.dtype).reshape(h.shape), idx, scores, load


def _head(c, w, x):
    jnp = _jnp()
    with part("head"):
        return jnp.einsum("...d,vd->...v", _rms(x, w["norm"], c.norm_eps),
                          w["embed"], preferred_element_type=jnp.float32)


def run_full(c, w, tokens, valid_length=None, want_selections=False):
    """The full causal forward over ``tokens`` [B, L], no cache.  Returns
    ``(logits [B, L, V] float32, [what each layer's cache holds of it],
    selections or None)``: a conv layer's ``(state [B, K, d],)`` as of
    ``valid_length`` [B] (the whole length, if None), an attention layer's
    ``(k rows, v rows)`` [B, L, KV * D]; selections are ``{"experts":
    [idx [B*L, k] an expert layer], "router_scores": [[B*L, E] an expert
    layer]}``."""
    jnp = _jnp()
    B, L = tokens.shape
    with part("embed"):
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
        vl = jnp.full((B,), L, jnp.int32) if valid_length is None \
            else valid_length.reshape(B).astype(jnp.int32)
        x = w["embed"][tokens]
    caches, sel = [], {"experts": [], "router_scores": []}
    for i, kind in enumerate(c.layer_types):
        lw = _sub(w, f"layers.{i}.")
        if kind == "conv":
            x, state = _conv_full(c, lw, x, vl)
            with part("conv"):
                caches.append((state.astype(c.cache_dtype),))
        else:
            x, k, v = _attn_full(c, lw, x, pos)
            with part("attention"), part("ring_write"):
                caches.append((k.astype(c.cache_dtype),
                               v.astype(c.cache_dtype)))
        x, idx, scores, _load = _ffn(c, lw, i, x)
        if idx is not None:
            sel["experts"].append(idx)
            sel["router_scores"].append(scores)
    return _head(c, w, x), caches, (sel if want_selections else None)


def _attn_step(c, w, h, ring_k, ring_v, pos, act):
    """One position a slot of the stream ``h`` [S, d] (attention's
    pre-norm and its residual add are here) against the rings
    [S, M, KV * D]: the new rows
    land at ``pos % M`` of the active slots (one scatter a ring), and
    every query head attends over its slot's valid positions
    (:func:`parts.grouped_ring_attend`: on one TPU a kernel over the
    slot's valid blocks, else einsums over the whole rings).  Returns
    ``(h + out [S, d], rings, positions valid [S], ring rows read [S])``."""
    jnp = _jnp()
    S, M, W = ring_k.shape
    with part("attention"):
        q, k, v = _qkv(c, w, h, pos)
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
        with part("project"):
            return h + _mm(o.astype(h.dtype), w["wo"]), ring_k, ring_v, \
                n_valid, rows_read


def _conv_step(c, w, h, state, act):
    """One position a slot of the stream ``h`` [S, d] (the convolution's
    pre-norm and its residual add are here) against the state [S, K, d]:
    shifted by one row with this step's ``B * x`` appended, in the active
    slots; the others keep theirs.  Returns ``(h + out [S, d], state)``."""
    jnp = _jnp()
    K = c.conv_L_cache
    with part("conv"):
        u = _rms(h, w["op_norm"], c.norm_eps)
        b, gate, xx = jnp.split(_mm(u, w["conv_in"]), 3, axis=-1)
        bx = b * xx
        z = _conv_taps(w, [state[:, j].astype(u.dtype) for j in range(1, K)]
                       + [bx])
        shifted = jnp.concatenate(
            [state[:, 1:], bx[:, None].astype(state.dtype)], axis=1)
        state = jnp.where(act[:, None, None] > 0, shifted, state)
        return h + _mm(gate * z.astype(u.dtype), w["conv_out"]), state


def decode(c, w, tok, caches, pos, active=None, want_selections=False):
    """One token a slot, ``tok`` [S] at ``pos`` [S], through ``caches`` =
    [(state [S, K, d],) or (k ring [S, M, KV * D], v ring) a layer].
    Returns ``(logits [S, V] float32, caches, counts [len(STEP_COUNTERS)]
    int32)``, and with ``want_selections`` a fourth: :func:`run_full`'s
    selections for this one position a slot."""
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
        if kind == "conv":
            x, state = _conv_step(c, lw, x, caches[i][0], act)
            new.append((state,))
        else:
            x, ring_k, ring_v, n_valid, rows_read = _attn_step(
                c, lw, x, *caches[i], pos, act)
            new.append((ring_k, ring_v))
            with part("attention"):
                counts = counts.at[3].add((act * n_valid).sum())
                counts = counts.at[5].add((act * rows_read).sum())
        x, idx, scores, load = _ffn(c, lw, i, x, weight=act)
        if idx is not None:
            sel["experts"].append(idx)
            sel["router_scores"].append(scores)
            with part("experts"):
                counts = counts.at[0].add(load[0])
                counts = counts.at[1].add(load[2])
                counts = counts.at[2].max(load[3]).at[4].add(load[4])
    logits = _head(c, w, x)
    if want_selections:
        return logits, new, counts, sel
    return logits, new, counts


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
class _LFM2Block(HybridBlock):
    def __init__(self, c, index, dtype, grad_req):
        super().__init__()
        d, D = c.hidden_size, c.head_dim
        H, KV = c.num_attention_heads, c.num_key_value_heads
        winit = FanInNormal()

        def par(name, shape, pinit=winit):
            setattr(self, name, Parameter(name, shape=shape, dtype=dtype,
                                          init=pinit, grad_req=grad_req))
        par("op_norm", (d,), init.One())
        if c.layer_types[index] == "conv":
            par("conv_in", (d, 3 * d))
            # taps oldest first, a row each: [K, d] keeps d on the lanes
            par("conv_w", (c.conv_L_cache, d),
                FanInNormal(c.conv_L_cache ** -0.5))
            par("conv_out", (d, d))
        else:
            par("wq", (d, H * D))
            par("wk", (d, KV * D))
            par("wv", (d, KV * D))
            par("wo", (H * D, d))
            par("q_norm", (D,), init.One())
            par("k_norm", (D,), init.One())
        par("ffn_norm", (d,), init.One())
        if index < c.num_dense_layers:
            f = c.intermediate_size
            par("ffn_w1", (d, f))
            par("ffn_w3", (d, f))
            par("ffn_w2", (f, d))
        else:
            self.ffn = _moe.DroplessMoE(
                d, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, held=c.held,
                route_scale=c.routed_scaling_factor, shared_experts=0,
                norm_eps=GATE_NORM_EPS, dtype=dtype, weight_initializer=winit,
                bias_initializer=_DrawnBias(EXPERT_BIAS_SIGMA),
                grad_req=grad_req)

    hybrid_forward = None


class LFM2MoeLM(HybridBlock):
    """LFM2-MoE as the generation engine serves it.

    ``config`` holds the published keys (:data:`LFM2_PUBLISHED`; what is
    given overrides), with ``num_experts`` the router's width whatever is
    held.  ``held=(first, count)`` are the routed experts this chip
    computes (all, if None).  ``dtype`` is the type of the weights and the
    activations, ``cache_dtype`` that of the rings and the conv states
    (``dtype`` if None); norms, the router, the taps' sum and softmax are
    float32 inside whatever they are.  The head is the embedding.
    Parameters take no gradient: a served model of billions of parameters
    must not allocate them."""

    def __init__(self, config=None, held=None, dtype="bfloat16",
                 cache_dtype=None, **kwargs):
        super().__init__(**kwargs)
        merged = dict(LFM2_PUBLISHED)
        merged.update(config or {})
        c = self._cfg = types.SimpleNamespace(**{
            k: merged[k] for k in LFM2_PUBLISHED})
        c.layer_types = tuple(c.layer_types)
        if len(c.layer_types) != c.num_hidden_layers or \
                set(c.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types {c.layer_types} must name "
                f"{c.num_hidden_layers} layers, each conv or full_attention")
        c.rope_theta = c.rope_parameters["rope_theta"]
        c.head_dim = c.hidden_size // c.num_attention_heads
        c.held = tuple(held) if held is not None else (0, c.num_experts)
        c.cache_dtype = np_dtype(dtype if cache_dtype is None
                                 else cache_dtype)
        grad_req = "null"
        # the head too, stored [out, in]: drawn at the head's fan-in.  At
        # scale 1 every token's largest logit would be its own input's
        self.embed = Parameter("embed", shape=(c.vocab_size, c.hidden_size),
                               dtype=dtype,
                               init=FanInNormal(c.hidden_size ** -0.5),
                               grad_req=grad_req)
        self.layers = nn.HybridSequential()
        for i in range(c.num_hidden_layers):
            self.layers.add(_LFM2Block(c, i, dtype, grad_req))
        self.norm = Parameter("norm", shape=(c.hidden_size,), dtype=dtype,
                              init=init.One(), grad_req=grad_req)

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
        keeps a slot: a conv layer one state of ``conv_L_cache`` rows, no
        position axis; an attention layer a key ring and a value ring of
        ``max_len`` positions, a row the heads side by side."""
        c = self._cfg
        row = c.num_key_value_heads * c.head_dim
        return [[("conv", (c.conv_L_cache, c.hidden_size), c.cache_dtype)]
                if kind == "conv" else
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
        ``want_selections`` also what the routers chose.  ``valid_length``
        is accepted for the protocol: no valid position sees a padded one
        under a causal mask or a causal convolution."""
        jnp = _jnp()
        logits, _caches, sel = run_full(
            self._cfg, self.raw_weights(), unwrap(tokens).astype(jnp.int32),
            want_selections=want_selections)
        return (NDArray(logits), sel) if want_selections else NDArray(logits)

    hybrid_forward = None

    def prefill(self, tokens, valid_length=None, probe=False):
        """Prompt pass: ``(logits (B, L, vocab), [(state,) or (k rows, v
        rows) a layer])``, the states as of ``valid_length``; with
        ``probe`` :func:`run_full`'s selections besides."""
        jnp = _jnp()
        logits, caches, sel = run_full(
            self._cfg, self.raw_weights(), unwrap(tokens).astype(jnp.int32),
            None if valid_length is None else unwrap(valid_length), probe)
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


def tiny_lfm2(vocab_size=96, dtype="float32", **kwargs):
    """A CPU-sized LFM2-MoE for tests: every mechanism present (both kinds
    of operator, a dense and expert layers, grouped heads), no width as
    published."""
    cfg = {"hidden_size": 32, "num_attention_heads": 4,
           "num_key_value_heads": 2, "intermediate_size": 48,
           "moe_intermediate_size": 16, "num_experts": 16,
           "num_experts_per_tok": 4, "num_hidden_layers": 5,
           "num_dense_layers": 1,
           "layer_types": ["conv", "full_attention", "conv", "conv",
                           "full_attention"],
           "vocab_size": vocab_size}
    cfg.update(kwargs.pop("config", {}))
    return LFM2MoeLM(cfg, dtype=dtype, **kwargs)
