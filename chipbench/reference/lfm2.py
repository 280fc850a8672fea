"""LFM2-MoE in plain float32 ``jax.numpy``: the full causal forward over
one sequence, no cache, no kernels, no batching: the short convolution as
three shifted copies of the sequence, grouped-query attention with the
keys and values repeated for their query heads, the experts as a loop
over the held set.  What prefill-then-decode through the conv states and
the key/value rings has to reproduce.

Written from the layer equations of the publisher's ``lfm2_moe`` modelling
code (``transformers``): pre-norm RMSNorm blocks; ``[B, C, x] = split3(u
W_in)``, a depthwise causal convolution of ``conv_L_cache`` taps over ``B *
x``, ``(C * z) W_out``; attention with RMS norm on q and k per head, rotary
halves over the whole head, scale ``head_dim ** -0.5``; a dense SwiGLU in
the leading ``num_dense_layers``, elsewhere sigmoid scores, the
``num_experts_per_tok`` largest of ``score + expert_bias``, gates ``s /
(sum s + 1e-6) * routed_scaling_factor``; a final RMSNorm and the tied
head.

Weights come in a dict under the program's names, each [in, out] (the
taps ``conv_w`` [K, d], oldest first), in whatever type they are stored;
every use upcasts to float32.  A layer is one jitted call and its experts
a scan, so that only one layer's matrices and one expert's are alive in
float32 beside the stored ones.

``selections`` lets the caller impose which experts each token takes (the
program's own), so that logits are compared on the same discrete choices;
without it the reference makes its own.  Either way it returns its own
router scores, from which a caller judges whether imposed choices were
defensible.
"""
import functools

import jax
import jax.numpy as jnp

# plumbing, not mathematics: a configuration as a cache key, a layer's weights
from .deepseek_v32 import _hashable, layer_weights

F32 = jnp.float32
GATE_NORM_EPS = 1e-6


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rotate_halves(x, angle):
    """Pairs (i, i + dim/2) of the last axis turned by ``angle``
    [..., dim/2]."""
    h = x.shape[-1] // 2
    a, b = x[..., :h], x[..., h:]
    c, s = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def short_conv(cfg, w, u):
    """One sequence [L, d] (already normed) through the gated short
    convolution."""
    up = lambda name: w[name].astype(F32)         # noqa: E731
    K, L = cfg["conv_L_cache"], u.shape[0]
    b, c, x = jnp.split(u @ up("conv_in"), 3, axis=-1)
    bx = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), b * x])
    taps = up("conv_w")                           # [K, d], oldest first
    z = sum(taps[j] * bx[j:j + L] for j in range(K))
    return (c * z) @ up("conv_out")


def attention(cfg, w, u, block):
    """One sequence [L, d] (already normed) through causal grouped-query
    attention, in blocks of queries."""
    up = lambda name: w[name].astype(F32)         # noqa: E731
    L = u.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // H
    eps = cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    angle = jnp.arange(L, dtype=F32)[:, None, None] \
        * theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)     # [L, 1, D/2]
    q = rms_norm((u @ up("wq")).reshape(L, H, D), up("q_norm"), eps)
    k = rms_norm((u @ up("wk")).reshape(L, KV, D), up("k_norm"), eps)
    v = (u @ up("wv")).reshape(L, KV, D)
    q, k = rotate_halves(q, angle), rotate_halves(k, angle)
    # key/value head j serves query heads j * H/KV .. (j + 1) * H/KV - 1
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    outs = []
    for lo in range(0, L, block):
        hi = min(L, lo + block)
        causal = jnp.arange(L)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v).reshape(hi - lo, H * D))
    return jnp.concatenate(outs) @ up("wo")


def expert(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(cfg, scores, bias):
    """The published gate: ``idx [T, k]``, the largest ``score + bias``
    first, from sigmoid scores [T, E]."""
    return jnp.argsort(-(scores + bias), axis=-1)[
        :, :cfg["num_experts_per_tok"]]


def feed_forward(cfg, w, x, imposed=None):
    """``(y, router scores or None, idx or None)`` on [L, d].  ``cfg``'s
    ``held`` = (first, count) names the experts whose part is computed
    (all of them, if absent)."""
    up = lambda name: w[name].astype(F32)         # noqa: E731
    if "ffn_w1" in w:
        return expert(x, up("ffn_w1"), up("ffn_w3"), up("ffn_w2")), None, \
            None
    scores = jax.nn.sigmoid(x @ up("ffn.gate_weight"))          # [L, E]
    idx = imposed if imposed is not None \
        else route(cfg, scores, up("ffn.select_bias"))
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = cfg["routed_scaling_factor"] * chosen \
        / (chosen.sum(-1, keepdims=True) + GATE_NORM_EPS)
    first, count = cfg.get("held", (0, scores.shape[-1]))

    def add(y, held):
        e, w1, w3, w2 = held
        g = jnp.where(idx == first + e, gates, 0.0).sum(-1)     # [L]
        return y + g[:, None] * expert(x, w1.astype(F32), w3.astype(F32),
                                       w2.astype(F32)), None
    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(count), w["ffn.held_w1"], w["ffn.held_w3"],
        w["ffn.held_w2"]))
    return y, scores, idx


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_key, kind, block, impose_experts):
    cfg = dict(cfg_key)
    cfg["rope_parameters"] = dict(cfg["rope_parameters"])

    def layer(w, x, experts):
        with jax.default_matmul_precision("highest"):
            eps = cfg["norm_eps"]
            u = rms_norm(x, w["op_norm"].astype(F32), eps)
            x = x + (short_conv(cfg, w, u) if kind == "conv"
                     else attention(cfg, w, u, block))
            y, scores, idx = feed_forward(
                cfg, w, rms_norm(x, w["ffn_norm"].astype(F32), eps),
                experts if impose_experts else None)
            return x + y, scores, idx
    return jax.jit(layer)


def forward(w, tokens, cfg, selections=None, block=256):
    """``tokens`` [L] -> dict: ``logits`` [L, V]; an expert layer:
    ``router_scores`` [L, E], ``experts`` [L, k].  ``cfg`` holds the
    published keys and optionally ``held``; ``selections`` =
    ``{"experts": [idx [L, k] an expert layer]}`` imposes the choices."""
    key = _hashable({k: v for k, v in cfg.items()
                     if isinstance(v, (int, float, str, dict, list, tuple))})
    out = {"router_scores": [], "experts": []}
    x = w["embed"][tokens].astype(F32)
    moe = 0
    for i, kind in enumerate(cfg["layer_types"]):
        lw = layer_weights(w, i)
        dense = "ffn_w1" in lw
        experts = None if dense or not selections \
            else selections["experts"][moe]
        x, scores, idx = _layer_program(key, kind, block,
                                        experts is not None)(lw, x, experts)
        if not dense:
            moe += 1
            out["router_scores"].append(scores)
            out["experts"].append(idx)
    with jax.default_matmul_precision("highest"):
        out["logits"] = rms_norm(x, w["norm"].astype(F32),
                                 cfg["norm_eps"]) @ w["embed"].astype(F32).T
    return out
