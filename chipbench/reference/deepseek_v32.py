"""DeepSeek-V3.2-Exp in plain float32 ``jax.numpy``: the full causal
forward over one sequence, no cache, no kernels, keys and values expanded
from the latent row, the indexer's selection as a mask, the experts as a
loop over the held set.  What prefill-then-decode through the two rings
has to reproduce.

Follows the publisher's ``inference/model.py`` (MLA with ``q_lora`` and
``kv_lora``, YaRN rotary frequencies, the lightning indexer, ``noaux_tc``
group-limited routing).  Left out with the program under test: the
indexer's Hadamard rotation (orthogonal: no product changes) and every
FP8 quantisation (a storage choice).  The absent experts' part of an
expert layer is left out here as in the program.

Weights come in a dict under the program's names, each [in, out], in
whatever type they are stored; every use upcasts to float32.  A layer is
one jitted call, so that only one layer's float32 copies are alive, and
attention inside it runs in blocks of queries.

``selections`` lets the caller impose which positions each query attends
to and which experts each token takes (the program's own), so that logits
are compared on the same discrete choices; without it the reference makes
its own.  Either way it returns its own index scores and router scores,
from which a caller judges whether imposed choices were defensible.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp

F32 = jnp.float32


def rotary_frequencies(cfg):
    """YaRN (Peng et al. 2023) as the publisher applies it once the
    context exceeds the original one: a frequency that completes fewer
    than ``beta_slow`` turns over the original context is interpolated
    (divided by ``factor``), more than ``beta_fast`` is kept, and those
    between are blended linearly in the index of the frequency."""
    dim, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    out = []
    span = rs["original_max_position_embeddings"]

    def index_of(turns):
        return dim * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(index_of(rs["beta_fast"])), 0)
    hi = min(math.ceil(index_of(rs["beta_slow"])), dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    for j in range(dim // 2):
        f = theta ** (-2.0 * j / dim)
        interpolated = min(max((j - lo) / (hi - lo), 0.0), 1.0)
        out.append(f / rs["factor"] * interpolated + f * (1 - interpolated))
    return onp.asarray(out, onp.float32)


def attention_scale(cfg):
    rs = cfg["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return mscale * mscale / math.sqrt(cfg["qk_nope_head_dim"]
                                       + cfg["qk_rope_head_dim"])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                                    + eps) * g + b


def rotate_pairs(x, angle):
    """Pairs (2i, 2i+1) of the last axis turned by ``angle`` [..., dim/2]:
    MLA's pairing."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def rotate_halves(x, angle):
    """Pairs (i, i + dim/2): the indexer's pairing."""
    h = x.shape[-1] // 2
    a, b = x[..., :h], x[..., h:]
    c, s = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def keep_largest(scores, allowed, k):
    """Mask of the ``k`` largest of ``scores`` [..., N] among ``allowed``
    (all of them where there are no more than ``k``)."""
    if k >= scores.shape[-1]:
        return allowed
    masked = jnp.where(allowed, scores, -jnp.inf)
    kth = jnp.sort(masked, axis=-1)[..., -k][..., None]
    return allowed & (masked >= kth)


def attention(cfg, w, x, index_topk, imposed, block):
    """One sequence [L, d] (already normed).  Returns ``(out [L, d], index
    scores [L, L] with -inf above the diagonal, mask used [L, L])``."""
    L = x.shape[0]
    H = cfg["num_attention_heads"]
    n, r, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kvr, Hi, Di = cfg["kv_lora_rank"], cfg["index_n_heads"], \
        cfg["index_head_dim"]
    eps = cfg["rms_norm_eps"]
    up = lambda name: w[name].astype(F32)         # noqa: E731
    angle = jnp.arange(L, dtype=F32)[:, None] \
        * jnp.asarray(rotary_frequencies(cfg))[None, :]         # [L, r/2]

    c_q = rms_norm(x @ up("wq_a"), up("q_norm"), eps)
    q = (c_q @ up("wq_b")).reshape(L, H, n + r)
    q_nope, q_rope = q[..., :n], rotate_pairs(q[..., n:], angle[:, None])
    kv = x @ up("wkv_a")
    c_kv = rms_norm(kv[:, :kvr], up("kv_norm"), eps)
    k_rope = rotate_pairs(kv[:, kvr:], angle)                   # [L, r]
    kvb = (c_kv @ up("wkv_b")).reshape(L, H, n + dv)
    k = jnp.concatenate([kvb[..., :n],
                         jnp.broadcast_to(k_rope[:, None], (L, H, r))], -1)
    v = kvb[..., n:]
    q = jnp.concatenate([q_nope, q_rope], -1)                   # [L, H, n+r]

    qi = (c_q @ up("idx_wq_b")).reshape(L, Hi, Di)
    qi = jnp.concatenate([rotate_halves(qi[..., :r], angle[:, None]),
                          qi[..., r:]], -1)
    ki = layer_norm(x @ up("idx_wk"), up("idx_knorm_w"), up("idx_knorm_b"),
                    eps)
    ki = jnp.concatenate([rotate_halves(ki[:, :r], angle), ki[:, r:]], -1)
    wi = (x @ up("idx_w")) * (Hi ** -0.5 * Di ** -0.5)          # [L, Hi]

    scale = attention_scale(cfg)
    outs, index, masks = [], [], []
    for lo in range(0, L, block):
        hi = min(L, lo + block)
        causal = jnp.arange(L)[None, :] <= jnp.arange(lo, hi)[:, None]
        per_head = jnp.einsum("qhd,kd->qhk", qi[lo:hi], ki)
        score = (jax.nn.relu(per_head) * wi[lo:hi, :, None]).sum(1)
        score = jnp.where(causal, score, -jnp.inf)              # [q, L]
        mask = imposed[lo:hi] if imposed is not None \
            else keep_largest(score, causal, index_topk)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khv->qhv", p, v).reshape(hi - lo, H * dv))
        index.append(score)
        masks.append(mask)
    out = jnp.concatenate(outs) @ up("wo")
    return out, jnp.concatenate(index), jnp.concatenate(masks)


def expert(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(cfg, scores, bias):
    """The published gate: ``(idx [T, k], group scores [T, G])`` from
    sigmoid scores [T, E] and the selection bias."""
    T, E = scores.shape
    G, keep_groups = cfg["n_group"], cfg["topk_group"]
    biased = scores + bias
    grouped = biased.reshape(T, G, E // G)
    group_score = jnp.sort(grouped, -1)[..., -2:].sum(-1)
    kept = jnp.argsort(-group_score, axis=-1)[:, :keep_groups]
    allowed = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None],
                                         kept].set(True)
    masked = jnp.where(allowed[:, :, None], grouped, -jnp.inf).reshape(T, E)
    idx = jnp.argsort(-masked, axis=-1)[:, :cfg["num_experts_per_tok"]]
    return idx, group_score


def feed_forward(cfg, w, x, imposed):
    """``(y, router scores or None, idx or None)`` on [L, d]."""
    up = lambda name: w[name].astype(F32)         # noqa: E731
    if "ffn_w1" in w:
        return expert(x, up("ffn_w1"), up("ffn_w3"), up("ffn_w2")), None, \
            None
    scores = jax.nn.sigmoid(x @ up("ffn.gate_weight"))          # [L, E]
    idx = imposed if imposed is not None \
        else route(cfg, scores, up("ffn.select_bias"))[0]
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = cfg["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)
    first, count = cfg["held"]
    y = jnp.zeros_like(x)
    for e in range(count):                     # the experts held here
        g = jnp.where(idx == first + e, gates, 0.0).sum(-1)     # [L]
        y = y + g[:, None] * expert(
            x, w["ffn.held_w1"][e].astype(F32),
            w["ffn.held_w3"][e].astype(F32), w["ffn.held_w2"][e].astype(F32))
    if "ffn.shared_w1" in w:
        y = y + expert(x, up("ffn.shared_w1"), up("ffn.shared_w3"),
                       up("ffn.shared_w2"))
    return y, scores, idx


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_key, index_topk, block, impose_positions,
                   impose_experts):
    cfg = dict(cfg_key)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])

    def layer(w, x, positions, experts):
        with jax.default_matmul_precision("highest"):
            eps = cfg["rms_norm_eps"]
            a, index, mask = attention(
                cfg, w, rms_norm(x, w["attn_norm"].astype(F32), eps),
                index_topk, positions if impose_positions else None, block)
            x = x + a
            y, scores, idx = feed_forward(
                cfg, w, rms_norm(x, w["ffn_norm"].astype(F32), eps),
                experts if impose_experts else None)
            return x + y, index, mask, scores, idx
    return jax.jit(layer)


def _hashable(cfg):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else (tuple(v) if isinstance(v, list) else v))
                        for k, v in cfg.items()))


def layer_weights(w, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def forward(w, tokens, cfg, index_topk=None, selections=None, block=256):
    """``tokens`` [L] -> dict: ``logits`` [L, V]; a layer: ``index_scores``
    [L, L], ``positions`` (the mask attended under); an expert layer:
    ``router_scores`` [L, E], ``experts`` [L, k].  ``cfg`` holds the
    published keys and ``held`` = (first, count)."""
    index_topk = cfg["index_topk"] if index_topk is None else index_topk
    key = _hashable({k: v for k, v in cfg.items()
                     if isinstance(v, (int, float, str, dict, list, tuple))})
    out = {"index_scores": [], "positions": [], "router_scores": [],
           "experts": []}
    x = w["embed"][tokens].astype(F32)
    moe = 0
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        dense = "ffn_w1" in lw
        positions = selections["positions"][i] if selections else None
        experts = None if dense or not selections \
            else selections["experts"][moe]
        prog = _layer_program(key, index_topk, block, positions is not None,
                              experts is not None)
        x, index, mask, scores, idx = prog(lw, x, positions, experts)
        out["index_scores"].append(index)
        out["positions"].append(mask)
        if not dense:
            moe += 1
            out["router_scores"].append(scores)
            out["experts"].append(idx)
    with jax.default_matmul_precision("highest"):
        out["logits"] = rms_norm(x, w["norm"].astype(F32),
                                 cfg["rms_norm_eps"]) @ w["head"].astype(F32)
    return out
