"""Keye-VL-2.0's language model in plain float32 ``jax.numpy``: the full
causal forward over one sequence, no cache, no kernels, no batching:
grouped-query attention with the keys and values repeated for their query
heads, the indexer's selection as a mask, the experts as a loop over the
held set.  What prefill-then-decode through the three rings has to
reproduce.

Written from the layer equations of ISSUE 35 (the catalog's ``config`` of
``Kwai-Keye/Keye-VL-2.0-30B-A3B`` and, for the selection, the lightning
indexer DeepSeek-V3.2-Exp publishes): pre-norm RMSNorm blocks; q and k
RMS-normalised per head, then rotated by **sectioned** angles (a position
is three numbers; frequency ``i`` turns by the axis whose section holds
it; halves paired); the indexer on the same normed input, ``I(t, s) =
sum_j w_j(t) ReLU(q^I_j(t) . k^I(s))`` with ``k^I`` through a LayerNorm and
both rotated over their whole head with the sections halved; a query
attends to the ``topk`` positions ``s <= t`` of largest ``I``; the router a
float32 softmax over all experts, the ``num_experts_per_tok`` largest,
gates renormalised over the chosen; a final RMSNorm and a head of its own.

Weights come in a dict under the program's names, each [in, out], in
whatever type they are stored; every use upcasts to float32.  A layer is a
few jitted calls (the projections, attention a block of queries at a
time, the experts as a scan), so that only one layer's matrices and one
expert's are alive in float32 beside the stored ones and nothing of size
[L, L] is ever whole unless ``whole`` asks for it.

``selections`` lets the caller impose which positions each query attends
to (``[L, K]`` indices, -1 for none) and which experts each token takes
(the program's own), so that logits are compared on the same discrete
choices; without it the reference makes its own.  Either way it returns
its own router scores and, of its own index scores, either all of them
(``whole``: [L, L], for small sizes) or what a caller needs to judge
imposed positions: the scores at those positions, each query's k-th
largest, and the scores' moments.
"""
import collections
import functools

import jax
import jax.numpy as jnp

# plumbing, not mathematics: a layer's weights out of the dict
from .deepseek_v32 import layer_weights

F32 = jnp.float32

Dims = collections.namedtuple(
    "Dims", "H KV D Hi Di theta sections index_sections eps per_token topk "
            "first count")


def dims_of(cfg, index_topk=None):
    """The numbers of the published keys that the equations use.
    ``cfg["held"]`` = (first, count) names the experts whose part is
    computed (all of them, if absent)."""
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("written for one indexer key head")
    sections = tuple(cfg["rope_scaling"]["mrope_section"])
    scale = cfg["head_dim"] // sa["indexer_head_dim"]
    first, count = cfg.get("held", (0, cfg["num_experts"]))
    return Dims(cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], sa["indexer_num_heads"],
                sa["indexer_head_dim"], float(cfg["rope_theta"]), sections,
                tuple(n // scale for n in sections), cfg["rms_norm_eps"],
                cfg["num_experts_per_tok"],
                sa["topk"] if index_topk is None else index_topk,
                first, count)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                                    + eps) * g + b


def angles(pos3, dim, theta, sections):
    """[L, dim / 2] from three-axis positions ``pos3`` [3, L]: frequency
    ``i`` is ``theta ** (-2 i / dim)`` and turns by the axis whose section
    holds it, the sections in chunks: the first ``sections[0]`` by the
    temporal axis, the next by the height, the rest by the width."""
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    owner = jnp.concatenate([jnp.full((n,), axis, jnp.int32)
                             for axis, n in enumerate(sections)])
    return pos3.astype(F32).T[:, owner] * freq


def rotate_halves(x, angle):
    """Pairs (i, i + dim/2) of the last axis turned by ``angle``
    [..., dim/2]."""
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


@functools.lru_cache(maxsize=None)
def _project(d):
    def project(w, x, pos3):
        """One sequence [L, hidden] (the stream) -> the normed input's q
        [L, H, D], k and v [L, H, D] (repeated for their query heads),
        q^I [L, Hi, Di], k^I [L, Di], w [L, Hi]."""
        with jax.default_matmul_precision("highest"):
            up = lambda name: w[name].astype(F32)         # noqa: E731
            L = x.shape[0]
            u = rms_norm(x, up("attn_norm"), d.eps)
            angle = angles(pos3, d.D, d.theta, d.sections)[:, None]
            q = rms_norm((u @ up("wq")).reshape(L, d.H, d.D), up("q_norm"),
                         d.eps)
            k = rms_norm((u @ up("wk")).reshape(L, d.KV, d.D), up("k_norm"),
                         d.eps)
            v = (u @ up("wv")).reshape(L, d.KV, d.D)
            q, k = rotate_halves(q, angle), rotate_halves(k, angle)
            # key/value head g serves query heads g * H/KV .. + H/KV - 1
            k = jnp.repeat(k, d.H // d.KV, axis=1)
            v = jnp.repeat(v, d.H // d.KV, axis=1)
            iangle = angles(pos3, d.Di, d.theta, d.index_sections)
            qi = rotate_halves((u @ up("idx_wq")).reshape(L, d.Hi, d.Di),
                               iangle[:, None])
            ki = rotate_halves(layer_norm(u @ up("idx_wk"),
                                          up("idx_knorm_w"),
                                          up("idx_knorm_b"), d.eps), iangle)
            wi = (u @ up("idx_w")) * (d.Hi ** -0.5 * d.Di ** -0.5)
            return q, k, v, qi, ki, wi
    return jax.jit(project)


@functools.lru_cache(maxsize=None)
def _attend(d, impose):
    def attend(q, k, v, qi, wi, ki, lo, chosen):
        """A block of queries ``q`` [bq, H, D] (the rows from ``lo`` on)
        over the whole sequence's keys.  Returns ``(out [bq, H * D], index
        scores [bq, L] with -inf above the diagonal, the mask attended
        under)``; ``chosen`` [bq, K] imposes the mask (-1: none)."""
        with jax.default_matmul_precision("highest"):
            bq, L = q.shape[0], k.shape[0]
            causal = jnp.arange(L)[None, :] <= (lo + jnp.arange(bq))[:, None]
            per_head = jnp.einsum("qhd,kd->qhk", qi, ki)
            score = (jax.nn.relu(per_head) * wi[:, :, None]).sum(1)
            score = jnp.where(causal, score, -jnp.inf)
            if impose:
                mask = jnp.zeros((bq, L), bool).at[
                    jnp.arange(bq)[:, None],
                    jnp.where(chosen >= 0, chosen, L)].set(True, mode="drop")
            else:
                mask = keep_largest(score, causal, d.topk)
            s = jnp.einsum("qhd,khd->hqk", q, k) * d.D ** -0.5
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            out = jnp.einsum("hqk,khd->qhd", p, v).reshape(bq, d.H * d.D)
            return out, score, mask
    return jax.jit(attend)


@functools.lru_cache(maxsize=None)
def _summarise(d):
    def summarise(score, chosen):
        """Of a block's index scores what judges imposed positions: the
        scores at them [bq, K] (-inf where none), each query's
        ``topk``-th largest (-inf with fewer valid), and the valid
        scores' sum, sum of squares and count."""
        valid = jnp.isfinite(score)
        at = jnp.take_along_axis(score, jnp.maximum(chosen, 0), axis=-1)
        at = jnp.where(chosen >= 0, at, -jnp.inf)
        kth = jnp.sort(score, axis=-1)[:, -min(d.topk, score.shape[-1])]
        clean = jnp.where(valid, score, 0.0)
        return at, kth, jnp.stack([clean.sum(), (clean ** 2).sum(),
                                   valid.sum().astype(F32)])
    return jax.jit(summarise)


def expert(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(d, probs):
    """The published gate: ``idx [T, k]``, the largest probability
    first."""
    return jnp.argsort(-probs, axis=-1)[:, :d.per_token]


def feed_forward(d, w, x, imposed=None):
    """``(y, router probabilities [L, E], idx [L, k])`` on [L, hidden]
    (already normed): a float32 softmax over all experts, the
    ``per_token`` largest, gates renormalised over the chosen, the held
    experts one at a time."""
    probs = jax.nn.softmax(x @ w["ffn.gate_weight"].astype(F32), axis=-1)
    idx = imposed if imposed is not None else route(d, probs)
    chosen = jnp.take_along_axis(probs, idx, axis=-1)
    gates = chosen / chosen.sum(-1, keepdims=True)

    def add(y, held):
        e, w1, w3, w2 = held
        g = jnp.where(idx == d.first + e, gates, 0.0).sum(-1)   # [L]
        return y + g[:, None] * expert(x, w1.astype(F32), w3.astype(F32),
                                       w2.astype(F32)), None
    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(d.count), w["ffn.held_w1"], w["ffn.held_w3"],
        w["ffn.held_w2"]))
    return y, probs, idx


@functools.lru_cache(maxsize=None)
def _finish(d, impose):
    def finish(w, x, attended, experts):
        """The stream after a layer: ``x + attended W_o``, then the
        experts on its norm."""
        with jax.default_matmul_precision("highest"):
            x = x + attended @ w["wo"].astype(F32)
            y, probs, idx = feed_forward(
                d, w, rms_norm(x, w["ffn_norm"].astype(F32), d.eps),
                experts if impose else None)
            return x + y, probs, idx
    return jax.jit(finish)


@functools.lru_cache(maxsize=None)
def _head(eps):
    def logits(norm, head_w, x):
        with jax.default_matmul_precision("highest"):
            return rms_norm(x, norm.astype(F32), eps) @ head_w.astype(F32)
    return jax.jit(logits)


def head(w, x, cfg):
    """Logits [..., V] of the stream ``x`` [..., hidden] (``forward``'s
    ``hidden``): rows of it, where [L, V] would not fit."""
    return _head(cfg["rms_norm_eps"])(w["norm"], w["head"], x)


def forward(w, tokens, cfg, positions=None, index_topk=None,
            selections=None, block=512, whole=True, logits=True):
    """``tokens`` [L] at ``positions`` [3, L] (a text token's three axes
    are its index, if None) -> dict: ``hidden`` [L, hidden] (before the
    final norm) and, with ``logits``, ``logits`` [L, V]; a layer:
    ``router_scores`` [L, E], ``experts`` [L, k]; with ``whole``
    ``index_scores`` [L, L] and ``positions`` (the mask attended under);
    without it, for imposed positions, ``index_at`` [L, K], ``index_kth``
    [L] and ``index_moments`` [3] (:func:`_summarise`).  ``selections`` =
    ``{"positions": [[L, K] indices or None a layer], "experts": [idx
    [L, k] a layer]}`` imposes the choices."""
    d = dims_of(cfg, index_topk)
    L = tokens.shape[0]
    pos3 = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (3, L)) \
        if positions is None else jnp.asarray(positions, jnp.int32)
    names = ("router_scores", "experts") + (
        ("index_scores", "positions") if whole
        else ("index_at", "index_kth", "index_moments"))
    out = {name: [] for name in names}
    x = w["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        chosen = selections["positions"][i] if selections else None
        experts = selections["experts"][i] if selections else None
        q, k, v, qi, ki, wi = _project(d)(lw, x, pos3)
        attend = _attend(d, chosen is not None)
        parts = []
        for lo in range(0, L, block):
            hi = min(L, lo + block)
            mine = None if chosen is None else chosen[lo:hi]
            o, score, mask = attend(q[lo:hi], k, v, qi[lo:hi], wi[lo:hi], ki,
                                    lo, mine)
            if whole:
                parts.append((o, score, mask))
            elif mine is not None:
                parts.append((o,) + _summarise(d)(score, mine))
            else:
                parts.append((o,))
        cols = list(zip(*parts))
        x, probs, idx = _finish(d, experts is not None)(
            lw, x, jnp.concatenate(cols[0]), experts)
        out["router_scores"].append(probs)
        out["experts"].append(idx)
        if whole:
            out["index_scores"].append(jnp.concatenate(cols[1]))
            out["positions"].append(jnp.concatenate(cols[2]))
        elif chosen is not None:
            out["index_at"].append(jnp.concatenate(cols[1]))
            out["index_kth"].append(jnp.concatenate(cols[2]))
            out["index_moments"].append(sum(cols[3]))
        else:
            for name in ("index_at", "index_kth", "index_moments"):
                out[name].append(None)
    out["hidden"] = x
    if logits:
        out["logits"] = head(w, x, cfg)
    return out
