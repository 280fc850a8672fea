"""Solar Open 2 in plain float32 ``jax.numpy``: the full causal forward over
one sequence, no cache, no kernels, no batching: the gated delta rule as
its recurrence, one position after another, the short convolution as four
shifted copies of the sequence, gated grouped-query attention with the
keys and values repeated for their query heads, the experts as a loop over
the held set.  What prefill-then-decode through the convolution's rows,
the delta-rule states and the key/value rings has to reproduce.

Written from the layer equations of the published configuration
(``upstage/Solar-Open2-250B``'s ``config.json``; KDA as Kimi Linear, arXiv
2510.26692, and the fla library's ``naive_recurrent_kda`` state it):
pre-norm RMSNorm blocks, eps ``rms_norm_eps``.

* KDA, ``u`` the normed stream: ``q, k, v = SiLU(conv4(u W_qkv))`` (causal,
  depthwise, zeros before the first position), q and k divided by their
  length a head (guard 1e-6), q times ``K ** -0.5``; ``g = -exp(A_log) *
  softplus(u W_f_down W_f_up + dt_bias)``; ``beta = 2 sigmoid(u W_b)``;
  a head's ``S`` [K, V] from zeros: ``S <- S * exp(g)`` by rows, ``S <- S +
  beta k (v - S^T k)^T``, ``o = S^T q``; ``y = (RMSNorm_head(o) * sigmoid(u
  W_g_down W_g_up)) W_o``.
* Gated attention: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``, no
  position signal, causal softmax at ``D ** -0.5`` over each head's
  key/value group; ``y = (attn * sigmoid(u W_gate)) W_o``.
* Experts: sigmoid scores over all experts, the ``num_experts_per_tok``
  largest of ``score + bias``, gates ``s / sum s * routed_scaling_factor``
  over the held experts, plus the shared SwiGLU expert.
* A final RMSNorm and a head of its own.

Weights come in a dict under the program's names, each [in, out], in
whatever type they are stored; every use upcasts to float32.  A layer is a
few jitted calls (the mixer, the experts as a scan, the head a block of
rows at a time), so that only one layer's matrices and one expert's are
alive in float32 beside the stored ones and nothing of size [L, L] or [L,
V] is ever whole.

``selections`` lets the caller impose which experts each token takes (the
program's own), so that logits are compared on the same discrete choices;
without it the reference makes its own.  Either way it returns its own
router scores.
"""
import functools

import jax
import jax.numpy as jnp

# plumbing, not mathematics: a configuration as a cache key, a layer's weights
from .deepseek_v32 import _hashable, layer_weights

F32 = jnp.float32
L2_EPS = 1e-6


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def recurrence(q, k, v, g, beta, stop):
    """The delta rule one position after another over ``q``, ``k``, ``g``
    [L, H, K], ``v`` [L, H, V], ``beta`` [L, H]: ``(o [L, H, V], S [H, K,
    V] after position stop - 1)``."""
    H, K, V = q.shape[1], q.shape[2], v.shape[2]

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[:, :, None]
        S = S + bt[:, None, None] * kt[:, :, None] * (
            vt - jnp.einsum("hk,hkv->hv", kt, S))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S)
    seq = (q, k, v, g, beta)
    S, head = jax.lax.scan(step, jnp.zeros((H, K, V), F32),
                           tuple(a[:stop] for a in seq))
    _end, tail = jax.lax.scan(step, S, tuple(a[stop:] for a in seq))
    return jnp.concatenate([head, tail]), S


@jax.jit
def state_after(q, k, v, g, beta):
    """The state [H, K, V] the recurrence leaves after every position of
    ``q``, ``k``, ``g`` [L, H, K], ``v`` [L, H, V], ``beta`` [L, H]: the
    delta rule alone, on inputs a caller gives."""
    with jax.default_matmul_precision("highest"):
        return recurrence(q, k, v, g, beta, q.shape[0])[1]


@functools.lru_cache(maxsize=None)
def _kda(cfg_key, stop):
    cfg = dict(cfg_key)
    la = dict(cfg["linear_attn_config"])
    H, K, taps = la["num_heads"], la["head_dim"], \
        la["short_conv_kernel_size"]

    def layer(w, x):
        """The stream [L, d] after a KDA layer, and the state [H, K, V]
        after position ``stop - 1``."""
        with jax.default_matmul_precision("highest"):
            up = lambda name: w[name].astype(F32)     # noqa: E731
            L = x.shape[0]
            u = rms_norm(x, up("op_norm"), cfg["rms_norm_eps"])
            p = u @ up("wqkv")
            p = jnp.concatenate([jnp.zeros((taps - 1, p.shape[1]), F32), p])
            c = up("conv_w")                          # [taps, 3HK], oldest first
            z = jax.nn.silu(sum(c[j] * p[j:j + L] for j in range(taps)))
            q, k, v = (a.reshape(L, H, K) for a in jnp.split(z, 3, axis=-1))
            q, k = unit(q) * K ** -0.5, unit(k)
            g = -jnp.exp(up("A_log"))[:, None] * jax.nn.softplus(
                (u @ up("f_down") @ up("f_up")).reshape(L, H, K)
                + up("dt_bias").reshape(H, K))
            beta = jax.nn.sigmoid(u @ up("b_proj"))
            if cfg["kda_allow_neg_eigval"]:
                beta = 2.0 * beta
            o, S = recurrence(q, k, v, g, beta, stop)
            o = rms_norm(o, up("o_norm"), cfg["rms_norm_eps"])
            gate = jax.nn.sigmoid(u @ up("g_down") @ up("g_up"))
            return x + (o.reshape(L, H * K) * gate) @ up("wo"), S
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _gqa(cfg_key, block):
    cfg = dict(cfg_key)
    H, KV, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]

    def layer(w, x):
        with jax.default_matmul_precision("highest"):
            up = lambda name: w[name].astype(F32)     # noqa: E731
            L = x.shape[0]
            u = rms_norm(x, up("op_norm"), cfg["rms_norm_eps"])
            q = (u @ up("wq")).reshape(L, H, D)
            # key/value head j serves query heads j * H/KV .. + H/KV - 1
            k = jnp.repeat((u @ up("wk")).reshape(L, KV, D), H // KV, axis=1)
            v = jnp.repeat((u @ up("wv")).reshape(L, KV, D), H // KV, axis=1)
            outs = []
            for lo in range(0, L, block):
                hi = min(L, lo + block)
                causal = jnp.arange(L)[None, :] <= jnp.arange(lo, hi)[:, None]
                s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * D ** -0.5
                p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf),
                                   axis=-1)
                outs.append(jnp.einsum("hqk,khd->qhd", p, v).reshape(
                    hi - lo, H * D))
            gate = jax.nn.sigmoid(u @ up("w_gate"))
            return x + (jnp.concatenate(outs) * gate) @ up("wo")
    return jax.jit(layer)


def expert(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(cfg, scores, bias):
    """The published gate: ``idx [T, k]``, the largest ``score + bias``
    first, from sigmoid scores [T, E]."""
    return jnp.argsort(-(scores + bias), axis=-1)[
        :, :cfg["num_experts_per_tok"]]


@functools.lru_cache(maxsize=None)
def _experts(cfg_key, impose):
    cfg = dict(cfg_key)
    first, count = cfg["held"]

    def layer(w, x, imposed):
        """The stream after a layer's experts, their router scores [L, E]
        and choices [L, k]."""
        with jax.default_matmul_precision("highest"):
            up = lambda name: w[name].astype(F32)     # noqa: E731
            u = rms_norm(x, up("ffn_norm"), cfg["rms_norm_eps"])
            scores = jax.nn.sigmoid(u @ up("ffn.gate_weight"))
            idx = imposed if impose else route(cfg, scores,
                                               up("ffn.select_bias"))
            chosen = jnp.take_along_axis(scores, idx, axis=-1)
            gates = cfg["routed_scaling_factor"] * chosen \
                / chosen.sum(-1, keepdims=True)

            def add(y, held):
                e, w1, w3, w2 = held
                g = jnp.where(idx == first + e, gates, 0.0).sum(-1)
                return y + g[:, None] * expert(u, w1.astype(F32),
                                               w3.astype(F32),
                                               w2.astype(F32)), None
            y, _ = jax.lax.scan(add, jnp.zeros_like(u), (
                jnp.arange(count), w["ffn.held_w1"], w["ffn.held_w3"],
                w["ffn.held_w2"]))
            y = y + expert(u, up("ffn.shared_w1"), up("ffn.shared_w3"),
                           up("ffn.shared_w2"))
            return x + y, scores, idx
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head(eps):
    def logits(norm, head_w, x):
        with jax.default_matmul_precision("highest"):
            return rms_norm(x, norm.astype(F32), eps) @ head_w.astype(F32)
    return jax.jit(logits)


def head(w, x, cfg):
    """Logits [..., V] of the stream ``x`` [..., d] (``forward``'s
    ``hidden``): rows of it, where [L, V] would not fit."""
    return _head(cfg["rms_norm_eps"])(w["norm"], w["head"], x)


def forward(w, tokens, cfg, selections=None, state_at=None, block=512,
            logits=True):
    """``tokens`` [L] -> dict: ``hidden`` [L, d] (before the final norm)
    and, with ``logits``, ``logits`` [L, V]; a layer: ``router_scores``
    [L, E], ``experts`` [L, k]; a KDA layer: ``delta_states`` [H, K, V]
    after position ``state_at - 1`` (the last, if None).  ``cfg`` holds the
    published keys and ``held`` = (first, count); ``selections`` =
    ``{"experts": [idx [L, k] a layer]}`` imposes the choices."""
    L = tokens.shape[0]
    key = _hashable({k: v for k, v in cfg.items()
                     if isinstance(v, (int, float, str, dict, list, tuple))})
    stop = L if state_at is None else int(state_at)
    out = {"router_scores": [], "experts": [], "delta_states": []}
    x = w["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        if i in cfg["gqa_layers"]:
            x = _gqa(key, block)(lw, x)
        else:
            x, S = _kda(key, stop)(lw, x)
            out["delta_states"].append(S)
        imposed = selections["experts"][i] if selections else None
        x, scores, idx = _experts(key, imposed is not None)(lw, x, imposed)
        out["router_scores"].append(scores)
        out["experts"].append(idx)
    out["hidden"] = x
    if logits:
        out["logits"] = head(w, x, cfg)
    return out
