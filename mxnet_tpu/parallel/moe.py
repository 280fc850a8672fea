"""Mixture-of-Experts with expert parallelism (SURVEY.md §2.3 "EP/MoE").

The reference has no MoE (sparse ops exist but no routing — SURVEY §2.3);
this is greenfield capability built the TPU way, after GShard/Switch
Transformer: routing is *static-shape* — every (expert, capacity-slot) pair
exists whether or not a token fills it, so the whole layer is three einsums
XLA can tile onto the MXU, and sharding the stacked expert weights over an
``expert`` mesh axis turns the dispatch/combine einsums into all-to-all
collectives over ICI (no ragged transfers, no host-side routing).

Pieces:

- :func:`moe_dispatch` — pure-jax top-k router with capacity: returns the
  [T,E,C] combine tensor + load-balance aux loss.
- :class:`MoE` — Gluon ``HybridBlock`` position-wise FFN MoE layer; expert
  weights are stacked ``(E, ...)`` so one regex rule shards them.
- :func:`noaux_route` / :func:`dropless_experts` / :class:`DroplessMoE` —
  the other kind of layer: sigmoid scores with a selection bias (or a
  softmax over all experts and none), group-limited top-k, no capacity
  and so no dropped pair, one grouped product a projection
  (``jax.lax.ragged_dot``) over the experts this chip *holds* of
  ``num_experts``, and a shared expert.
- :func:`moe_sharding_rules` — ``shard_params`` rules for the EP axis.
- :func:`aux_loss_scope` — collects router aux losses during a forward so
  the training loss can add them (pure-function-friendly: the collected
  values are tracers inside a traced step).
"""
from __future__ import annotations

import functools
import threading

from ..ndarray.ndarray import NDArray, apply_op, unwrap
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from .. import initializer as init
from ..ops import grouped_product as _gp
from ..telemetry import part as _part

__all__ = ["MoE", "moe_dispatch", "moe_sharding_rules", "aux_loss_scope",
           "collected_aux_loss", "DroplessMoE", "noaux_route",
           "dropless_experts", "dropless_moe", "held_load", "rows_computed",
           "swiglu"]

_moe_tls = threading.local()


class aux_loss_scope:
    """Context manager collecting MoE router aux losses.

    with moe.aux_loss_scope() as losses:
        out = net(x)
        loss = task_loss + lambda * sum(losses)
    """

    def __init__(self):
        self.losses = []

    def __enter__(self):
        self._prev = getattr(_moe_tls, "sink", None)
        _moe_tls.sink = self.losses
        return self.losses

    def __exit__(self, *exc):
        _moe_tls.sink = self._prev


def collected_aux_loss(losses):
    """Sum a list of collected aux losses into one scalar NDArray."""
    if not losses:
        raise ValueError("no MoE aux losses were collected")
    total = losses[0]
    for l in losses[1:]:
        total = total + l
    return total


def moe_dispatch(probs, k, capacity):
    """Top-k routing with per-expert capacity (pure jax, static shapes).

    probs: [T, E] router softmax.  Returns (combine [T,E,C], aux_loss).
    Tokens overflowing an expert's C slots are dropped (their combine row is
    zero — the residual connection carries them, Switch-Transformer style).
    GShard position assignment: slot-0 choices of all tokens are placed
    before any slot-1 choice, priority by token order.
    """
    import jax.numpy as jnp

    T, E = probs.shape
    p = probs
    base = jnp.zeros((E,), probs.dtype)       # tokens already queued per expert
    slots = []
    top1_frac = None
    for s in range(k):
        idx = jnp.argmax(p, axis=-1)          # [T]
        oh = jnp.eye(E, dtype=probs.dtype)[idx]
        if s == 0:
            top1_frac = oh.mean(axis=0)       # fraction routed (for aux loss)
        pos = (jnp.cumsum(oh, axis=0) - oh) + base[None, :]
        pos = (pos * oh).sum(-1)              # [T] position within the expert
        keep = (pos < capacity).astype(probs.dtype)
        gate = (p * oh).sum(-1) * keep        # chosen prob, 0 if dropped
        slots.append((idx, pos, gate, oh))
        base = base + oh.sum(axis=0)
        p = p * (1.0 - oh)                    # exclude expert for next slot

    denom = sum(g for _, _, g, _ in slots) + 1e-9
    combine = 0.
    cap_eye = jnp.eye(capacity, dtype=probs.dtype)
    for idx, pos, gate, oh in slots:
        pos_oh = cap_eye[jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)]
        combine = combine + (gate / denom)[:, None, None] \
            * oh[:, :, None] * pos_oh[:, None, :]

    me = probs.mean(axis=0)                   # mean router prob per expert
    aux = E * jnp.sum(me * top1_frac)         # GShard load-balance loss
    return combine, aux


def _moe_core(x2d, w1, b1, b2, w2, k, capacity, act, router_logits,
              groups=1):
    """Grouped GShard dispatch: tokens compete for capacity only within
    their group of S = T/G tokens, so the one-hot dispatch/combine
    einsums cost O(T*E*c*d) with the PER-GROUP capacity c = k*S/E*cf —
    a factor G cheaper than ungrouped routing at the same total expert
    batch (G*E*c slots).  groups=1 is the ungrouped original."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    T, E = probs.shape
    G = groups
    S = T // G
    combine, aux = jax.vmap(
        lambda p: moe_dispatch(p, k, capacity))(probs.reshape(G, S, E))
    aux = aux.mean()
    combine = combine.astype(x2d.dtype)           # [G, S, E, c]
    xg = x2d.reshape(G, S, x2d.shape[-1])
    # dispatch tokens into [G, E, c, d] expert batches — with expert
    # weights sharded P('expert') these einsums lower to an all-to-all
    # over ICI
    dispatch = (combine != 0).astype(x2d.dtype)   # hard routing mask; the
    # gradient path to the router runs through `combine` in the final einsum
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg)
    h = jnp.einsum("gecd,edh->gech", xe, w1) + b1[None, :, None, :]
    if act == "relu":
        h = jax.nn.relu(h)
    elif act == "gelu":
        h = jax.nn.gelu(h, approximate=False)
    else:
        h = jax.nn.silu(h)
    ye = jnp.einsum("gech,ehd->gecd", h, w2) + b2[None, :, None, :]
    y = jnp.einsum("gsec,gecd->gsd", combine, ye)
    return y.reshape(T, x2d.shape[-1]), aux.astype(jnp.float32)


class MoE(HybridBlock):
    """Position-wise FFN Mixture-of-Experts layer.

    Drop-in replacement for a transformer FFN: input [..., units] ->
    output [..., units].  ``num_experts`` stacked FFN experts, top-``k``
    routing with ``capacity_factor`` slack.  The reference framework has no
    analogue (SURVEY §2.3: EP "not in core").
    """

    def __init__(self, units, hidden_size, num_experts, k=2,
                 capacity_factor=1.25, activation="gelu", dtype="float32",
                 num_groups=1, weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        E = num_experts
        self._units = units
        self._hidden = hidden_size
        self._E = E
        self._k = min(k, E)
        self._cf = capacity_factor
        self._act = activation
        # GShard token groups: capacity competition is per group of
        # S = T/G tokens, which shrinks the dispatch/combine einsums by G
        # at the same total expert batch.  1 = ungrouped.
        self._groups = max(1, int(num_groups))
        winit = weight_initializer or init.Xavier()
        self.gate_weight = Parameter("gate_weight", shape=(E, units),
                                     dtype=dtype, init=winit)
        self.expert_w1 = Parameter("expert_w1", shape=(E, units, hidden_size),
                                   dtype=dtype, init=winit)
        self.expert_b1 = Parameter("expert_b1", shape=(E, hidden_size),
                                   dtype=dtype, init=init.Zero())
        self.expert_w2 = Parameter("expert_w2", shape=(E, hidden_size, units),
                                   dtype=dtype, init=winit)
        self.expert_b2 = Parameter("expert_b2", shape=(E, units),
                                   dtype=dtype, init=init.Zero())

    def capacity(self, num_tokens):
        import math
        return max(self._k, int(math.ceil(
            self._k * num_tokens / self._E * self._cf)))

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_b1,
                       expert_w2, expert_b2):
        shape = x.shape
        T = 1
        for s in shape[:-1]:
            T *= int(s)
        G = self._groups if T % self._groups == 0 else 1
        cap = self.capacity(T // G)
        x2d = x.reshape((T, shape[-1]))
        router_logits = F.dot(x2d, gate_weight, transpose_b=True)

        def core(x_r, w1_r, b1_r, b2_r, w2_r, logits_r):
            return _moe_core(x_r, w1_r, b1_r, b2_r, w2_r,
                             self._k, cap, self._act, logits_r, groups=G)

        y2d, aux = apply_op(core, x2d, expert_w1, expert_b1, expert_b2,
                            expert_w2, router_logits,
                            op_name="MoE", has_aux=False)
        sink = getattr(_moe_tls, "sink", None)
        if sink is not None:
            sink.append(aux)
        return y2d.reshape(shape)


# ---------------------------------------------------------------------------
# dropless routing over the experts held here (DeepSeek-V3 style)
# ---------------------------------------------------------------------------
def noaux_route(scores, bias, k, n_group=1, topk_group=1, route_scale=1.0,
                norm_eps=0.0):
    """Group-limited top-k of ``scores`` [T, E] (sigmoid, float32) by
    ``scores + bias``: a group's score is the sum of its two largest
    biased scores, the best ``topk_group`` of ``n_group`` groups stay, and
    the ``k`` largest biased scores among their experts are chosen.  Gates
    are the *unbiased* scores of the chosen, normalised to sum to
    ``route_scale`` (over ``sum + norm_eps``, where a publisher guards the
    division so).  Returns ``(idx [T, k] int32, gates [T, k])``."""
    import jax
    import jax.numpy as jnp

    T, E = scores.shape
    biased = scores + bias[None, :]
    if n_group > 1:
        g = biased.reshape(T, n_group, E // n_group)
        group_score = jax.lax.top_k(g, 2)[0].sum(-1)            # [T, G]
        _, keep = jax.lax.top_k(group_score, topk_group)
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        biased = jnp.where(kept[:, :, None], g, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(biased, k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    total = chosen.sum(-1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    gates = route_scale * chosen / total
    return idx.astype(jnp.int32), gates


def swiglu(x, w1, w3, w2):
    """``W2(SiLU(W1 x) * W3 x)`` with weights stored [in, out]; products
    accumulate in float32, activations keep ``x``'s type."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    h = jax.nn.silu(jnp.dot(x, w1, preferred_element_type=f32)) \
        * jnp.dot(x, w3, preferred_element_type=f32)
    return jnp.dot(h.astype(x.dtype), w2, preferred_element_type=f32)


def _held_pairs(idx, first, count):
    """``(key, sizes)`` of routed pairs ``idx`` [T, k]: a pair's held
    expert (``count`` where it is not held here), and the pairs a held
    expert got [count]."""
    import jax.numpy as jnp
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    return key, jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]


def dropless_experts(x2d, idx, gates, w1, w3, w2, first):
    """What the experts ``first .. first + count`` (the stacks' leading
    axis) add for tokens ``x2d`` [T, d] routed by ``idx`` / ``gates``
    [T, k]: every pair whose expert is held is computed, whatever the
    load.  Pairs are sorted by held expert (the others last), and each
    projection is one grouped product over the stack.  float32 [T, d].

    What runs where.  On a TPU, outside a mesh, the product is
    :func:`mxnet_tpu.ops.grouped_product.grouped_product` (megablox
    ``gmm``) at the row tile
    :func:`~mxnet_tpu.ops.grouped_product.row_tile` gives for the pairs
    and the held experts, the weights in whole rows of an expert's
    matrix.  Elsewhere (a CPU, a mesh, an ONNX export, a compiler's
    refusal) it is ``jax.lax.ragged_dot``, the kernel's tested reference.
    On a TPU that is XLA's own grouped matmul at a row tile of its own
    choosing, the largest power of two up to 512 that divides the pairs:
    a decode step's 512 pairs were one tile that each of 64 experts
    multiplied whole, 1.26 ms a call on a v5e in
    ``lfm2_24b.decode_rollout`` (ledger, PR 35) where the kernel takes
    0.56 (my chip runs, PR 36)."""
    T, k = idx.shape
    count, d, hidden = w1.shape
    tm = _gp.kernel_tile(T * k, count, d, hidden, w1.dtype)
    return _experts_program()(x2d, idx, gates, w1, w3, w2, first=first, tm=tm)


def _experts(x2d, idx, gates, w1, w3, w2, first, tm):
    """:func:`dropless_experts` at the row tile ``tm`` (``ragged_dot``
    where None)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T, k = idx.shape
    count = w1.shape[0]
    # the part again, inside the jitted function: XLA's expansion of the
    # scatter-add names its sort and its fusion by the innermost jit's
    # own stack ("mx.combine/scatter-add"), without the caller's
    with _part("experts"):
        with _part("sort"):
            key, sizes = _held_pairs(idx, first, count)
            order = jnp.argsort(key)                      # stable
            token = (jnp.arange(T * k, dtype=jnp.int32) // k)[order]
            xs = x2d[token]

        def product(a, w):
            if tm is None:
                return jax.lax.ragged_dot(a, w, sizes,
                                          preferred_element_type=f32)
            return _gp.grouped_product(a, w, sizes, tm)
        with _part("product"):
            h = jax.nn.silu(product(xs, w1)) * product(xs, w3)
            y = product(h.astype(x2d.dtype), w2)
        with _part("combine"):
            # rows past the held pairs belong to no group: whatever is there
            # is not a result
            g = jnp.where(key < count, gates.reshape(-1), 0.0)[order]
            g = g.astype(f32)
            live = jnp.arange(T * k) < sizes.sum()
            y = jnp.where(live[:, None], y, 0.0) * g[:, None]
            return jnp.zeros((T, x2d.shape[-1]), f32).at[token].add(y)


@functools.lru_cache(maxsize=None)
def _experts_program():
    """:func:`_experts` as one jitted function: a model's expert layers
    are alike, so a program that holds eight of them traces and lowers
    the layer (three kernels and what prepares their groups) once and
    calls it eight times.  XLA inlines the calls: the compiled program is
    the one the unrolled trace gave, an engine's start is seconds
    shorter (my chip runs, PR 36)."""
    import jax
    return jax.jit(_experts, static_argnames=("first", "tm"))


def rows_computed(idx, first, stack):
    """Rows one grouped product of :func:`dropless_experts` multiplies for
    pairs ``idx`` [T, k] over ``stack`` [count, d, hidden] (row tiles
    visited x tile rows, int32), at the kernel's row tile where it runs
    and elsewhere at the one XLA's ``ragged_dot`` takes on a TPU.  Over
    the held pairs it is the product's redundancy."""
    import jax.numpy as jnp
    T, k = idx.shape
    count, d, hidden = stack.shape
    _key, sizes = _held_pairs(idx, first, count)
    tm = _gp.kernel_tile(T * k, count, d, hidden, stack.dtype) \
        or _gp.xla_row_tile(T * k)
    return _gp.rows_visited(sizes, tm).astype(jnp.int32)


def held_load(idx, first, count, weight=None):
    """[pairs, pairs on held experts, held experts touched, largest load
    of a held expert] as int32, over the tokens ``weight`` [T] marks
    (all, if None)."""
    import jax.numpy as jnp
    T, k = idx.shape
    w = jnp.ones((T,), jnp.int32) if weight is None \
        else weight.astype(jnp.int32)
    local = idx - first
    held = ((local >= 0) & (local < count)) * w[:, None]
    load = jnp.zeros((count + 1,), jnp.int32).at[
        jnp.where(held > 0, local, count).reshape(-1)].add(1)[:count]
    return jnp.stack([w.sum() * k, held.sum(), (load > 0).sum(),
                      load.max()]).astype(jnp.int32)


def dropless_moe(x2d, w, k, first, n_group=1, topk_group=1, route_scale=1.0,
                 with_shared=True, norm_eps=0.0, scoring="sigmoid"):
    """One expert layer on raw tokens [T, d] from its raw weights ``w``
    (``gate_weight`` [d, E], optionally ``select_bias`` [E] (none, if
    absent), ``held_w1/w3/w2``, optionally ``shared_w1/w3/w2``).  The
    router's product and its ``scoring`` are float32: ``"sigmoid"`` of
    each logit, or ``"softmax"`` over all ``E`` (the gates are then the
    chosen probabilities renormalised).  Returns ``(y [T, d] float32, idx,
    gates, scores)``.  The caller names the part (``experts``, with the
    layer's pre-norm and residual add); the sub-parts are named here:
    ``router``, ``sort``, ``product``, ``combine``, ``shared``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring {scoring!r} is neither sigmoid nor softmax")
    with _part("router"):
        logits = jnp.dot(x2d.astype(f32), w["gate_weight"].astype(f32))
        scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        bias = w["select_bias"].astype(f32) if "select_bias" in w \
            else jnp.zeros((scores.shape[-1],), f32)
        idx, gates = noaux_route(scores, bias, k, n_group, topk_group,
                                 route_scale, norm_eps)
    y = dropless_experts(x2d, idx, gates, w["held_w1"], w["held_w3"],
                         w["held_w2"], first)
    if with_shared and "shared_w1" in w:
        with _part("shared"):
            y = y + swiglu(x2d, w["shared_w1"], w["shared_w3"],
                           w["shared_w2"])
    return y, idx, gates, scores


class DroplessMoE(HybridBlock):
    """Expert layer that holds ``held=(first, count)`` of ``num_experts``
    routed SwiGLU experts and a shared expert.

    The router and the selection run over all ``num_experts``
    (:func:`noaux_route`); this layer computes the part of the result
    that its own experts give (:func:`dropless_experts`) plus the shared
    expert, which every holder computes alike.  On one chip it runs
    without its exchange: what the absent experts would add is left out.
    The parts of all the holders, the shared expert counted once, add up
    to the whole layer (``tests/test_moe.py``).  Weights are stored
    [in, out]; ``select_bias`` is the ``noaux_tc`` selection bias, left
    out with ``select_bias=False``; ``scoring`` is the router's
    (:func:`dropless_moe`)."""

    def __init__(self, units, hidden_size, num_experts, k, held=None,
                 n_group=1, topk_group=1, route_scale=1.0, shared_experts=1,
                 dtype="float32", weight_initializer=None,
                 bias_initializer=None, grad_req="write", norm_eps=0.0,
                 scoring="sigmoid", select_bias=True, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        first, count = held if held is not None else (0, num_experts)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"held={held} outside 0..{num_experts}")
        self._k = k
        self._first, self._count = int(first), int(count)
        self._n_group, self._topk_group = n_group, topk_group
        self._route_scale, self._norm_eps = route_scale, norm_eps
        self._scoring = scoring
        winit = weight_initializer or init.Xavier()
        sh = shared_experts * hidden_size

        def mat(name, *shape):
            setattr(self, name, Parameter(name, shape=shape, dtype=dtype,
                                          init=winit, grad_req=grad_req))
        mat("gate_weight", units, num_experts)
        if select_bias:
            self.select_bias = Parameter(
                "select_bias", shape=(num_experts,), dtype="float32",
                init=bias_initializer or init.Zero(), grad_req=grad_req)
        mat("held_w1", count, units, hidden_size)
        mat("held_w3", count, units, hidden_size)
        mat("held_w2", count, hidden_size, units)
        if shared_experts:
            mat("shared_w1", units, sh)
            mat("shared_w3", units, sh)
            mat("shared_w2", sh, units)

    @property
    def held(self):
        return self._first, self._count

    def apply(self, x, with_shared=True):
        """Raw [..., d] -> ``(y raw [..., d], idx [T, k], scores [T, E])``:
        the layer's result and what the router decided."""
        w = {name: unwrap(p.data()) for name, p in self._reg_params.items()}
        y, idx, _gates, scores = dropless_moe(
            x.reshape(-1, x.shape[-1]), w, k=self._k, first=self._first,
            n_group=self._n_group, topk_group=self._topk_group,
            route_scale=self._route_scale, with_shared=with_shared,
            norm_eps=self._norm_eps, scoring=self._scoring)
        return y.astype(x.dtype).reshape(x.shape), idx, scores

    def forward(self, x):
        return NDArray(self.apply(unwrap(x))[0])

    hybrid_forward = None


def moe_sharding_rules(expert_axis="expert"):
    """``shard_params`` rules placing stacked expert weights on the EP axis.

    The router gate stays replicated (with :class:`DroplessMoE`'s
    selection bias and shared expert); every ``expert_*`` and ``held_*``
    stack shards its leading E dimension.  Compose with TP/DP rules by concatenation (first
    match wins in ``shard_params``).
    """
    from jax.sharding import PartitionSpec as P
    return [
        (r"expert_w1$|expert_b1$|expert_w2$|expert_b2$", P(expert_axis)),
        (r"held_w1$|held_w3$|held_w2$", P(expert_axis)),
        (r"gate_weight$|select_bias$|shared_w[123]$", P()),
    ]
