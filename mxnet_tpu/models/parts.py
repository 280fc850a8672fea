"""What the decoder families written as pure functions of a dict of raw
weights share (``deepseek.py``, ``lfm2.py``, ``keye.py``, ``solar.py``): the
norms, the product, the rotation and its sectioned angles, the learned
selection of positions (the indexer's scores, the top-k and its mask), a
decode step's attention over grouped key / value rings, a prefill's
attention under the selection a block of queries at a time, a depthwise
short convolution and the gated delta rule in their full-sequence and
one-step forms, a sigmoid output gate, the weights of one layer, and the
initializer of a served model built from a seed.
Weights are stored [in, out]; norms, rotations and index scores are float32
inside whatever the activations are."""
from __future__ import annotations

import functools

import numpy as onp

from .. import initializer as init
from .. import random as _random
from ..ops import delta_rule_step as _drs
from ..ops import grouped_ring_attention as _gra
from ..ops import sparse_prefill_attention as _spa
from ..ops import topk_select as _tks
from ..telemetry import part

__all__ = ["rms_norm", "layer_norm", "l2_norm", "matmul", "rope",
           "sectioned_angles", "index_scores", "topk_mask", "mask_positions",
           "grouped_ring_attend", "sparse_block_attend", "short_conv",
           "short_conv_step", "delta_rule_chunked", "delta_rule_step",
           "output_gate", "sub_weights", "FanInNormal", "DrawnBias", "LANES",
           "part"]

# the chip's lane width: a ring whose row is a multiple of it lies with the
# rows contiguous
LANES = 128


def rms_norm(x, g, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 / jnp.sqrt(ms + eps) * g.astype(jnp.float32)).astype(x.dtype)


def l2_norm(x, eps):
    """``x`` over its last axis's Euclidean length, in float32."""
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    return x32 / jnp.sqrt(jnp.sum(jnp.square(x32), -1, keepdims=True) + eps)


def layer_norm(x, g, b, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mu).mean(-1, keepdims=True)
    return ((x32 - mu) / jnp.sqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def matmul(x, w):
    """x @ w, accumulated in float32, in x's type."""
    import jax.numpy as jnp
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def rope(x, cos, sin, interleaved):
    """Rotate the last axis of ``x`` by the angles behind ``cos`` / ``sin``
    ([..., dim / 2], broadcast against x): pairs are (2i, 2i + 1) if
    ``interleaved`` else (i, i + dim / 2)."""
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    if interleaved:
        a, b = x32[..., 0::2], x32[..., 1::2]
    else:
        a, b = jnp.split(x32, 2, axis=-1)
    ra, rb = a * cos - b * sin, a * sin + b * cos
    if interleaved:
        out = jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    else:
        out = jnp.concatenate([ra, rb], axis=-1)
    return out.astype(x.dtype)


def sectioned_angles(pos3, dim, theta, sections):
    """Rotary angles [..., dim / 2] float32 of three-axis positions
    ``pos3`` [3, ...] (temporal, height, width): frequency ``i`` of the
    ``dim / 2`` (``theta ** (-2 i / dim)``) turns by the axis whose section
    holds it, the sections laid out in chunks (``sections`` = how many
    frequencies each axis takes, in order).  With the three axes equal
    these are plain rotary angles."""
    import jax.numpy as jnp
    if sum(sections) != dim // 2:
        raise ValueError(f"sections {sections} must add up to {dim // 2}")
    inv = 1.0 / float(theta) ** (
        onp.arange(0, dim, 2, dtype=onp.float64) / dim)
    # the axis that owns each frequency: its position, a frequency
    axis = onp.repeat(onp.arange(len(sections)), sections)     # [dim / 2]
    p = jnp.moveaxis(pos3.astype(jnp.float32), 0, -1)[..., axis]
    return p * jnp.asarray(inv.astype(onp.float32))


def index_scores(qi, wi, ki):
    """``I`` [B, Q, K] float32 from q^I [B,Q,Hi,Di], w [B,Q,Hi] and k^I
    [B,K,Di].  The weighted sum over heads is elementwise: a float32
    product through the matrix unit would round ``w`` and the ReLUs."""
    import jax
    import jax.numpy as jnp
    with part("scores"):
        s = jnp.einsum("bqhd,bkd->bqhk", qi, ki,
                       preferred_element_type=jnp.float32)
        return (jax.nn.relu(s) * wi[..., None]).sum(axis=2)


def topk_mask(scores, valid, k):
    """The ``k`` largest of ``scores`` [..., N] float32 among ``valid``, as
    a mask: exactly ``min(k, n_valid)`` a row (all of ``valid`` where it
    has no more than ``k``), and ``tie`` [...] bool, the rows in which
    scores exactly equal to the k-th straddled the cut.  The set is
    ``lax.top_k``'s except in which of such ties it keeps: the lowest
    positions here, an order of its own in ``top_k`` on a TPU.  With no
    sort and no index: the k-th largest score is found by bisection over
    its bits, and the mask is a comparison
    (:mod:`mxnet_tpu.ops.topk_select`):

    * on one TPU, :func:`mxnet_tpu.ops.topk_select.topk_select`, one
      Pallas call over blocks of rows held in VMEM;
    * on a CPU, under a mesh, or where the compiler refuses the kernel,
      the same passes in ``jnp``, equal to it bit for bit.

    At the sparse cells' decode shapes on a v5e a sort by ``top_k`` and
    one-hot products that made its indices a mask took 0.40 ms a layer
    (DeepSeek, 64 x 6,144) and 0.39 (Keye, 40 x 12,288), the kernel 0.036
    and 0.045; a Keye prefill block of 512 x 8,192, 2.0 ms against 0.26
    (PERF.md, section 6)."""
    import jax.numpy as jnp
    lead, N = scores.shape[:-1], scores.shape[-1]
    if k >= N:
        return valid, jnp.zeros(lead, bool)
    with part("top_k"):
        key = _tks.keys(scores, valid).reshape(-1, N)
        rows = _tks.kernel_rows(key.shape[0], N, k)
        if rows is not None:
            mask, tie = _tks.topk_select(key, k, rows=rows)
        else:
            t, cut, tie = _tks.threshold_xla(key, k)
    with part("mask"):
        if rows is None:
            mask = _tks.mask_at(key, t, cut)
        return mask.reshape(scores.shape), tie.reshape(lead)


def mask_positions(mask, K):
    """The positions ``mask`` [S, N] keeps, at most ``K`` a row, as
    ``(indices [S, K] int32 in ascending order, keep [S, K])``: a scatter
    of each kept position to its rank, no sort."""
    import jax.numpy as jnp
    S, N = mask.shape
    rank = jnp.where(mask, jnp.cumsum(mask, axis=-1) - 1, K)
    chosen = jnp.zeros((S, K), jnp.int32).at[
        jnp.arange(S)[:, None], rank].set(
        jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (S, N)),
        mode="drop")
    return chosen, jnp.arange(K)[None, :] < mask.sum(-1, keepdims=True)


def grouped_ring_attend(q, ring_k, ring_v, n_valid, mask=None):
    """A decode step's attention: every query head of ``q`` [S, H, D] over
    its slot's rows of the rings [S, M, KV * D], those before ``n_valid``
    [S] (>= 1) or, with a selection, those its ``mask`` [S, M] keeps (none
    at or past ``n_valid``).  The heads stay side by side on the row's
    lanes: a head's query is laid into its key head's ``D`` of the row's
    numbers and the rest left zero, so that scores and values are products
    over whole rows and the rings are never reshaped (a ring split by
    heads has ``D`` numbers on the lanes, and the chip then copies it
    whole).  Scores float32, probabilities in ``q``'s type, products
    accumulated in float32.

    * on one TPU, for a ring a block divides,
      :func:`mxnet_tpu.ops.grouped_ring_attention.grouped_ring_attention`
      reads the valid blocks of both rings where they lie and writes
      neither scores nor probabilities to memory;
    * on a CPU, under a mesh, or where the compiler refuses the kernel, two
      einsums over the whole rings and the masked softmax between them.

    At 128 slots x 5,120 x 512 bfloat16 with 0.6-2.9 k valid on a v5e the
    kernel took 0.83 ms where the einsums took 2.05; at 40 x 12,288 with
    6.4-9.9 k valid and 2,048 kept 0.97 against 1.36 (PERF.md, PR 38).
    Returns ``(out [S, H * D] float32, ring rows read a slot [S])``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    S, H, D = q.shape
    M, W = ring_k.shape[1:]
    KV = W // D
    G = H // KV
    scale = D ** -0.5
    # [S, KV, G, KV', D]: head (kv, g) holds its query where kv' == kv
    own = jnp.eye(KV, dtype=q.dtype)[None, :, None, :, None]
    wide = (q.reshape(S, KV, G, 1, D) * own).reshape(S, H, W)
    block = _gra.kernel_block(S, H, W, M, q.dtype, ring_k.dtype,
                              mask is not None)
    if block is not None:
        o = _gra.grouped_ring_attention(wide, ring_k, ring_v, n_valid,
                                        scale, mask, block=block)
        rows_read = _gra.rows_visited(n_valid, block)
    else:
        if mask is None:
            mask = jnp.arange(M)[None, :] < n_valid[:, None]
        s = jnp.einsum("shw,smw->shm", wide, ring_k.astype(q.dtype),
                       preferred_element_type=f32) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
        o = jnp.einsum("shm,smw->shw", p.astype(q.dtype),
                       ring_v.astype(q.dtype), preferred_element_type=f32)
        rows_read = jnp.full((S,), M, jnp.int32)
    o = (o.reshape(S, KV, G, KV, D) * own.astype(f32)).sum(3)
    return o.reshape(S, H * D), rows_read


def sparse_block_attend(q, k, v, mask, q_start, scale):
    """A prefill's attention for one block of ``bq`` queries: every query
    head of ``q`` [B, KV, G * bq, Dk] (key head ``kv``'s ``G`` query heads
    one after the other) over the positions its block's ``mask``
    [B, bq, L] keeps (the indexer's selection, causal included; none past
    ``q_start + bq - 1``) of ``k`` [B, KV, L, Dk] and ``v`` [B, KV, L, Dv].
    Scores float32, probabilities in ``v``'s type, products accumulated in
    float32.

    * on one TPU, for a sequence a block divides,
      :func:`mxnet_tpu.ops.sparse_prefill_attention.sparse_prefill_attention`
      keeps scores and probabilities in VMEM and skips the key blocks past
      the block's last query;
    * on a CPU, under a mesh, or where the compiler refuses the kernel,
      two einsums over all ``L`` positions and the masked softmax between
      them.

    One layer's blocks on a v5e took 8.85 ms in the kernel where the
    einsums took 34.5 (DeepSeek, 2,816 positions), 5.23 against 39.4
    (Keye, 8,192): a DeepSeek prefill 340 -> 210 ms (PERF.md, section 6).
    Returns ``o`` [B, bq, KV * G * Dv] float32, the heads side by side."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    B, KV, R, Dk = q.shape
    L, Dv = k.shape[2], v.shape[3]
    bq = mask.shape[1]
    G = R // bq
    block = _spa.kernel_block(B, KV, G, bq, L, Dk, Dv, q.dtype)
    if block is not None:
        return _spa.sparse_prefill_attention(q, k, v, mask, q_start, scale,
                                             block_k=block)
    s = jnp.einsum("bkrd,bkmd->bkrm", q, k,
                   preferred_element_type=f32).reshape(B, KV, G, bq, L)
    s = jnp.where(mask[:, None, None], s * scale, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype).reshape(B, KV, R, L)
    o = jnp.einsum("bkrm,bkmd->bkrd", p, v, preferred_element_type=f32)
    return jnp.moveaxis(o.reshape(B, KV, G, bq, Dv), 3, 1).reshape(
        B, bq, KV * G * Dv)


def short_conv(x, taps, valid_length):
    """A depthwise causal convolution over a whole sequence: ``z_t = sum_j
    taps[j] * x_{t - (K - 1) + j}`` for ``x`` [B, L, C] and ``taps`` [K, C]
    (oldest first), zeros before the first row, summed in float32.
    Returns ``(z [B, L, C] float32, state [B, (K - 1) * C])``: the ``K -
    1`` rows of ``x`` before position ``valid_length`` [B], oldest first,
    side by side (zeros before the first), in ``x``'s type, which is what
    :func:`short_conv_step` takes."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    K = taps.shape[0]
    B, L, C = x.shape
    t = taps.astype(f32)
    # K - 1 zeros in front: row t of x is row K - 1 + t
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    z = padded[:, :L].astype(f32) * t[0]
    for j in range(1, K):
        z = z + padded[:, j:j + L].astype(f32) * t[j]
    state = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
        rows, n, K - 1, axis=0))(padded, valid_length)
    return z, state.reshape(B, (K - 1) * C)


def short_conv_step(x, state, taps, act):
    """One row a slot ``x`` [S, C] of :func:`short_conv` against ``state``
    [S, (K - 1) * C] (the rows before it side by side: a row axis of 3
    would be padded to a whole tile on the chip).  Returns ``(z [S, C]
    float32, state)``: shifted by one row with ``x`` appended in the slots
    ``act`` [S] marks; the others keep theirs."""
    import jax.numpy as jnp
    f32 = jnp.float32
    K, C = taps.shape
    t = taps.astype(f32)
    z = x.astype(f32) * t[K - 1]
    for j in range(K - 1):
        z = z + state[:, j * C:(j + 1) * C].astype(f32) * t[j]
    shifted = jnp.concatenate([state[:, C:], x.astype(state.dtype)], axis=1)
    return z, jnp.where(act[:, None] > 0, shifted, state)


def delta_rule_chunked(q, k, v, g, beta, state, chunk):
    """The gated delta rule over a whole sequence, ``chunk`` positions at a
    time.  Per head, with ``S`` [K, V]:

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
        o_t = S_t^T q_t

    for ``q``, ``k`` [B, L, H, K], ``v`` [B, L, H, V], the log decay ``g``
    [B, L, H, K] (<= 0), ``beta`` [B, L, H] and the state before the first
    position [B, H, K, V], all float32; ``L`` a multiple of ``chunk``.  A
    position with ``beta = 0`` and ``g = 0`` passes the state through, so a
    padded tail leaves it as of the last valid position.  Returns ``(o [B,
    L, H, V], S_L)``.

    Inside a chunk the state is ``Diag(Gamma_t) S_0 + sum_{i <= t}
    Diag(Gamma_t / Gamma_i) k_i u_i^T`` (``Gamma`` the cumulative decay
    from the chunk's start): the pseudo-values ``u`` solve one unit lower
    triangular system, ``(I + Diag(beta) A) U = Diag(beta) (V - (K *
    Gamma) S_0)`` with ``A_ti = sum_c k_t k_i exp(G_t - G_i)`` (i < t).  The
    decay between two positions is taken pairwise in log space, ``exp(G_t
    - G_i)`` with ``t >= i``, which never exceeds 1: the inverse of a
    cumulative product would overflow float32 within a chunk where the
    decay is steep.  Products are at full float32 precision."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    B, L, H, K = q.shape
    n = L // chunk

    def chunks(a):
        """[B, L, H, ...] -> [n, B, H, chunk, ...]"""
        a = a.reshape((B, n, chunk, H) + a.shape[3:])
        return jnp.swapaxes(jnp.moveaxis(a, 1, 0), 2, 3)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def one(S, xs):
        qc, kc, vc, gc, bc = xs             # [B, H, C, ...], bc [B, H, C]
        G = jnp.cumsum(gc, axis=2)
        # exp(G_t - G_i) for t >= i, 0 above the diagonal: [B, H, t, i, K]
        decay = jnp.exp(jnp.where(causal[:, :, None],
                                  G[:, :, :, None] - G[:, :, None], -jnp.inf))
        dk = decay * kc[:, :, None]
        A = jnp.where(strict, (kc[:, :, :, None] * dk).sum(-1), 0.0)
        QK = jnp.where(causal, (qc[:, :, :, None] * dk).sum(-1), 0.0)
        gamma = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhtk,bhkv->bhtv", kc * gamma, S, precision=hp))
        U = jax.scipy.linalg.solve_triangular(
            jnp.eye(chunk, dtype=q.dtype) + bc[..., None] * A, rhs,
            lower=True, unit_diagonal=True)
        o = jnp.einsum("bhtk,bhkv->bhtv", qc * gamma, S, precision=hp) \
            + jnp.einsum("bhti,bhiv->bhtv", QK, U, precision=hp)
        to_end = jnp.exp(G[:, :, -1:] - G)                  # [B, H, C, K]
        S = gamma[:, :, -1, :, None] * S + jnp.einsum(
            "bhik,bhiv->bhkv", kc * to_end, U, precision=hp)
        return S, o
    S, o = jax.lax.scan(one, state, tuple(
        chunks(a) for a in (q, k, v, g, beta)))
    # [n, B, H, C, V] -> [B, L, H, V]
    o = jnp.moveaxis(jnp.swapaxes(o, 2, 3), 0, 1)
    return o.reshape(B, L, H, v.shape[-1]), S


def delta_rule_step(q, k, v, g, beta, state, act):
    """One position a slot of :func:`delta_rule_chunked`'s rule: ``q``,
    ``k``, ``g`` [S, H, K], ``v`` [S, H, V], ``beta`` [S, H], the state
    [S, H, K, V] and ``act`` [S]: the active slots' states move on, the
    others' are kept bit for bit.  Returns ``(o [S, H, V] float32, state'
    in the state's type, passes)``: ``passes`` is how many times the step
    moved each state, reads and writes counted.  The rule is float32, its
    products over ``K`` elementwise and summed in float32.

    * on one TPU, for a float32 state a block of heads divides,
      :func:`mxnet_tpu.ops.delta_rule_step.delta_rule_step` reads each
      state once and writes it back in place: 2 passes;
    * on a CPU, under a mesh, for a state of another type, or where the
      compiler refuses the kernel, XLA splits the rule at its reduction
      over ``K``: each state is read for ``S1^T k`` and ``S1^T q``, read
      again for the decay, the rank-one write and the select, and written:
      3 passes."""
    import jax.numpy as jnp
    S, H, K = q.shape
    V = v.shape[-1]
    heads = _drs.kernel_heads(S, H, K, V, state.dtype)
    if heads is not None:
        o, new = _drs.delta_rule_step(q, k, v, g, beta, state, act,
                                      heads=heads)
        return o, new, 2
    S0 = state.astype(jnp.float32)
    S1 = S0 * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - (S1 * k[..., None]).sum(-2))
    # S'^T q = S1^T q + (k . q) u: the new state is not read back
    o = (S1 * q[..., None]).sum(-2) + (q * k).sum(-1, keepdims=True) * u
    new = S1 + k[..., None] * u[..., None, :]
    return o, jnp.where(act[:, None, None, None] > 0,
                        new.astype(state.dtype), state), 3


def output_gate(o, gate):
    """``o * sigmoid(gate)`` in float32, in ``gate``'s type: the
    elementwise gate on an attention or mixer output before its output
    projection."""
    import jax
    import jax.numpy as jnp
    return (o.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)


def sub_weights(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


@functools.lru_cache(maxsize=None)
def _normal_maker(shape, dtype, sigma):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda key: (jax.random.normal(key, shape, jnp.float32)
                                * sigma).astype(dtype))


class FanInNormal(init.Initializer):
    """Normal of standard deviation ``sigma``, or ``fan_in ** -0.5`` of a
    matrix stored [..., in, out], so that every product keeps its input's
    scale.  Made in one jitted program a shape: no float32 copy of a
    bfloat16 stack of experts."""

    def __init__(self, sigma=None):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, name, shape, dtype):
        sigma = self.sigma or shape[-2] ** -0.5
        return _normal_maker(tuple(shape), str(onp.dtype(dtype)),
                             float(sigma))(_random.next_key())


class DrawnBias(FanInNormal):
    """:class:`FanInNormal` for a parameter whose name ends in ``bias``,
    which the base class reads as a zero."""

    def init_array(self, name, shape, dtype):
        return self._init_weight(name, shape, dtype)
