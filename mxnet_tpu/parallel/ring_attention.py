"""Ring attention: sequence/context parallelism over the ICI ring
(SURVEY.md §5.7 — greenfield headroom; the reference caps at seq 512 with
O(L²) materialized scores).

Blockwise online-softmax attention where each device holds a shard of the
sequence and K/V blocks rotate around the mesh axis with ``ppermute`` —
compute on the current block overlaps the next block's transfer (the ICI
torus makes neighbor exchange effectively free).  Memory per device is
O(L_local · d), enabling sequences far beyond single-chip HBM.

Use inside ``shard_map`` (``ring_attention``) or via the convenience wrapper
``ring_self_attention`` which sets up the shard_map over a mesh axis.
"""
from __future__ import annotations

from ..base import MXNetError

__all__ = ["ring_attention", "ring_self_attention"]


def _block_attn(q, k, v, scale, causal, q_offset, kv_offset):
    """One (q_block, kv_block) tile: returns (unnormalized out, row max,
    row sumexp) for online-softmax accumulation."""
    import jax.numpy as jnp
    # q (B, Lq, H, D), k/v (B, Lk, H, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        qi = q_offset + jnp.arange(Lq)
        ki = kv_offset + jnp.arange(Lk)
        mask = qi[:, None] >= ki[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    m = jnp.max(s, axis=-1)                      # (B, H, Lq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                      # (B, H, Lq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)      # unnormalized
    return o, m, l


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Attention over a sequence sharded on ``axis_name``.

    Call INSIDE ``shard_map``: q/k/v are the local shards
    (B, L_local, H, D).  K/V rotate ``axis_size`` times via ``ppermute``;
    partial results merge with the numerically-stable online softmax.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_off = idx * Lq

    def body(i, carry):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        # block currently held came from device (idx - i) mod n
        src = (idx - i) % n
        kv_off = src * Lk
        o_b, m_b, l_b = _block_attn(q, k_cur, v_cur, scale, causal,
                                    q_off, kv_off)
        # online softmax merge
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        l_new = l_acc * alpha + l_b * beta
        o_new = o_acc * alpha.transpose(0, 2, 1)[..., None] \
            + o_b * beta.transpose(0, 2, 1)[..., None]
        # rotate k/v to the next device (skip after the last block)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt

    o0 = jnp.zeros((B, Lq, H, D), q.dtype)
    m0 = jnp.full((B, H, Lq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    return o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]


def ring_self_attention(x_q, x_k, x_v, mesh, seq_axis="seq", causal=False):
    """Convenience wrapper: shard_map ring attention over ``seq_axis``.

    Inputs (B, L, H, D) NDArrays/arrays sharded (or shardable) on L.
    """
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from ..ndarray.ndarray import NDArray, apply_op, unwrap
    from ..base import is_tracer

    spec = P(None, seq_axis, None, None)

    def f(q, k, v):
        # check_vma off: the ppermute ring produces device-varying values
        # the replication checker mis-models
        fn = jax.shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, seq_axis,
                                              causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(q, k, v)

    sh = NamedSharding(mesh, spec)
    args = []
    for x in (x_q, x_k, x_v):
        raw = unwrap(x)
        if not is_tracer(raw):
            from . import global_put
            raw = global_put(raw, sh)
        args.append(NDArray(raw) if isinstance(x, NDArray) else raw)
    return apply_op(f, *args, op_name="ring_attention")
