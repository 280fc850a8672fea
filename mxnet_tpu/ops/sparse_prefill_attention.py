"""Attention of one block of a prefill's queries under the indexer's
selection, in VMEM.

The full-sequence forward of :mod:`mxnet_tpu.models.deepseek` and
``keye`` runs over blocks of ``bq`` queries (a ``lax.map``), and a block
attends, every head, over the positions its per-query ``mask`` [B, bq, L]
keeps: the indexer's selection, causal included, one set for all the
heads of a key head (in DeepSeek for all 128).  The XLA form
(``models/parts.py::sparse_block_attend``) writes float32 scores of every
head over all ``L`` positions, ``where``, ``softmax`` and a cast over them
in HBM: ``f32[128, 256, 2816]`` a block in DeepSeek, 170 of a 340 ms
prefill in five layers, against 8.2 ms for the causal products at the
matrix unit's peak (PERF.md, section 5).  This kernel keeps them in VMEM:

- grid ``(batch, block of key heads, block of key positions)``; a key
  block that starts past the query block's last position
  (``q_start + bq - 1``, a scalar prefetch) is neither fetched nor
  computed (``pl.when``): on average half of them.  Past the last one
  the index map names the next head block's first key block, so that its
  fetch runs beside the last block's products (as in
  ``grouped_ring_attention``);
- a grid step takes :data:`HEADS` query heads: ``G`` query heads of one
  key head in Keye (``G`` = 8), eight key heads of one query head each in
  DeepSeek, so that a step's products and the selection block it reads
  serve eight heads and the grid stays short;
- key and value may differ in width (DeepSeek: ``q_nope | q_rope``
  against ``k_nope | k_rope`` of 192, values of 128);
- online softmax: float32 running maximum, sum and accumulator; scores
  float32, probabilities cast to the activations' type for the product
  with ``v``, accumulated in float32: the casts of the XLA form.  A row
  whose mask keeps nothing gets zeros (none does here: every query keeps
  itself);
- the output leaves with the heads side by side on a row's lanes,
  ``[B, bq, H * Dv]`` float32, as the output projection reads it.
"""
from __future__ import annotations

import functools

from .latent_ring_attention import _MASKED

__all__ = ["sparse_prefill_attention", "kernel_block", "pick_block",
           "BLOCKS", "HEADS"]

# key positions a block, the first that divides the sequence.  One layer's
# attend on a v5e, ms at blocks of 256 / 512 / 1,024: DeepSeek at 3,072
# 10.37 / 6.49 / 5.94 (2,816: 256 alone divides, 8.85; the XLA form 34.5
# and 38.7), Keye at 7,168 7.45 / 6.45 / 4.12 and at 8,192 9.57 / 8.28 /
# 5.23 (the XLA form 30.0 and 39.4): a larger block pays fewer grid steps
# and no more work (PERF.md, section 6)
BLOCKS = (1024, 512, 256, 128)

# query heads a grid step
HEADS = 8

# a step of 8 heads at bq 512, keys of 192 and blocks of 1,024 holds about
# 36 MB of VMEM with the pipeline's two buffers: above the default scope
# (16 heads there ran out of VMEM)
_VMEM_LIMIT = 64 * 1024 * 1024


def pick_block(length):
    """The first of :data:`BLOCKS` that divides ``length``, or None."""
    for b in BLOCKS:
        if length % b == 0:
            return b
    return None


def _head_block(kv_heads, groups):
    """Key heads a grid step: the largest divisor of ``kv_heads`` whose
    query heads are at most :data:`HEADS`."""
    return max(d for d in range(1, kv_heads + 1)
               if kv_heads % d == 0 and d * groups <= max(HEADS, groups))


def _kernel(scale, hb, groups, bq, qs_ref, q_ref, k_ref, v_ref, bias_ref,
            o_ref, m_sc, l_sc, acc_sc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    j = pl.program_id(2)
    bk = k_ref.shape[2]
    dv = v_ref.shape[3]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, f32)
        l_sc[...] = jnp.zeros(l_sc.shape, f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    @pl.when(j * bk < qs_ref[0] + bq)
    def _():
        bias = bias_ref[0]                        # [bq, bk]: 0 or _MASKED
        for a in range(hb):
            k = k_ref[0, a]
            v = v_ref[0, a]
            for g in range(groups):
                i = a * groups + g
                q = q_ref[0, a, g * bq:(g + 1) * bq]           # [bq, Dk]
                sc = jax.lax.dot_general(
                    q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale + bias
                m_old = m_sc[i]
                m_new = jnp.maximum(m_old, sc.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_old - m_new)
                # a masked position's exp is 0 once a kept one has raised
                # the maximum; until then (m_new == _MASKED) it is 1, and
                # the first kept one's alpha, exp(_MASKED - m_new) == 0,
                # wipes it
                p = jnp.exp(sc - m_new)
                l_sc[i] = alpha * l_sc[i] + p.sum(axis=-1, keepdims=True)
                acc_sc[i] = alpha * acc_sc[i] + jnp.dot(
                    p.astype(q.dtype), v.astype(q.dtype),
                    preferred_element_type=f32)
                m_sc[i] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for i in range(hb * groups):
            # nothing kept at all: the maximum never left _MASKED
            o_ref[0, :, i * dv:(i + 1) * dv] = jnp.where(
                m_sc[i] > _MASKED, acc_sc[i] / l_sc[i], 0.0)


def sparse_prefill_attention(q, k, v, mask, q_start, scale, *, block_k=None,
                             interpret=False):
    """``o`` [B, bq, KV * G * Dv] float32: every query head of one block of
    ``bq`` queries, softmax over the positions ``mask`` [B, bq, L] keeps of
    ``q . k * scale``, times ``v``; head ``(kv, g)``'s output in lanes
    ``(kv * G + g) * Dv`` onwards.

    ``q`` [B, KV, G * bq, Dk] holds key head ``kv``'s ``G`` query heads one
    after the other; ``k`` [B, KV, L, Dk] and ``v`` [B, KV, L, Dv] may
    differ in width.  ``q_start`` is the block's first position: ``mask``
    keeps nothing past ``q_start + bq - 1`` (it is causal), which is what
    lets the key blocks past it go unread.  Operands in the activations'
    type."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, KV, R, Dk = q.shape
    L, Dv = k.shape[2], v.shape[3]
    bq = mask.shape[1]
    groups = R // bq
    block_k = pick_block(L) if block_k is None else block_k
    if block_k is None or L % block_k:
        raise ValueError(f"no block of {BLOCKS} divides {L} positions")
    hb = _head_block(KV, groups)
    n_heads = KV // hb

    def at(b, h, j, qs):
        # past the query block's last key block: the next head block's
        # first, so that its fetch runs beside the last block's products;
        # the last head block keeps the block it has
        last = (qs[0] + bq - 1) // block_k
        on = jnp.logical_and(j > last, h + 1 < n_heads)
        return jnp.where(on, h + 1, h), jnp.where(on, 0, jnp.minimum(j, last))

    def kv_at(b, h, j, qs):
        head, blk = at(b, h, j, qs)
        return b, head, blk, 0

    def bias_at(b, h, j, qs):
        return b, 0, at(b, h, j, qs)[1]

    return pl.pallas_call(
        functools.partial(_kernel, float(scale), hb, groups, bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n_heads, L // block_k),
            in_specs=[
                pl.BlockSpec((1, hb, R, Dk), lambda b, h, j, qs: (b, h, 0, 0)),
                pl.BlockSpec((1, hb, block_k, Dk), kv_at),
                pl.BlockSpec((1, hb, block_k, Dv), kv_at),
                pl.BlockSpec((1, bq, block_k), bias_at)],
            out_specs=pl.BlockSpec((1, bq, hb * groups * Dv),
                                   lambda b, h, j, qs: (b, 0, h)),
            scratch_shapes=[pltpu.VMEM((hb * groups, bq, 1), jnp.float32),
                            pltpu.VMEM((hb * groups, bq, 1), jnp.float32),
                            pltpu.VMEM((hb * groups, bq, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, bq, KV * groups * Dv),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="sparse_prefill_attention",
        interpret=interpret,
    )(jnp.reshape(q_start, (1,)).astype(jnp.int32), q, k, v,
      # the selection as it came: fused into the bias, a one-hot product
      # behind it ran three times as long (Keye's mask on a v5e)
      jnp.where(jax.lax.optimization_barrier(mask), 0.0, _MASKED
                ).astype(jnp.float32))


def kernel_block(B, KV, G, bq, L, Dk, Dv, dtype):
    """The key block the kernel runs at for these shapes, or None where
    the XLA form runs: on a CPU, under a mesh, in an ONNX export, for a
    sequence no block divides, or where the chip's compiler refuses the
    variant (kept in ``kernel_report()``)."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    block = pick_block(L)
    if block is None or not kernel_dispatch_allowed():
        return None

    def compile_fn():
        def shape(*dims, dt=dtype):
            return jax.ShapeDtypeStruct(dims, jnp.dtype(dt))
        jax.jit(functools.partial(sparse_prefill_attention, scale=1.0,
                                  block_k=block)).lower(
            shape(B, KV, G * bq, Dk), shape(B, KV, L, Dk),
            shape(B, KV, L, Dv), shape(B, bq, L, dt=bool),
            shape(dt=jnp.int32)).compile()

    signature = (B, KV, G, bq, L, Dk, Dv, str(jnp.dtype(dtype)), block)
    return block if probe_compile("sparse_prefill_attention", signature,
                                  compile_fn) else None
