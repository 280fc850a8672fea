"""Grouped-query attention of a decode step over a slot's valid blocks of
its key and value rings.

A decode step of :mod:`mxnet_tpu.models.lfm2` and ``keye`` attends, a
slot, over the ``n_valid`` positions its ``k`` and ``v`` rings
[S, M, KV * D] hold, every query head against its key head's ``D`` of a
row's numbers.  The XLA form (``models/parts.py::grouped_ring_attend``)
is two einsums over the whole rings with a masked softmax between them:
it reads every row of both rings whatever ``n_valid`` is.  This kernel
reads what is valid:

- grid ``(slot, block of ring positions)``; a block past the slot's
  ``n_valid`` is neither fetched nor computed (``pl.when``): past a
  slot's last valid block the index map names the next slot's first
  block, so that its fetch runs beside the last block's products and the
  slot's other steps find it there (with the map clamped to the last
  valid block instead, every slot began with a fetch nothing hid: 1.01 ms
  a call against 0.88 at 128 slots x 5,120 with 0.6-2.9 k valid on a
  v5e; PERF.md, PR 38);
- the heads stay side by side on a row's lanes, as the rings are stored:
  the query comes wide ([H, KV * D], a head's query in its key head's
  lanes, the rest zero) and the output leaves wide; the caller reduces
  it.  The rings are never reshaped.  A lane slice a key head would load
  as many 128 x 128 tiles of the rings into the matrix unit;
- with a selection ``mask`` [S, M] the kept positions come as a bias
  block; without one the kernel masks the positions at and past
  ``n_valid`` of the last block itself, from an iota;
- online softmax: float32 running maximum, sum and accumulator in VMEM;
  scores float32, probabilities cast to the activations' type for the
  product with ``v``, accumulated in float32: the casts of the XLA form.
  No score and no probability reaches HBM.

The rings come in the type they are stored in and are cast a block at a
time.
"""
from __future__ import annotations

import functools

from .latent_ring_attention import _MASKED, last_valid_block, rows_visited

__all__ = ["grouped_ring_attention", "kernel_block", "pick_block",
           "rows_visited", "BLOCKS"]

# positions a block, the first that divides the ring: two rings' blocks of
# 512 rows of 512 bfloat16 are 1 MB, twice (the pipeline's two buffers)
# beside [32, 512] float32 scores and a [32, 512] accumulator.  On a v5e a
# call took, at blocks of 512 / 1,024: 0.826 / 0.880 ms at 128 slots x
# 5,120 with 0.6-2.9 k valid, 0.975 / 0.986 at 40 x 12,288 with 6.4-9.9 k
# (2,048: 1.09; 256: 1.12 and 1.23 before the index map looked ahead): a
# larger block reads more rows past ``n_valid`` than it saves in grid
# steps (PERF.md, PR 38)
BLOCKS = (512, 256, 128)


def pick_block(ring_len):
    """The first of :data:`BLOCKS` that divides ``ring_len``, or None."""
    for b in BLOCKS:
        if ring_len % b == 0:
            return b
    return None


def _kernel(scale, masked, nv_ref, q_ref, k_ref, v_ref, *rest):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    bias_ref = rest[0] if masked else None
    o_ref, m_sc, l_sc, acc_sc = rest[-4:]
    s, j = pl.program_id(0), pl.program_id(1)
    block = k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, f32)
        l_sc[...] = jnp.zeros(l_sc.shape, f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    @pl.when(j * block < nv_ref[s])
    def _():
        q = q_ref[0]                                      # [H, W]
        sc = jax.lax.dot_general(
            q, k_ref[0].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale           # [H, block]
        if masked:
            sc = sc + bias_ref[0]              # 0 where selected, _MASKED
        else:
            at = j * block + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(at < nv_ref[s], sc, _MASKED)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, sc.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        # a masked row's exp is 0 once a kept one has raised the maximum;
        # until then (m_new == _MASKED) it is 1, and the first kept row's
        # alpha, exp(_MASKED - m_new) == 0, wipes it
        p = jnp.exp(sc - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(q.dtype), v_ref[0].astype(q.dtype),
            preferred_element_type=f32)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # nothing kept at all: the maximum never left _MASKED
        o_ref[0] = jnp.where(m_sc[...] > _MASKED, acc_sc[...] / l_sc[...],
                             0.0)


def grouped_ring_attention(q, ring_k, ring_v, n_valid, scale, mask=None, *,
                           block=None, interpret=False):
    """``o`` [S, H, W] float32: for each head of the wide query ``q``
    [S, H, W] (a head's query in its key head's lanes, zeros in the
    others') the softmax over its slot's positions of ``q . k * scale``
    times ``v``, over whole rows of the rings [S, M, W]; a head's own
    numbers of it are those in its key head's lanes.

    The positions are those before ``n_valid`` [S] (>= 1), and with a
    ``mask`` [S, M] those it keeps, which are none at or past ``n_valid``:
    that lets the blocks past it go unread.  ``q`` is in the activations'
    type, the rings in the type they are stored in.  A slot whose mask
    keeps nothing gets zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, W = q.shape
    M = ring_k.shape[1]
    block = pick_block(M) if block is None else block
    if block is None or M % block:
        raise ValueError(f"no block divides a ring of {M}")

    def at(s, j, nv):
        # past the slot's last valid block: the next slot's first, so that
        # its fetch runs beside the last block's products; the last slot
        # keeps the block it has
        last = last_valid_block(nv[s], block)
        on = jnp.logical_and(j > last, s + 1 < S)
        return jnp.where(on, s + 1, s), jnp.where(
            on, 0, jnp.minimum(j, last))

    def ring_at(s, j, nv):
        slot, blk = at(s, j, nv)
        return slot, blk, 0

    def bias_at(s, j, nv):
        slot, blk = at(s, j, nv)
        return slot, 0, blk

    ring_spec = pl.BlockSpec((1, block, W), ring_at)
    in_specs = [pl.BlockSpec((1, H, W), lambda s, j, nv: (s, 0, 0)),
                ring_spec, ring_spec]
    args = [n_valid.astype(jnp.int32), q, ring_k, ring_v]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, 1, block), bias_at))
        args.append(jnp.where(mask, 0.0, _MASKED).astype(jnp.float32)
                    .reshape(S, 1, M))
    return pl.pallas_call(
        functools.partial(_kernel, float(scale), mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, M // block),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, W), lambda s, j, nv: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, W), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="grouped_ring_attention",
        interpret=interpret,
    )(*args)


def kernel_block(S, H, W, M, dtype, ring_dtype, masked):
    """The block the kernel runs at for these shapes, or None where the
    XLA form runs: on a CPU, under a mesh, in an ONNX export, for a ring
    no block divides, or where the chip's compiler refuses the variant
    (kept in ``kernel_report()``)."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    block = pick_block(M)
    if block is None or not kernel_dispatch_allowed():
        return None

    def compile_fn():
        def shape(*dims, dt=dtype):
            return jax.ShapeDtypeStruct(dims, jnp.dtype(dt))
        ring = shape(S, M, W, dt=ring_dtype)
        jax.jit(functools.partial(grouped_ring_attention, scale=1.0,
                                  block=block)).lower(
            shape(S, H, W), ring, ring, shape(S, dt=jnp.int32),
            **({"mask": shape(S, M, dt=bool)} if masked else {})).compile()

    signature = (S, H, W, M, str(jnp.dtype(dtype)),
                 str(jnp.dtype(ring_dtype)), bool(masked), block)
    return block if probe_compile("grouped_ring_attention", signature,
                                  compile_fn) else None
