"""Absorbed latent attention of a decode step, read from the ring in place.

A decode step of :mod:`mxnet_tpu.models.deepseek` attends, a slot, over the
``index_topk`` positions its indexer chose among the ``n_valid`` the slot's
latent ring holds.  The XLA form gathers the chosen rows out of the ring
([S, K, stride], 168 MB a layer at 64 slots x 2,048 x 640 in bfloat16, at a
tenth of the peak's rate), and then writes float32 scores and probabilities
for every head.  This kernel reads the ring where it lies instead:

- grid ``(slot, block of ring positions)``; the selection comes as a mask
  over the ring, so a block is one contiguous read of ``block`` rows;
- a block past the slot's ``n_valid`` is neither fetched (the index map
  clamps to the slot's last valid block, and the pipeline keeps the block
  it has) nor computed (``pl.when``);
- online softmax: float32 running maximum, sum and accumulator in VMEM;
  scores float32, probabilities cast to the activations' type for the
  product with ``c_kv``, accumulated in float32: the casts of the XLA form.
  No gathered copy, no score and no probability tensor reaches HBM.

The ring comes in the type it is stored in and is cast a block at a time.
``latent_ring_attention_ref`` is the plain masked softmax, for tests.
"""
from __future__ import annotations

import functools

# positions a block, largest first: 1,024 rows of 640 bfloat16 are 1.3 MB,
# twice (the pipeline's two buffers) beside [128, 1024] float32 scores and
# a [128, 512] accumulator: under 5 MB of VMEM.  At 64 slots x 6,144 on a
# v5e a block of 1,024 took 0.73 ms a call, 512 0.80, 256 1.03 and 2,048
# 0.81 though it reads more rows (my chip runs, PR 34): a grid step costs
# as much as a hundred rows
BLOCKS = (1024, 512, 256, 128)

_MASKED = -1e30


def pick_block(ring_len):
    """The largest of :data:`BLOCKS` that divides ``ring_len``, or None."""
    for b in BLOCKS:
        if ring_len % b == 0:
            return b
    return None


def last_valid_block(n_valid, block):
    """Index of the last block that holds a valid position, for
    ``n_valid >= 1`` positions from the ring's start.  An index map clamps
    to it so that the blocks past it are not fetched; written apart so
    that a kernel over grouped key / value rings can take it too."""
    return (n_valid - 1) // block


def rows_visited(n_valid, block):
    """Ring rows the kernel reads for a slot: whole blocks."""
    return (last_valid_block(n_valid, block) + 1) * block


def _kernel(kvr, scale, nv_ref, q_ref, ring_ref, bias_ref, o_ref,
            m_sc, l_sc, acc_sc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    s, j = pl.program_id(0), pl.program_id(1)
    block = ring_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, f32)
        l_sc[...] = jnp.zeros(l_sc.shape, f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    @pl.when(j * block < nv_ref[s])
    def _():
        q = q_ref[0]                                      # [H, stride]
        rows = ring_ref[0].astype(q.dtype)                # [block, stride]
        # q is (q_abs, q_rope, 0): one product over the row as stored
        sc = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)  # [H, block]
        sc = sc * scale + bias_ref[0]          # 0 where selected, _MASKED
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, sc.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        # an unselected row's exp is 0 once a selected one has raised the
        # maximum; until then (m_new == _MASKED) it is 1, and the first
        # selected row's alpha, exp(_MASKED - m_new) == 0, wipes it
        p = jnp.exp(sc - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(q.dtype), rows[:, :kvr], preferred_element_type=f32)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # nothing selected at all: the maximum never left _MASKED
        o = jnp.where(m_sc[...] > _MASKED, acc_sc[...] / l_sc[...], 0.0)
        o_ref[0] = o.astype(o_ref.dtype)


def latent_ring_attention(q_abs, q_rope, ring, mask, n_valid, scale, *,
                          block=None, interpret=False):
    """``o`` [S, H, kvr]: softmax over the positions ``mask`` [S, M] keeps
    of ``(q_abs . c_kv + q_rope . k_rope) * scale``, times ``c_kv``, where a
    row of ``ring`` [S, M, stride] is ``(c_kv [kvr], k_rope [r], padding)``.

    ``q_abs`` [S, H, kvr] and ``q_rope`` [S, H, r] are in the activations'
    type, which is also ``o``'s; ``ring`` is in the type it is stored in;
    ``mask`` keeps nothing at or past ``n_valid`` [S] (>= 1), which is what
    lets the blocks past it go unread.  A slot whose mask keeps nothing
    gets zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, kvr = q_abs.shape
    M, stride = ring.shape[1:]
    block = pick_block(M) if block is None else block
    if block is None or M % block:
        raise ValueError(f"no block of {BLOCKS} divides a ring of {M}")
    pad = stride - kvr - q_rope.shape[-1]
    # the padding's product is 0 whatever the ring holds there only if the
    # ring holds numbers: it holds the zeros _ring_row wrote
    q = jnp.concatenate(
        [q_abs, q_rope, jnp.zeros((S, H, pad), q_abs.dtype)], axis=-1)

    def at_block(s, j, nv):
        return jnp.minimum(j, last_valid_block(nv[s], block))

    return pl.pallas_call(
        functools.partial(_kernel, kvr, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, M // block),
            in_specs=[
                pl.BlockSpec((1, H, stride), lambda s, j, nv: (s, 0, 0)),
                pl.BlockSpec((1, block, stride),
                             lambda s, j, nv: (s, at_block(s, j, nv), 0)),
                pl.BlockSpec((1, 1, block),
                             lambda s, j, nv: (s, 0, at_block(s, j, nv))),
            ],
            out_specs=pl.BlockSpec((1, H, kvr), lambda s, j, nv: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, kvr), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, kvr), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="latent_ring_attention",
        interpret=interpret,
    )(n_valid.astype(jnp.int32), q, ring,
      jnp.where(mask, 0.0, _MASKED).astype(jnp.float32).reshape(S, 1, M))


def latent_ring_attention_ref(q_abs, q_rope, ring, mask, scale):
    """The plain masked softmax over the whole ring, the XLA form's casts."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    kvr, r = q_abs.shape[-1], q_rope.shape[-1]
    rows = ring.astype(q_abs.dtype)
    s = jnp.einsum("shc,smc->shm", q_abs, rows[..., :kvr],
                   preferred_element_type=f32) \
        + jnp.einsum("shr,smr->shm", q_rope, rows[..., kvr:kvr + r],
                     preferred_element_type=f32)
    s = jnp.where(mask[:, None], s * scale, _MASKED)
    p = jax.nn.softmax(s, axis=-1).astype(q_abs.dtype)
    return jnp.einsum("shm,smc->shc", p, rows[..., :kvr],
                      preferred_element_type=f32).astype(q_abs.dtype)


def kernel_block(S, H, kvr, r, M, stride, dtype, ring_dtype):
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
        jax.jit(functools.partial(latent_ring_attention, scale=1.0,
                                  block=block)).lower(
            shape(S, H, kvr), shape(S, H, r),
            shape(S, M, stride, dt=ring_dtype), shape(S, M, dt=bool),
            shape(S, dt=jnp.int32)).compile()

    signature = (S, H, kvr, r, M, stride, str(jnp.dtype(dtype)),
                 str(jnp.dtype(ring_dtype)), block)
    return block if probe_compile("latent_ring_attention", signature,
                                  compile_fn) else None
