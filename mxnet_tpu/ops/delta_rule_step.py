"""One decode step of the gated delta rule over a slot's float32 states,
each read once and written once, in place.

A KDA layer of :mod:`mxnet_tpu.models.solar` keeps a state ``S`` [K, V]
float32 a head and a slot, and every decode step rewrites it whole:

    S1 = Diag(exp g) S,   u = beta (v - S1^T k),
    o = S1^T q + (q . k) u,   S' = S1 + k u^T.

The XLA form (``models/parts.py::delta_rule_step``) cannot do this on one
read: ``o`` and ``S'`` both hang on a reduction over ``K`` of the decayed
state, so XLA splits the work at the reduction, and a step reads each
state twice (one fusion for ``S1^T k`` and ``S1^T q``, one for the decay,
the rank-one write and the active slots' select) and writes it once.
This kernel holds a block of heads' states in VMEM and does all of it on
one read:

- grid ``(slot, block of heads)``; the state is aliased input to output,
  so the caller's donated buffer is written where it lies and no
  state-sized temporary appears in the program;
- ``act`` [S] comes ahead of the grid (scalar prefetch): an inactive
  slot writes back the state it read, bit for bit (a select, not a
  branch); ``o`` is computed for every slot, as the XLA form computes it;
- a head at a time inside the block, its decays, keys and queries
  taken from one transpose of the block's rows: the block's broadcasts
  made all at once spill the registers, and held a call to 557 GB/s;
- float32 throughout, the products over ``K`` elementwise on the vector
  unit and summed in float32: the arithmetic of the XLA form, summed in
  another order.

At 128 slots x 64 heads of 128 x 128 on a v5e a call takes 1.69 ms, the
time of a kernel that only copies the states through the same blocks
(636 GB/s read and written), where the XLA form takes 2.57 (PERF.md).
"""
from __future__ import annotations

import functools

__all__ = ["delta_rule_step", "kernel_heads", "pick_heads", "HEADS"]

# heads a block, the first that divides the layer's: a block of 16 heads
# of 128 x 128 float32 is 1 MiB of state, read and written through the
# pipeline's two buffers each way.  On a v5e at Solar's widths a call took
# 1.69 ms at 16 and 1.92 at 8, where the vector work outlasts the copies
# (PERF.md)
HEADS = (16, 8)


def pick_heads(heads):
    """The first of :data:`HEADS` that divides ``heads``, or None."""
    for b in HEADS:
        if heads % b == 0:
            return b
    return None


def _kernel(act_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref,
            out_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    active = act_ref[pl.program_id(0)] > 0
    q, k = q_ref[0], k_ref[0]                              # [hb, K]
    qk = (q * k).sum(-1, keepdims=True)                    # [hb, 1]
    # the block's decays, keys and queries transposed once, so that a
    # head's lie down the sublanes, whence they broadcast across its lanes
    eT, kT, qT = jnp.exp(g_ref[0]).T, k.T, q.T             # [K, hb]
    for h in range(s_ref.shape[1]):
        e, kc, qc = eT[:, h:h + 1], kT[:, h:h + 1], qT[:, h:h + 1]
        state = s_ref[0, h]                                # [K, V]
        s1 = state * e
        u = b_ref[0, h:h + 1] * (v_ref[0, h:h + 1]
                                 - (s1 * kc).sum(0, keepdims=True))
        # S'^T q = S1^T q + (k . q) u: the new state is not read back
        o_ref[0, h:h + 1] = (s1 * qc).sum(0, keepdims=True) \
            + qk[h:h + 1] * u
        out_ref[0, h] = jnp.where(active, s1 + kc * u, state)


def delta_rule_step(q, k, v, g, beta, state, act, *, heads=None,
                    interpret=False):
    """``(o [S, H, V], state')`` float32 of one step of the rule: ``q``,
    ``k``, ``g`` [S, H, K], ``v`` [S, H, V], ``beta`` [S, H], ``state``
    [S, H, K, V] float32 (written in place where the caller donates it)
    and ``act`` [S]: a slot with ``act`` 0 keeps its state."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, K = q.shape
    V = v.shape[-1]
    heads = pick_heads(H) if heads is None else heads
    if heads is None or H % heads:
        raise ValueError(f"no block of heads divides {H}")

    def rows(n):
        return pl.BlockSpec((1, heads, n), lambda s, j, a: (s, j, 0))
    states = pl.BlockSpec((1, heads, K, V), lambda s, j, a: (s, j, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, H // heads),
            in_specs=[rows(K), rows(K), rows(V), rows(K), rows(1), states],
            out_specs=[rows(V), states]),
        out_shape=[jax.ShapeDtypeStruct((S, H, V), f32),
                   jax.ShapeDtypeStruct((S, H, K, V), f32)],
        # operand 6 counts the scalar-prefetched act: the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=7 * S * H * K * V, transcendentals=S * H * K,
            bytes_accessed=2 * S * H * K * V * 4
            + 4 * S * H * (3 * K + 2 * V + 1)),
        name="delta_rule_step",
        interpret=interpret,
    )(act.astype(jnp.int32), q.astype(f32), k.astype(f32), v.astype(f32),
      g.astype(f32), beta.astype(f32)[..., None], state)


def kernel_heads(S, H, K, V, dtype):
    """The heads a block the kernel runs at for a state [S, H, K, V] of
    ``dtype``, or None where the XLA form runs: for a state not float32,
    on a CPU, under a mesh, in an ONNX export, for a head count no block
    divides, or where the chip's compiler refuses the variant (kept in
    ``kernel_report()``)."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    heads = pick_heads(H)
    if jnp.dtype(dtype) != jnp.float32 or heads is None \
            or not kernel_dispatch_allowed():
        return None

    def compile_fn():
        def shape(*dims, dt=jnp.float32):
            return jax.ShapeDtypeStruct(dims, jnp.dtype(dt))
        jax.jit(functools.partial(delta_rule_step, heads=heads)).lower(
            shape(S, H, K), shape(S, H, K), shape(S, H, V), shape(S, H, K),
            shape(S, H), shape(S, H, K, V), shape(S, dt=jnp.int32)
        ).compile()

    return heads if probe_compile("delta_rule_step", (S, H, K, V, heads),
                                  compile_fn) else None
