"""Fused residual-add + dropout + LayerNorm Pallas op for TPU.

Reference semantics: the post-LN transformer layer glue
``ln(x + dropout(inner))`` (GluonNLP BERTEncoder / src/operator/nn/
layer_norm.cc).  XLA runs this as 3+ separate HBM passes per direction
(dropout mask multiply, add, LN stats, LN apply; backward mirrors them) —
profiling puts the chains at ~0.6-0.9 ms/layer on BERT-base.  This op
does each direction in ONE pass per row block:

- forward: pre = x + inner * mask (in-kernel regenerable PRNG dropout),
  row mean/rstd over the feature dim, out = gamma * xhat + beta.  Side
  outputs: ``pre`` (bf16, the same residual-sum tensor the layer path
  materializes anyway) and per-row mean/rstd (f32).
- backward: ONE kernel emits dx (= dpre), dinner (= dpre * mask), and
  f32 VMEM-accumulated dgamma/dbeta; dpre is the standard LN backward
  rstd * (g·dy - mean(g·dy) - xhat * mean(g·dy · xhat)).

Layout: (B, L, d) blocks of (1, R, d), weights/stat vectors resident —
the ffn_fused.py conventions.
"""
from __future__ import annotations

import functools

from .flash_attention import _kernel_dropout_mult


def _resln_fwd_kernel(dropout, has_do, eps, *refs):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = 0
    sd_ref = None
    if has_do:
        sd_ref = refs[0]
        i = 1
    (x_ref, in_ref, g_ref, b_ref,
     y_ref, pre_ref, mean_ref, rstd_ref) = refs[i:]

    # blocks are (B, R, d) — whole batch, R rows of L (pallas wants the
    # last two block dims tile-aligned or full, which rules out (1, R)
    # stat blocks; (B, R) with B equal to the array dim is legal)
    x = x_ref[...].astype(jnp.float32)
    inner = in_ref[...].astype(jnp.float32)
    if has_do:
        inner *= _kernel_dropout_mult(dropout, sd_ref, pl.program_id(0),
                                      inner.shape)
    # round the residual sum to storage dtype BEFORE the stats: the layer
    # path materializes the bf16 sum and the backward recomputes xhat
    # from the saved bf16 pre — stats must see the same values
    pre = (x + inner).astype(pre_ref.dtype)
    pre_ref[...] = pre
    # the shared cancellation-floor one-pass moments (ndarray/ops.py):
    # the unclamped E[x^2]-E[x]^2 can go negative when |mean| >> std,
    # turning rstd into NaN
    from ..ndarray.ops import _one_pass_moments
    pre = pre.astype(jnp.float32)
    mean, var = _one_pass_moments(jnp, pre, -1)
    rstd = 1.0 / jnp.sqrt(var + eps)
    mean_ref[...] = mean
    rstd_ref[...] = rstd
    xhat = (pre - mean[..., None]) * rstd[..., None]
    y = xhat * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _resln_bwd_kernel(dropout, has_do, *refs):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = 0
    sd_ref = None
    if has_do:
        sd_ref = refs[0]
        i = 1
    (dy_ref, pre_ref, g_ref, mean_ref, rstd_ref,
     dx_ref, din_ref, dg_ref, db_ref, ag, ab) = refs[i:]

    i = pl.program_id(0)
    n = pl.num_programs(0)

    dy = dy_ref[...].astype(jnp.float32)
    pre = pre_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (pre - mean[..., None]) * rstd[..., None]

    gdy = dy * g_ref[...].astype(jnp.float32)
    m1 = jnp.mean(gdy, axis=-1)
    m2 = jnp.mean(gdy * xhat, axis=-1)
    dpre = rstd[..., None] * (gdy - m1[..., None] - xhat * m2[..., None])
    dx_ref[...] = dpre.astype(dx_ref.dtype)
    dinner = dpre
    if has_do:
        dinner = dinner * _kernel_dropout_mult(dropout, sd_ref, i,
                                               dinner.shape)
    din_ref[...] = dinner.astype(din_ref.dtype)

    dg = jnp.sum(dy * xhat, axis=(0, 1))[None]
    db = jnp.sum(dy, axis=(0, 1))[None]

    @pl.when(i == 0)
    def _init():
        ag[...] = dg
        ab[...] = db

    @pl.when(i > 0)
    def _acc():
        ag[...] += dg
        ab[...] += db

    @pl.when(i == n - 1)
    def _flush():
        dg_ref[...] = ag[...].astype(dg_ref.dtype)
        db_ref[...] = ab[...].astype(db_ref.dtype)


def _pick_rows(B, L, d, itemsize=2):
    """Largest L-block with the whole-batch (B, R, d) operand tiles (x,
    inner, y, pre + f32 temps) comfortably inside VMEM."""
    for r in (1024, 512, 256, 128):
        if L % r == 0 and B * r * d * itemsize <= 9 * 2 ** 20:
            return r
    return None


def _fwd_call(x3, inner, gamma, beta, dropout, seed, eps):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from .ffn_fused import _call

    B, L, d = x3.shape
    R = _pick_rows(B, L, d, x3.dtype.itemsize)
    has_do = dropout > 0.0 and seed is not None
    scalars = [seed.astype(jnp.int32)] if has_do else []
    nm = (lambda j, *a: (0, j, 0))
    nm2 = (lambda j, *a: (0, j))
    cm = (lambda j, *a: (0, 0))
    y, pre, mean, rstd = _call(
        functools.partial(_resln_fwd_kernel, float(dropout), has_do,
                          float(eps)),
        (L // R,),
        [pl.BlockSpec((B, R, d), nm), pl.BlockSpec((B, R, d), nm),
         pl.BlockSpec((1, d), cm), pl.BlockSpec((1, d), cm)],
        [pl.BlockSpec((B, R, d), nm), pl.BlockSpec((B, R, d), nm),
         pl.BlockSpec((B, R), nm2), pl.BlockSpec((B, R), nm2)],
        [jax.ShapeDtypeStruct((B, L, d), x3.dtype),
         jax.ShapeDtypeStruct((B, L, d), x3.dtype),
         jax.ShapeDtypeStruct((B, L), jnp.float32),
         jax.ShapeDtypeStruct((B, L), jnp.float32)],
        [], scalars,
        (x3, inner, gamma.reshape(1, d), beta.reshape(1, d)))
    return y, pre, mean, rstd


def _bwd_call(dy, pre, gamma, mean, rstd, dropout, seed):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .ffn_fused import _call

    B, L, d = dy.shape
    R = _pick_rows(B, L, d, dy.dtype.itemsize)
    has_do = dropout > 0.0 and seed is not None
    scalars = [seed.astype(jnp.int32)] if has_do else []
    nm = (lambda j, *a: (0, j, 0))
    nm2 = (lambda j, *a: (0, j))
    cm = (lambda j, *a: (0, 0))
    dx, dinner, dg, db = _call(
        functools.partial(_resln_bwd_kernel, float(dropout), has_do),
        (L // R,),
        [pl.BlockSpec((B, R, d), nm), pl.BlockSpec((B, R, d), nm),
         pl.BlockSpec((1, d), cm), pl.BlockSpec((B, R), nm2),
         pl.BlockSpec((B, R), nm2)],
        [pl.BlockSpec((B, R, d), nm), pl.BlockSpec((B, R, d), nm),
         pl.BlockSpec((1, d), cm), pl.BlockSpec((1, d), cm)],
        [jax.ShapeDtypeStruct((B, L, d), dy.dtype),
         jax.ShapeDtypeStruct((B, L, d), dy.dtype),
         jax.ShapeDtypeStruct((1, d), gamma.dtype),
         jax.ShapeDtypeStruct((1, d), gamma.dtype)],
        [pltpu.VMEM((1, d), jnp.float32),
         pltpu.VMEM((1, d), jnp.float32)],
        scalars, (dy, pre, gamma.reshape(1, d), mean, rstd))
    return dx, dinner, dg.reshape(d), db.reshape(d)


@functools.partial(__import__("jax").custom_vjp, nondiff_argnums=(4, 6))
def residual_ln(x3, inner, gamma, beta, dropout=0.0, seed=None, eps=1e-12):
    y, _, _, _ = _fwd_call(x3, inner, gamma, beta, dropout, seed, eps)
    return y


def _rl_fwd(x3, inner, gamma, beta, dropout, seed=None, eps=1e-12):
    y, pre, mean, rstd = _fwd_call(x3, inner, gamma, beta, dropout, seed,
                                   eps)
    return y, (pre, gamma, mean, rstd, seed)


def _rl_bwd(dropout, eps, res, dy):
    pre, gamma, mean, rstd, seed = res
    dx, dinner, dg, db = _bwd_call(dy, pre, gamma, mean, rstd, dropout,
                                   seed)
    return dx, dinner, dg, db, None


residual_ln.defvjp(_rl_fwd, _rl_bwd)


def residual_ln_ref(x3, inner, gamma, beta, eps=1e-12):
    """Pure-jnp reference (no dropout) for parity tests."""
    import jax.numpy as jnp
    from ..ndarray.ops import _one_pass_moments
    pre = x3.astype(jnp.float32) + inner.astype(jnp.float32)
    # same cancellation-floor moments as the kernel, so parity tests
    # compare against the guarded form
    mean, var = _one_pass_moments(jnp, pre, -1, keepdims=True)
    xhat = (pre - mean) / jnp.sqrt(var + eps)
    return (xhat * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x3.dtype)


def use_residual_ln(B, L, d, dtype="bfloat16", dropout=0.0,
                    param_dtype=None):
    """True when the fused residual+dropout+LN op applies and compiles on
    this platform (TPU, single-device mesh, tiled shapes).

    ``param_dtype``: gamma/beta dtype when it differs from the activation
    dtype (AMP keeps LN params fp32) — the probe compiles the EXACT
    mixed-dtype kernel variant the model will run (the kernel itself is
    dtype-agnostic: every operand is astype'd to f32 internally, no
    dot_general)."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    if not kernel_dispatch_allowed():
        return False
    itemsize = jnp.dtype(dtype).itemsize
    if _pick_rows(B, L, d, itemsize) is None or d % 128:
        return False
    # below ~16 MB per tensor the per-call launch overhead of 2-3 extra
    # custom calls per layer outweighs the pass fusion (measured:
    # transformer_base at (32, 128, 512) loses ~2%; BERT-base at
    # (32, 512, 768) wins ~8%) — let XLA's fusions handle small glue
    if B * L * d * itemsize < 16 * 2 ** 20:
        return False
    pdt = jnp.dtype(param_dtype) if param_dtype is not None \
        else jnp.dtype(dtype)

    def compile_fn():
        dt = jnp.dtype(dtype)
        xr = jnp.zeros((B, L, d), dt)
        sd = jnp.zeros((1,), jnp.int32) if dropout > 0 else None

        def probe_loss(*a):
            return residual_ln(*a, float(dropout), sd) \
                .astype(jnp.float32).sum()

        jax.jit(jax.grad(probe_loss, argnums=(0, 1, 2, 3))) \
            .lower(xr, xr, jnp.zeros((d,), pdt),
                   jnp.zeros((d,), pdt)).compile()

    return probe_compile(
        "residual_ln_fwd_bwd",
        (B, L, d, str(dtype), float(dropout), str(pdt)), compile_fn)


def residual_ln_nd(x3, inner, gamma, beta, dropout=0.0, eps=1e-12):
    """NDArray-facing fused ln(x + dropout(inner)) (post-LN glue)."""
    from ..ndarray.ndarray import apply_op
    from .flash_attention import _attn_seed
    seed = _attn_seed(dropout)
    rate = dropout if seed is not None else 0.0
    if seed is not None:
        return apply_op(
            lambda x_, i_, g_, b_, sd: residual_ln(
                x_, i_, g_, b_, rate, sd, eps),
            x3, inner, gamma, beta, seed, op_name="residual_ln")
    return apply_op(
        lambda x_, i_, g_, b_: residual_ln(x_, i_, g_, b_, 0.0, None, eps),
        x3, inner, gamma, beta, op_name="residual_ln")
