"""Fused position-wise FFN (Dense -> GELU -> Dense -> Dropout) Pallas
kernels for TPU.

Reference semantics: GluonNLP ``PositionwiseFFN`` (ffn_1 -> gelu(erf) ->
ffn_2 -> dropout), i.e. ``src/operator/nn/fully_connected.cc`` +
``src/operator/nn/activation.cc`` chained per-op in the reference.  On TPU
the XLA layer path runs the two matmuls at peak but round-trips the
(B*L, hidden) activations through HBM several times per training step (u
saved for backward, GELU-backward multiply chain, dropout backward), which
profiling puts at ~15 ms/step of VPU/HBM-bound loop fusions on BERT-base.

Kernel design (one grid cell = one row block, weights resident in VMEM
across the sequential grid; v5e VMEM is ~128 MB, measured):

- forward: u = x @ W1^T + b1 computed in f32 on the MXU, GELU applied
  in-register, y = gelu(u) @ W2^T + b2, output dropout from the in-kernel
  PRNG (regenerable: the backward re-draws the same mask from the same
  seed — no mask ever materializes in HBM).  The only side output is ``u``
  in bf16 (the same tensor the XLA path saves for backward anyway).
- backward: ONE kernel computes all five gradients.  Per row block:
  dyd = dy * mask, dg = dyd @ W2, du = dg * gelu'(u), dx = du @ W1, and
  f32 VMEM accumulators carry dW1 += du^T x, dW2 += dyd^T g, db1 += sum du,
  db2 += sum dyd across the (sequential) grid; the last cell casts and
  writes them.  The hidden-state gradients dg/du never touch HBM.

Weight layout follows ``nn.Dense``: W1 (hidden, units), W2 (units, hidden),
so every dot here contracts the last axis of the activation with axis 1 or
0 of the weight — all MXU-shaped (R >= 128 rows, 768/3072 lanes).
"""
from __future__ import annotations

import functools

from .flash_attention import _kernel_dropout_mult

_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327


def _erf_f32(x):
    """f32 erf from VPU primitives (Pallas TPU has no erf lowering).

    Abramowitz & Stegun 7.1.26 rational polynomial, max abs error 1.5e-7 —
    three decimal orders below bf16 resolution, so results round to the
    same bf16 values as XLA's own erf approximation."""
    import jax.numpy as jnp
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * a)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    y = 1.0 - poly * jnp.exp(-a * a)
    return jnp.sign(x) * y


def _gelu_f32(u):
    """erf-form GELU in f32 (the reference's non-approximate gelu)."""
    return 0.5 * u * (1.0 + _erf_f32(u * _SQRT_HALF))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _ffn_fwd_kernel(dropout, has_do, act, want_u, *refs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = 0
    sd_ref = None
    if has_do:
        sd_ref = refs[0]
        i = 1
    if want_u:
        x_ref, w1_ref, b1_ref, w2_ref, b2_ref, y_ref, u_ref = refs[i:]
    else:
        x_ref, w1_ref, b1_ref, w2_ref, b2_ref, y_ref = refs[i:]

    x = x_ref[...]
    u = jax.lax.dot_general(
        x, w1_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    u += b1_ref[...].astype(jnp.float32)
    if want_u:
        u_ref[...] = u.astype(u_ref.dtype)
    g = (_gelu_f32(u) if act == "gelu"
         else jnp.maximum(u, 0.0)).astype(x.dtype)
    y = jax.lax.dot_general(
        g, w2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    y += b2_ref[...].astype(jnp.float32)
    if has_do:
        y *= _kernel_dropout_mult(dropout, sd_ref, pl.program_id(0),
                                  y.shape)
    y_ref[...] = y.astype(y_ref.dtype)


def _ffn_bwd_kernel(dropout, has_do, act, *refs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = 0
    sd_ref = None
    if has_do:
        sd_ref = refs[0]
        i = 1
    (x_ref, u_ref, dy_ref, w1_ref, w2_ref,
     dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
     aw1, ab1, aw2, ab2) = refs[i:]

    i = pl.program_id(0)
    n = pl.num_programs(0)

    dy = dy_ref[...].astype(jnp.float32)
    if has_do:
        dy *= _kernel_dropout_mult(dropout, sd_ref, i, dy.shape)
    dyd = dy.astype(dy_ref.dtype)

    u = u_ref[...].astype(jnp.float32)
    if act == "gelu":
        # one erf serves both gelu(u) = u*Phi and gelu'(u) = Phi + u*phi
        phi_cdf = 0.5 * (1.0 + _erf_f32(u * _SQRT_HALF))
        g = (u * phi_cdf).astype(dy_ref.dtype)
        gprime = phi_cdf + u * (_INV_SQRT_2PI * jnp.exp(-0.5 * u * u))
    else:
        g = jnp.maximum(u, 0.0).astype(dy_ref.dtype)
        gprime = (u > 0.0).astype(jnp.float32)

    dg = jax.lax.dot_general(
        dyd, w2_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    du = (dg * gprime).astype(dy_ref.dtype)

    dx = jax.lax.dot_general(
        du, w1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    x = x_ref[...]
    dw1 = jax.lax.dot_general(           # (hidden, units)
        du, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    dw2 = jax.lax.dot_general(           # (units, hidden)
        dyd, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    db1 = jnp.sum(du.astype(jnp.float32), axis=0, keepdims=True)
    db2 = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        aw1[...] = dw1
        aw2[...] = dw2
        ab1[...] = db1
        ab2[...] = db2

    @pl.when(i > 0)
    def _acc():
        aw1[...] += dw1
        aw2[...] += dw2
        ab1[...] += db1
        ab2[...] += db2

    @pl.when(i == n - 1)
    def _flush():
        dw1_ref[...] = aw1[...].astype(dw1_ref.dtype)
        dw2_ref[...] = aw2[...].astype(dw2_ref.dtype)
        db1_ref[...] = ab1[...].astype(db1_ref.dtype)
        db2_ref[...] = ab2[...].astype(db2_ref.dtype)


def _pick_rows2d(T, d, h):
    """Largest (B*L)-flattened row block under the VMEM budget.

    Measured r5 on BERT-base (B=32, L=512): flattening across the batch
    axis with R=1024 beats the old (B, L//R) per-element grid by ~0.6%
    (93.6 vs 94.2 ms step); R=2048 REGRESSES to 113 ms — the f32 hidden
    tiles hit ~50 MB and Mosaic's cross-cell pipelining collapses.  Cap
    at 1024.  Budget: two f32 (R, h) hidden tiles + bf16 weights + f32
    weight-grad accumulators + bf16 IO tiles within the VMEM limit."""
    for r in (1024, 512, 256, 128):
        if T % r:
            continue
        vmem = 2 * r * h * 4 + 2 * h * d * 2 + 2 * h * d * 4 \
            + 3 * r * d * 2 + r * h * 2
        if vmem <= 88 * 2 ** 20:
            return r
    return None


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch_shapes,
          scalars, args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    # Mosaic's scoped-vmem default is 16 MB; v5e has ~128 MB (measured).
    # The whole-weight + f32-accumulator design needs the real budget.
    params = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)
    if scalars:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch_shapes),
            compiler_params=params,
            out_shape=out_shape)(*scalars, *args)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes,
        compiler_params=params)(*args)


def _fwd_call(x3, w1, b1, w2, b2, dropout, seed, act="gelu",
              want_u=True):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, L, d = x3.shape
    h = w1.shape[0]
    T = B * L
    R = _pick_rows2d(T, d, h)
    x2 = x3.reshape(T, d)
    has_do = dropout > 0.0 and seed is not None
    scalars = [seed.astype(jnp.int32)] if has_do else []
    nm = (lambda i, *a: (i, 0))
    cm = (lambda i, *a: (0, 0))
    out_specs = [pl.BlockSpec((R, d), nm)]
    out_shape = [jax.ShapeDtypeStruct((T, d), x3.dtype)]
    if want_u:
        # the backward's residual; the primal/eval path skips the
        # (T, hidden) HBM write entirely
        out_specs.append(pl.BlockSpec((R, h), nm))
        out_shape.append(jax.ShapeDtypeStruct((T, h), x3.dtype))
    out = _call(
        functools.partial(_ffn_fwd_kernel, float(dropout), has_do, act,
                          want_u),
        (T // R,),
        [pl.BlockSpec((R, d), nm), pl.BlockSpec((h, d), cm),
         pl.BlockSpec((1, h), cm), pl.BlockSpec((d, h), cm),
         pl.BlockSpec((1, d), cm)],
        out_specs, out_shape,
        [], scalars,
        (x2, w1, b1.reshape(1, h), w2, b2.reshape(1, d)))
    y = out[0].reshape(B, L, d)
    return (y, out[1]) if want_u else (y, None)


def _bwd_call(x3, u, dy, w1, w2, dropout, seed, act="gelu"):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, d = x3.shape
    h = w1.shape[0]
    T = B * L
    R = _pick_rows2d(T, d, h)
    x2 = x3.reshape(T, d)
    u2 = u.reshape(T, h)
    dy2 = dy.reshape(T, d)
    has_do = dropout > 0.0 and seed is not None
    scalars = [seed.astype(jnp.int32)] if has_do else []
    nm = (lambda i, *a: (i, 0))
    cm = (lambda i, *a: (0, 0))
    dx, dw1, db1, dw2, db2 = _call(
        functools.partial(_ffn_bwd_kernel, float(dropout), has_do, act),
        (T // R,),
        [pl.BlockSpec((R, d), nm), pl.BlockSpec((R, h), nm),
         pl.BlockSpec((R, d), nm), pl.BlockSpec((h, d), cm),
         pl.BlockSpec((d, h), cm)],
        [pl.BlockSpec((R, d), nm), pl.BlockSpec((h, d), cm),
         pl.BlockSpec((1, h), cm), pl.BlockSpec((d, h), cm),
         pl.BlockSpec((1, d), cm)],
        [jax.ShapeDtypeStruct((T, d), x3.dtype),
         jax.ShapeDtypeStruct((h, d), w1.dtype),
         jax.ShapeDtypeStruct((1, h), w1.dtype),
         jax.ShapeDtypeStruct((d, h), w2.dtype),
         jax.ShapeDtypeStruct((1, d), w2.dtype)],
        [pltpu.VMEM((h, d), jnp.float32),
         pltpu.VMEM((1, h), jnp.float32),
         pltpu.VMEM((d, h), jnp.float32),
         pltpu.VMEM((1, d), jnp.float32)],
        scalars, (x2, u2, dy2, w1, w2))
    return dx.reshape(B, L, d), dw1, db1.reshape(h), dw2, db2.reshape(d)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(__import__("jax").custom_vjp, nondiff_argnums=(5, 7))
def ffn_gelu(x3, w1, b1, w2, b2, dropout=0.0, seed=None, act="gelu"):
    y, _ = _fwd_call(x3, w1, b1, w2, b2, dropout, seed, act, want_u=False)
    return y


def _ffn_fwd(x3, w1, b1, w2, b2, dropout, seed=None, act="gelu"):
    y, u = _fwd_call(x3, w1, b1, w2, b2, dropout, seed, act)
    return y, (x3, u, w1, w2, seed)


def _ffn_bwd(dropout, act, res, dy):
    x3, u, w1, w2, seed = res
    dx, dw1, db1, dw2, db2 = _bwd_call(x3, u, dy, w1, w2, dropout, seed,
                                       act)
    return dx, dw1, db1, dw2, db2, None


ffn_gelu.defvjp(_ffn_fwd, _ffn_bwd)


def ffn_gelu_ref(x3, w1, b1, w2, b2, act="gelu"):
    """Pure-jnp reference (no dropout) for parity tests."""
    import jax.numpy as jnp
    u = (x3.astype(jnp.float32) @ w1.astype(jnp.float32).T
         + b1.astype(jnp.float32))
    g = _gelu_f32(u) if act == "gelu" else jnp.maximum(u, 0.0)
    return (g @ w2.astype(jnp.float32).T
            + b2.astype(jnp.float32)).astype(x3.dtype)


# ---------------------------------------------------------------------------
# dispatch + NDArray surface
# ---------------------------------------------------------------------------
def use_fused_ffn(B, L, units, hidden, dtype="bfloat16", act="gelu",
                  dropout=0.0):
    """True when the fused FFN kernel applies and compiles on this
    platform (TPU, tiled shapes, lane-aligned units/hidden).  The probe
    compiles the same kernel VARIANTS the model will run (same dropout
    rate/act; grad probe = the want_u forward + backward pair) as a
    compilability check — the model's own jit entry still compiles its
    own executable on first step."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    if not kernel_dispatch_allowed():
        return False
    if _pick_rows2d(B * L, units, hidden) is None \
            or units % 128 or hidden % 128:
        return False
    if act not in ("gelu", "relu"):
        return False

    def compile_fn():
        dt = jnp.dtype(dtype)
        xr = jnp.zeros((B, L, units), dt)
        sd = jnp.zeros((1,), jnp.int32) if dropout > 0 else None

        # probe through jax.grad: compiles the want_u=True forward +
        # the backward — the EXACT kernel pair a training step runs
        # (the primal-only kernel is a strict subset)
        def probe_loss(*a):
            return ffn_gelu(*a, float(dropout), sd, act) \
                .astype(jnp.float32).sum()

        jax.jit(jax.grad(probe_loss, argnums=(0, 1, 2, 3, 4))) \
            .lower(xr, jnp.zeros((hidden, units), dt),
                   jnp.zeros((hidden,), dt),
                   jnp.zeros((units, hidden), dt),
                   jnp.zeros((units,), dt)).compile()

    return probe_compile(
        "ffn_fused_fwd_bwd",
        (B, L, units, hidden, str(dtype), act, float(dropout)), compile_fn)


def ffn_gelu_nd(x3, w1, b1, w2, b2, dropout=0.0, act="gelu"):
    """NDArray-facing fused FFN: x (B, L, units) -> (B, L, units).

    Output dropout is applied in-kernel when training (regenerable mask,
    reference PositionwiseFFN semantics).  ``act``: "gelu" (erf) or
    "relu"."""
    from ..ndarray.ndarray import apply_op
    from .flash_attention import _attn_seed
    seed = _attn_seed(dropout)
    rate = dropout if seed is not None else 0.0
    if seed is not None:
        return apply_op(
            lambda x_, w1_, b1_, w2_, b2_, sd: ffn_gelu(
                x_, w1_, b1_, w2_, b2_, rate, sd, act),
            x3, w1, b1, w2, b2, seed, op_name="ffn_" + act)
    return apply_op(
        lambda x_, w1_, b1_, w2_, b2_: ffn_gelu(
            x_, w1_, b1_, w2_, b2_, 0.0, None, act),
        x3, w1, b1, w2, b2, op_name="ffn_" + act)
