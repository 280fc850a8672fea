"""Fused conv+BN+ReLU blocks for TPU ResNets (Pallas).

Reference parity target: the cuDNN-fused Conv+BatchNorm+Activation path the
reference uses for its ResNet-50 headline (``src/operator/nn/convolution.cc``,
``src/operator/nn/batch_norm.cc`` with CUDNN_BATCHNORM_SPATIAL_PERSISTENT +
conv activation fusion).  TPU-first redesign rather than a translation:

* Activations flow as ``(R, C)`` matrices — flattened NHWC rows (``R = N*H*W``,
  channels on the lane dimension).  A 1x1 conv IS a matmul in this layout; a
  3x3 stride-1 conv is a 9-tap shifted-row matmul accumulation.
* Each kernel reads the RAW previous conv output ``z`` and applies the
  previous BatchNorm's ``scale/shift`` + ReLU inline during the operand read,
  computes its conv, and writes its own raw output plus per-channel
  ``(sum, sum_sq)``.  The BN-apply tensor therefore NEVER materializes in HBM
  — the structural reason XLA's step is HBM-bound (measured: XLA materializes
  conv-out + BN-out per layer; benchmark/conv_block_proto.py shows the fused
  read-once form 1.4-2.7x faster at ResNet layer-1/2 shapes).
* The BatchNorm *backward*'s mean-subtraction terms are not hand-assembled:
  each kernel's vjp returns cotangents for its ``(z, stats)`` outputs, and the
  ``stats -> scale/shift`` scalar glue (`bn_affine`) is plain differentiable
  jnp, so composing the vjps reproduces the exact batch-norm gradient.

Stats use the same one-pass E[x^2]-E[x]^2 form with the fp32 cancellation
floor as ``ndarray.ops._one_pass_moments`` (numerics match the unfused path).

Multi-chip note: under a >1-device mesh the fused model falls back to the
unfused op path (XLA cannot auto-partition custom calls); the headline bench
and single-chip training use it, SPMD sharding keeps the standard path.
"""
from __future__ import annotations

import functools

__all__ = ["matmul_stats", "conv3x3_stats", "bn_affine", "subsample2d",
           "fused_resnet_forward", "fused_supported"]

_INTERPRET_TEST = False        # parity tests force interpret-mode kernels
_VMEM_BUDGET = 10 * 2 ** 20    # row-block (streamed) bytes per grid step
# fixed-resident bytes (weights + whole-kernel accumulators): these sit in
# VMEM once, not per-block — budgeted separately so the wide-channel
# stages' backwards (e.g. 9*512*512 dw accumulators, ~24 MB) still take
# the Pallas path; rows+fixed stays under the 64 MB compiler limit
_VMEM_FIXED = 40 * 2 ** 20


def _jnp():
    import jax.numpy as jnp
    return jnp


def _cp():
    """Raise the scoped-VMEM ceiling: block-size estimates are approximate
    (concat/slice temporaries cost ~2-3x the operand blocks) and v5e has
    128 MiB physical VMEM; 64 MiB is the proven-safe setting the packed
    attention kernels already use."""
    if _INTERPRET_TEST:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _on_tpu():
    import jax
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:  # pragma: no cover - no backend
        return False


def _use_pallas(R, W=1):
    if _INTERPRET_TEST:
        return True
    return _on_tpu() and R % W == 0


# ---------------------------------------------------------------------------
# block-row selection
# ---------------------------------------------------------------------------
def _pick_br(R, per_row_bytes, mult=1, cap=4096):
    """Largest BR dividing R, multiple of ``mult``, with VMEM use in budget."""
    budget = _VMEM_BUDGET
    best = None
    br = mult
    while br <= min(R, cap):
        if R % br == 0 and br * per_row_bytes <= budget:
            best = br
        br += mult
    return best


# ---------------------------------------------------------------------------
# 1x1 conv (matmul) + stats
# ---------------------------------------------------------------------------
def _mm_fwd_pallas(x, w, scale, shift, affine, relu, br):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Cin = x.shape
    Cout = w.shape[1]
    grid = R // br

    def kernel(x_ref, sc_ref, sh_ref, w_ref, z_ref, st_ref, acc):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        xv = x_ref[...]
        if affine:
            a32 = xv.astype(jnp.float32) * sc_ref[...] + sh_ref[...]
        else:
            a32 = xv.astype(jnp.float32)
        if relu:
            a32 = jnp.maximum(a32, 0.0)
        a = a32.astype(xv.dtype)
        z = jax.lax.dot_general(a, w_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        z_ref[...] = z.astype(z_ref.dtype)
        acc[0, :] += jnp.sum(z, axis=0)
        acc[1, :] += jnp.sum(z * z, axis=0)

        @pl.when(i == grid - 1)
        def _fin():
            st_ref[...] = acc[...]

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, Cin), lambda i: (i, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, Cout), lambda i: (i, 0)),
            pl.BlockSpec((2, Cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Cout), x.dtype),
            jax.ShapeDtypeStruct((2, Cout), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((2, Cout), jnp.float32)],
        compiler_params=_cp(),
        interpret=_INTERPRET_TEST,
    )(x, scale.reshape(1, -1), shift.reshape(1, -1), w)


def _mm_bwd_pallas(gz, z, x, w, scale, shift, gst, affine, relu, br):
    """dgrad + wgrad in ONE pass over (gz, z, x).

    gz_eff = gz + gst[0] + 2*z*gst[1]   (the stats-output cotangent folds in)
    da     = gz_eff @ w^T
    dy     = da * relu'(y),  y = affine(x)
    dx     = dy * scale ; dsums = (sum dy, sum dy*x) ; dw = act(y)^T @ gz_eff
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Cin = x.shape
    Cout = w.shape[1]
    grid = R // br

    def kernel(gz_ref, z_ref, x_ref, gst_ref, sc_ref, sh_ref, w_ref,
               dx_ref, dw_ref, ds_ref, accw, accs):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            accw[...] = jnp.zeros_like(accw)
            accs[...] = jnp.zeros_like(accs)

        gze32 = (gz_ref[...].astype(jnp.float32)
                 + gst_ref[0, :][None, :]
                 + 2.0 * z_ref[...].astype(jnp.float32)
                 * gst_ref[1, :][None, :])
        gze = gze32.astype(gz_ref.dtype)
        da = jax.lax.dot_general(gze, w_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        xv = x_ref[...]
        x32 = xv.astype(jnp.float32)
        if affine:
            y = x32 * sc_ref[...] + sh_ref[...]
        else:
            y = x32
        if relu:
            dy = jnp.where(y > 0.0, da, 0.0)
            a = jnp.maximum(y, 0.0).astype(xv.dtype)
        else:
            dy = da
            a = y.astype(xv.dtype)
        if affine:
            dx_ref[...] = (dy * sc_ref[...]).astype(dx_ref.dtype)
        else:
            dx_ref[...] = dy.astype(dx_ref.dtype)
        accs[0, :] += jnp.sum(dy, axis=0)
        accs[1, :] += jnp.sum(dy * x32, axis=0)
        accw[...] += jax.lax.dot_general(
            a, gze, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(i == grid - 1)
        def _fin():
            dw_ref[...] = accw[...]
            ds_ref[...] = accs[...]

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, Cout), lambda i: (i, 0)),
            pl.BlockSpec((br, Cout), lambda i: (i, 0)),
            pl.BlockSpec((br, Cin), lambda i: (i, 0)),
            pl.BlockSpec((2, Cout), lambda i: (0, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, Cin), lambda i: (i, 0)),
            pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
            pl.BlockSpec((2, Cin), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Cin), x.dtype),
            jax.ShapeDtypeStruct((Cin, Cout), jnp.float32),
            jax.ShapeDtypeStruct((2, Cin), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Cin, Cout), jnp.float32),
                        pltpu.VMEM((2, Cin), jnp.float32)],
        compiler_params=_cp(),
        interpret=_INTERPRET_TEST,
    )(gz, z, x, gst, scale.reshape(1, -1), shift.reshape(1, -1), w)


def _mm_ref(x, w, scale, shift, affine, relu):
    import jax
    jnp = _jnp()
    x32 = x.astype(jnp.float32)
    y = x32 * scale[None, :] + shift[None, :] if affine else x32
    a32 = jnp.maximum(y, 0.0) if relu else y
    z = jax.lax.dot_general(a32.astype(x.dtype), w,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    st = jnp.stack([jnp.sum(z, axis=0), jnp.sum(z * z, axis=0)])
    return z.astype(x.dtype), st


@functools.lru_cache(maxsize=None)
def _mm_op(affine, relu, pallas_fwd, pallas_bwd):
    import jax
    jnp = None  # populated lazily inside closures

    def value(x, w, scale, shift):
        if pallas_fwd:
            R, Cin = x.shape
            Cout = w.shape[1]
            rb = 2 * (2 * (Cin + Cout) * 2 + 6 * max(Cin, Cout))
            br = _pick_br(R, rb + 1, mult=8 if R % 8 == 0 else 1)
            if br is not None:
                return _mm_fwd_pallas(x, w, scale, shift, affine, relu, br)
        return _mm_ref(x, w, scale, shift, affine, relu)

    def fwd(x, w, scale, shift):
        z, st = value(x, w, scale, shift)
        return (z, st), (x, w, scale, shift, z)

    def bwd(res, g):
        import jax.numpy as jnp
        x, w, scale, shift, z = res
        gz, gst = g
        R, Cin = x.shape
        Cout = w.shape[1]
        if pallas_bwd:
            rb = 2 * (2 * (Cin + Cout) * 2 + 2 * Cin * 2
                      + 8 * max(Cin, Cout))
            fixed = Cin * Cout * (2 + 4 + 4) + 1
            br = _pick_br(R, rb + 1, mult=8 if R % 8 == 0 else 1,
                          cap=max(1, _VMEM_BUDGET // max(rb, 1)))
            if br is not None and fixed < _VMEM_FIXED:
                dx, dw, ds = _mm_bwd_pallas(gz, z, x, w, scale, shift, gst,
                                            affine, relu, br)
                dscale = ds[1] if affine else jnp.zeros_like(scale)
                dshift = ds[0] if affine else jnp.zeros_like(shift)
                return dx, dw.astype(w.dtype), dscale, dshift
        gze32 = (gz.astype(jnp.float32) + gst[0][None, :]
                 + 2.0 * z.astype(jnp.float32) * gst[1][None, :])
        gze = gze32.astype(gz.dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * scale[None, :] + shift[None, :] if affine else x32
        import jax as _jax
        da = _jax.lax.dot_general(gze, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dy = jnp.where(y > 0.0, da, 0.0) if relu else da
        a = (jnp.maximum(y, 0.0) if relu else y).astype(x.dtype)
        dw = _jax.lax.dot_general(a, gze, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if affine:
            dx = (dy * scale[None, :]).astype(x.dtype)
            dscale = jnp.sum(dy * x32, axis=0)
            dshift = jnp.sum(dy, axis=0)
        else:
            dx = dy.astype(x.dtype)
            dscale = jnp.zeros_like(scale)
            dshift = jnp.zeros_like(shift)
        return dx, dw.astype(w.dtype), dscale, dshift

    f = jax.custom_vjp(value)
    f.defvjp(fwd, bwd)
    return f


def matmul_stats(x, w, scale=None, shift=None, relu=False, pallas=None):
    """z = act(x*scale+shift) @ w  plus per-channel (sum, sum_sq) of z.

    x: (R, Cin); w: (Cin, Cout); scale/shift: (Cin,) fp32 or None.
    Returns (z (R, Cout) in x.dtype, stats (2, Cout) fp32).
    ``pallas``: False forces the jnp reference form; True/None request the
    Pallas kernel, still subject to the feasibility gate (TPU backend,
    divisible rows, VMEM-fitting block) with silent jnp fallback.  The
    per-stage selector passes False where Pallas measured slower (stage
    1's C=64 shapes starve the MXU).
    """
    jnp = _jnp()
    affine = scale is not None
    if not affine:
        scale = jnp.ones((x.shape[1],), jnp.float32)
        shift = jnp.zeros((x.shape[1],), jnp.float32)
    use_p = _use_pallas(x.shape[0]) if pallas is None \
        else (pallas and _use_pallas(x.shape[0]))
    op = _mm_op(affine, relu, use_p, use_p)
    return op(x, w, scale, shift)


# ---------------------------------------------------------------------------
# 3x3 stride-1 conv (shifted-row accumulation) + stats
# ---------------------------------------------------------------------------
def _c3_masks(R, H, W, dtype):
    """(R, 9) tap-validity masks as a static operand.

    In-kernel mask math (int div/mod on row indices + 9 broadcast selects)
    measured ~1.9 ms per layer-1 kernel call — nearly half the kernel. The
    masks are a pure function of the row index, so they are built once as
    jnp (XLA CSEs the 6 per-stage uses) and applied as one broadcast
    multiply per tap.  Column order matches the (dh, dw) tap loop; the
    backward reuses column 8-t (mask_bwd(dh,dw) == mask_fwd(-dh,-dw))."""
    jnp = _jnp()
    r = jnp.arange(R, dtype=jnp.int32)
    w = r % W
    h = (r // W) % H
    cols = []
    for dh in (-1, 0, 1):
        for dw in (-1, 0, 1):
            m = jnp.ones((R,), jnp.bool_)
            if dh == -1:
                m &= h > 0
            elif dh == 1:
                m &= h < H - 1
            if dw == -1:
                m &= w > 0
            elif dw == 1:
                m &= w < W - 1
            cols.append(m)
    return jnp.stack(cols, axis=1).astype(dtype)



def _c3_fwd_pallas(x, w, scale, shift, H, W, affine, relu, br):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Cin = x.shape
    Cout = w.shape[-1]
    grid = R // br
    nb = grid
    masks = _c3_masks(R, H, W, x.dtype)

    def kernel(xp_ref, xc_ref, xn_ref, m_ref, sc_ref, sh_ref, w_ref, z_ref,
               st_ref, acc, pk):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        def act(ref):
            v = ref[...]
            if affine:
                a32 = v.astype(jnp.float32) * sc_ref[...] + sh_ref[...]
            else:
                a32 = v.astype(jnp.float32)
            if relu:
                a32 = jnp.maximum(a32, 0.0)
            return a32.astype(v.dtype)

        # per-block activation, bf16 concat: one (3BR, C) fp32 intermediate
        # would blow the scoped-vmem budget
        a = jnp.concatenate([act(xp_ref), act(xc_ref), act(xn_ref)], axis=0)

        # lane-pack the 9 masked shifted slices -> ONE (br, 9*Cin) x
        # (9*Cin, Cout) MXU dot (9 separate Cin-wide dots leave the MXU
        # mostly idle at Cin=64), staged through VMEM scratch (a direct
        # lane-concat of row-shifted slices trips Mosaic: "offset mismatch
        # on non-concat dimension").  Boundary masks ride in as a static
        # (R, 9) operand — one broadcast multiply per tap.
        for t, (dh, dw) in enumerate((dh, dw) for dh in (-1, 0, 1)
                                     for dw in (-1, 0, 1)):
            off = dh * W + dw
            sl = lax.slice_in_dim(a, br + off, 2 * br + off, axis=0)
            if t != 4:  # centre tap is always valid
                sl = sl * m_ref[:, t:t + 1]
            pk[:, t * Cin:(t + 1) * Cin] = sl
        ap = pk[...]                               # (br, 9*Cin)
        wp = w_ref[...].reshape(-1, Cout)          # (9*Cin, Cout)
        zacc = lax.dot_general(ap, wp, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        z_ref[...] = zacc.astype(z_ref.dtype)
        acc[0, :] += jnp.sum(zacc, axis=0)
        acc[1, :] += jnp.sum(zacc * zacc, axis=0)

        @pl.when(i == grid - 1)
        def _fin():
            st_ref[...] = acc[...]

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, Cin), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((br, Cin), lambda i: (i, 0)),
            pl.BlockSpec((br, Cin),
                         lambda i: (jnp.minimum(i + 1, nb - 1), 0)),
            pl.BlockSpec((br, 9), lambda i: (i, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, Cin, Cout), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, Cout), lambda i: (i, 0)),
            pl.BlockSpec((2, Cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Cout), x.dtype),
            jax.ShapeDtypeStruct((2, Cout), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((2, Cout), jnp.float32),
                        pltpu.VMEM((br, 9 * Cin), x.dtype)],
        compiler_params=_cp(),
        interpret=_INTERPRET_TEST,
    )(x, x, x, masks, scale.reshape(1, -1), shift.reshape(1, -1), w)


def _c3_bwd_pallas(gze, x, wt, scale, shift, H, W, affine, relu, br):
    """3x3 backward: dgrad + wgrad in one pass, lane-packed.

    ``gze`` is the effective output cotangent (stats term folded in by the
    caller, bf16); ``wt`` is the host-pre-transposed (3, 3, Cout, Cin)
    kernel.  The 9 masked shifted gze slices are packed on the lane axis:
    da = GE_packed (br, 9*Cout) @ wt (9*Cout, Cin) is one full-K MXU dot,
    and the whole wgrad is ONE dot dW = act(x)^T @ GE_packed (the shift
    identity dW_t = sum_s a[s] x gze[s - o_t] means only gze needs a halo).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Cin = x.shape
    Cout = wt.shape[-2]
    grid = R // br
    nb = grid
    masks = _c3_masks(R, H, W, gze.dtype)

    def kernel(gp_ref, gc_ref, gn_ref, x_ref, m_ref, sc_ref, sh_ref, wt_ref,
               dx_ref, dw_ref, ds_ref, accw, accs, pk):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            accw[...] = jnp.zeros_like(accw)
            accs[...] = jnp.zeros_like(accs)

        ge = jnp.concatenate([gp_ref[...], gc_ref[...], gn_ref[...]], axis=0)

        xv = x_ref[...]
        x32 = xv.astype(jnp.float32)
        if affine:
            y = x32 * sc_ref[...] + sh_ref[...]
        else:
            y = x32
        a = (jnp.maximum(y, 0.0) if relu else y).astype(xv.dtype)

        # row s pulls gze[s - o]; valid iff (s - o) lies in the same image:
        # 0 <= h-dh < H and 0 <= w-dw < W == the FORWARD mask of the
        # mirrored tap, so column (8 - t) of the shared mask operand.
        for t, (dh, dw) in enumerate((dh, dw) for dh in (-1, 0, 1)
                                     for dw in (-1, 0, 1)):
            off = dh * W + dw
            sl = lax.slice_in_dim(ge, br - off, 2 * br - off, axis=0)
            if t != 4:
                sl = sl * m_ref[:, 8 - t:9 - t]
            pk[:, t * Cout:(t + 1) * Cout] = sl      # VMEM-staged pack (see
        gep = pk[...]                                # fwd kernel note)
        wtp = wt_ref[...].reshape(-1, Cin)           # (9*Cout, Cin)
        da = lax.dot_general(gep, wtp, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        accw[...] += lax.dot_general(
            a, gep, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Cin, 9*Cout)
        if relu:
            dy = jnp.where(y > 0.0, da, 0.0)
        else:
            dy = da
        if affine:
            dx_ref[...] = (dy * sc_ref[...]).astype(dx_ref.dtype)
        else:
            dx_ref[...] = dy.astype(dx_ref.dtype)
        accs[0, :] += jnp.sum(dy, axis=0)
        accs[1, :] += jnp.sum(dy * x32, axis=0)

        @pl.when(i == grid - 1)
        def _fin():
            dw_ref[...] = accw[...]
            ds_ref[...] = accs[...]

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, Cout), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((br, Cout), lambda i: (i, 0)),
            pl.BlockSpec((br, Cout),
                         lambda i: (jnp.minimum(i + 1, nb - 1), 0)),
            pl.BlockSpec((br, Cin), lambda i: (i, 0)),
            pl.BlockSpec((br, 9), lambda i: (i, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((1, Cin), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, Cout, Cin), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, Cin), lambda i: (i, 0)),
            pl.BlockSpec((Cin, 9 * Cout), lambda i: (0, 0)),
            pl.BlockSpec((2, Cin), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Cin), x.dtype),
            jax.ShapeDtypeStruct((Cin, 9 * Cout), jnp.float32),
            jax.ShapeDtypeStruct((2, Cin), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Cin, 9 * Cout), jnp.float32),
                        pltpu.VMEM((2, Cin), jnp.float32),
                        pltpu.VMEM((br, 9 * Cout), x.dtype)],
        compiler_params=_cp(),
        interpret=_INTERPRET_TEST,
    )(gze, gze, gze, x, masks, scale.reshape(1, -1), shift.reshape(1, -1),
      wt)


def _c3_ref(x, w, scale, shift, H, W, affine, relu):
    import jax
    from jax import lax
    jnp = _jnp()
    R, Cin = x.shape
    Cout = w.shape[-1]
    N = R // (H * W)
    x32 = x.astype(jnp.float32)
    y = x32 * scale[None, :] + shift[None, :] if affine else x32
    a32 = jnp.maximum(y, 0.0) if relu else y
    a = a32.astype(x.dtype).reshape(N, H, W, Cin)
    z = lax.conv_general_dilated(
        a, w.astype(x.dtype), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    z = z.reshape(R, Cout).astype(jnp.float32)
    st = jnp.stack([jnp.sum(z, axis=0), jnp.sum(z * z, axis=0)])
    return z.astype(x.dtype), st


@functools.lru_cache(maxsize=None)
def _c3_op(H, W, affine, relu, pallas_fwd, pallas_bwd):
    import jax

    def value(x, w, scale, shift):
        if pallas_fwd:
            R, Cin = x.shape
            Cout = w.shape[-1]
            rb = 2 * (4 * Cin * 2 + 2 * Cout * 2) + 6 * Cin
            fixed = 9 * Cin * Cout * 2
            br = _pick_br(R, rb + 1, mult=W,
                          cap=max(W, _VMEM_BUDGET // max(rb, 1) // W * W))
            # the static halo slices need br > W+1 on both sides
            if br is not None and br >= 2 * W and fixed < _VMEM_FIXED:
                return _c3_fwd_pallas(x, w, scale, shift, H, W, affine,
                                      relu, br)
        return _c3_ref(x, w, scale, shift, H, W, affine, relu)

    def fwd(x, w, scale, shift):
        z, st = value(x, w, scale, shift)
        return (z, st), (x, w, scale, shift, z)

    def bwd(res, g):
        import jax.numpy as jnp
        from jax import lax
        x, w, scale, shift, z = res
        gz, gst = g
        R, Cin = x.shape
        Cout = w.shape[-1]
        gze32 = (gz.astype(jnp.float32) + gst[0][None, :]
                 + 2.0 * z.astype(jnp.float32) * gst[1][None, :])
        gze = gze32.astype(gz.dtype)
        if pallas_bwd:
            rb = 2 * (2 * Cin * 2 + 6 * Cout * 2 + 2 * Cin * 2) + 8 * Cin
            fixed = 9 * Cin * Cout * (2 + 8)
            if fixed < _VMEM_FIXED:
                br = _pick_br(R, rb + 1, mult=W,
                              cap=max(W, _VMEM_BUDGET // max(rb, 1)
                                      // W * W))
                if br is not None and br >= 2 * W:
                    wt = jnp.transpose(w, (0, 1, 3, 2))
                    dx, dwp, ds = _c3_bwd_pallas(
                        gze, x, wt, scale, shift, H, W, affine, relu, br)
                    dw = dwp.reshape(Cin, 3, 3, Cout).transpose(1, 2, 0, 3) \
                        .astype(w.dtype)
                    dscale = ds[1] if affine else jnp.zeros_like(scale)
                    dshift = ds[0] if affine else jnp.zeros_like(shift)
                    return dx, dw, dscale, dshift
        # XLA fallback: express dgrad/wgrad as convs over the NHWC views
        N = R // (H * W)
        x32 = x.astype(jnp.float32)
        y = x32 * scale[None, :] + shift[None, :] if affine else x32
        a = (jnp.maximum(y, 0.0) if relu else y).astype(x.dtype)
        a4 = a.reshape(N, H, W, Cin)
        ge4 = gze.reshape(N, H, W, Cout)
        # dgrad: conv with spatially flipped, IO-swapped kernel
        wflip = w[::-1, ::-1].swapaxes(2, 3)  # (3,3,Cout,Cin)
        da = lax.conv_general_dilated(
            ge4, wflip.astype(gze.dtype), (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        da = da.reshape(R, Cin)
        # wgrad: correlate activations with the cotangent
        dw = lax.conv_general_dilated(
            a4.transpose(3, 1, 2, 0), ge4.transpose(1, 2, 0, 3),
            (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)  # (Cin, 3, 3, Cout)
        dw = dw.transpose(1, 2, 0, 3)
        dy = jnp.where(y > 0.0, da, 0.0) if relu else da
        if affine:
            dx = (dy * scale[None, :]).astype(x.dtype)
            dscale = jnp.sum(dy * x32, axis=0)
            dshift = jnp.sum(dy, axis=0)
        else:
            dx = dy.astype(x.dtype)
            dscale = jnp.zeros_like(scale)
            dshift = jnp.zeros_like(shift)
        return dx, dw.astype(w.dtype), dscale, dshift

    f = jax.custom_vjp(value)
    f.defvjp(fwd, bwd)
    return f


def conv3x3_stats(x, w, H, W, scale=None, shift=None, relu=False,
                  pallas=None):
    """3x3 stride-1 pad-1 conv over flattened NHWC rows, with inline
    affine+ReLU on the operand and per-channel (sum, sum_sq) of the output.

    x: (N*H*W, Cin); w: (3, 3, Cin, Cout) HWIO.  ``pallas`` as in
    :func:`matmul_stats`.
    """
    jnp = _jnp()
    affine = scale is not None
    if not affine:
        scale = jnp.ones((x.shape[1],), jnp.float32)
        shift = jnp.zeros((x.shape[1],), jnp.float32)
    use_p = _use_pallas(x.shape[0], W) if pallas is None \
        else (pallas and _use_pallas(x.shape[0], W))
    op = _c3_op(H, W, affine, relu, use_p, use_p)
    return op(x, w, scale, shift)


# ---------------------------------------------------------------------------
# BN scalar glue + helpers
# ---------------------------------------------------------------------------
def bn_affine(stats, count, gamma, beta, eps):
    """(sum, sum_sq) -> (scale, shift, mean, var): one-pass moments with the
    fp32 cancellation floor (matches ndarray.ops._one_pass_moments), then
    scale = gamma/sqrt(var+eps), shift = beta - mean*scale."""
    jnp = _jnp()
    mean = stats[0] / count
    mean2 = stats[1] / count
    var = jnp.maximum(mean2 - jnp.square(mean),
                      32 * 1.2e-7 * jnp.square(mean))
    inv = gamma.astype(jnp.float32) / jnp.sqrt(var + eps)
    return inv, beta.astype(jnp.float32) - mean * inv, mean, var


def _global_affine(rm, rv, gamma, beta, eps):
    jnp = _jnp()
    inv = gamma.astype(jnp.float32) / jnp.sqrt(rv.astype(jnp.float32) + eps)
    return inv, beta.astype(jnp.float32) - rm.astype(jnp.float32) * inv


def _epi_bwd_pallas(g, a, z3, rz, sc3, scd, has_down, br):
    """One-pass epilogue backward: gm = relu'(a)*g; gz3 = gm*sc3;
    grz = gm*scd (or gm); sums = (sum gm, sum gm*z3, sum gm*rz).
    XLA splits this into several fusions with a materialized pred mask;
    one Pallas pass keeps everything in registers."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, C = g.shape
    grid = R // br

    def kernel(g_ref, a_ref, z_ref, r_ref, sc_ref, sd_ref,
               gz_ref, gr_ref, s_ref, acc):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        # compare in fp32: Mosaic lacks a bf16 vector compare on v5e
        gm = jnp.where(a_ref[...].astype(jnp.float32) > 0.0,
                       g_ref[...].astype(jnp.float32), 0.0)
        gz_ref[...] = (gm * sc_ref[...]).astype(gz_ref.dtype)
        if has_down:
            gr_ref[...] = (gm * sd_ref[...]).astype(gr_ref.dtype)
        else:
            gr_ref[...] = gm.astype(gr_ref.dtype)
        acc[0, :] += jnp.sum(gm, axis=0)
        acc[1, :] += jnp.sum(gm * z_ref[...].astype(jnp.float32), axis=0)
        acc[2, :] += jnp.sum(gm * r_ref[...].astype(jnp.float32), axis=0)

        @pl.when(i == grid - 1)
        def _fin():
            s_ref[...] = acc[...]

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))] * 4 + [
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((3, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), z3.dtype),
            jax.ShapeDtypeStruct((R, C), rz.dtype),
            jax.ShapeDtypeStruct((3, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((3, C), jnp.float32)],
        compiler_params=_cp(),
        interpret=_INTERPRET_TEST,
    )(g, a, z3, rz, sc3.reshape(1, -1), scd.reshape(1, -1))


@functools.lru_cache(maxsize=None)
def _epi_op(has_down, use_pallas=True):
    """Residual epilogue a = relu(z3*sc3+sh3 + res) as a custom_vjp.

    Without this, XLA materializes the fp32 pre-activation (822 MB at
    layer-1 shapes) as the relu-backward residual; here the backward mask is
    recomputed from the bf16 OUTPUT (a > 0 == pre-activation > 0), so only
    bf16 tensors ever hit HBM.  ``res`` is the raw downsample conv output
    (affine applied inline) or the identity activation."""
    import jax

    def value(z3, sc3, sh3, rz, scd, shd):
        import jax.numpy as jnp
        r32 = rz.astype(jnp.float32)
        res = r32 * scd[None, :] + shd[None, :] if has_down else r32
        out = z3.astype(jnp.float32) * sc3[None, :] + sh3[None, :] + res
        return jnp.maximum(out, 0.0).astype(z3.dtype)

    def fwd(z3, sc3, sh3, rz, scd, shd):
        a = value(z3, sc3, sh3, rz, scd, shd)
        return a, (z3, rz, a, sc3, scd)

    def bwd(resid, g):
        import jax.numpy as jnp
        z3, rz, a, sc3, scd = resid
        R, C = g.shape
        if use_pallas and _use_pallas(R) \
                and (not has_down or scd.shape[0] == C):
            scd_full = scd if has_down else jnp.ones((C,), jnp.float32)
            br = _pick_br(R, 16 * C, mult=8 if R % 8 == 0 else 1)
            if br is not None:
                gz3, grz, s = _epi_bwd_pallas(g, a, z3, rz, sc3, scd_full,
                                              has_down, br)
                dsh3 = s[0]
                dsc3 = s[1]
                if has_down:
                    return gz3, dsc3, dsh3, grz, s[2], s[0]
                return gz3, dsc3, dsh3, grz, jnp.zeros_like(scd), \
                    jnp.zeros_like(scd)
        gm = jnp.where(a > 0, g.astype(jnp.float32), 0.0)
        gz3 = (gm * sc3[None, :]).astype(z3.dtype)
        dsc3 = jnp.sum(gm * z3.astype(jnp.float32), axis=0)
        dsh3 = jnp.sum(gm, axis=0)
        if has_down:
            grz = (gm * scd[None, :]).astype(rz.dtype)
            dscd = jnp.sum(gm * rz.astype(jnp.float32), axis=0)
            dshd = jnp.sum(gm, axis=0)
        else:
            grz = gm.astype(rz.dtype)
            dscd = jnp.zeros_like(scd)
            dshd = jnp.zeros_like(scd)
        return gz3, dsc3, dsh3, grz, dscd, dshd

    f = jax.custom_vjp(value)
    f.defvjp(fwd, bwd)
    return f


def block_epilogue(z3, sc3, sh3, rz, scd=None, shd=None, pallas=True):
    """relu(affine3(z3) + residual); residual = affine_d(rz) or rz."""
    jnp = _jnp()
    has_down = scd is not None
    if not has_down:
        scd = jnp.ones((1,), jnp.float32)
        shd = jnp.zeros((1,), jnp.float32)
    return _epi_op(has_down, pallas)(z3, sc3, sh3, rz, scd, shd)


def subsample2d(x, H, W, stride):
    """(N*H*W, C) -> (N*(H/s)*(W/s), C) taking every s-th row/col."""
    C = x.shape[1]
    x4 = x.reshape(-1, H, W, C)
    return x4[:, ::stride, ::stride, :].reshape(-1, C)


# ---------------------------------------------------------------------------
# whole-model fused forward (ResNetV1 + BottleneckV1)
# ---------------------------------------------------------------------------
def fused_supported(net):
    """True if ``net`` is a ResNetV1 whose stages are all BottleneckV1 and
    the device setup can take the Pallas path (single TPU chip, or any
    non-TPU backend where the jnp reference impls — which XLA can shard —
    are used)."""
    import jax
    from ..gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1
    from ..gluon.nn import HybridSequential
    from .flash_attention import _FORCE_DENSE
    from ..parallel import active_mesh_size
    # NOT the shared kernel_dispatch_allowed(): the conv fallback here is
    # the jnp reference impls, which run (and shard) on CPU too
    if _FORCE_DENSE or active_mesh_size() > 1:
        return False
    if not isinstance(net, ResNetV1):
        return False
    try:
        if jax.devices()[0].platform == "tpu" and len(jax.devices()) > 1:
            # pallas_call custom calls cannot be auto-partitioned by pjit;
            # multi-chip SPMD keeps the unfused op path
            return False
    except Exception:  # pragma: no cover - no backend
        return False
    for child in net.features._children.values():
        if isinstance(child, HybridSequential):
            for blk in child._children.values():
                if not isinstance(blk, BottleneckV1):
                    return False
    return True


def _block_spec(blk):
    """Extract (params, static config) from one BottleneckV1."""
    body = list(blk.body._children.values())
    conv1, bn1, _, conv2, bn2, _, conv3, bn3 = body
    spec = {
        "stride": int(conv1._kwargs["stride"][0]),
        "convs": [conv1, conv2, conv3],
        "bns": [bn1, bn2, bn3],
        "down": None,
    }
    if blk.downsample is not None:
        dconv, dbn = list(blk.downsample._children.values())
        spec["down"] = (dconv, dbn)
    return spec


def _bias_stats(st, b, count):
    """Per-channel stats of z+b from the kernel's stats of z ((C,)-sized
    post-hoc math keeps bias-carrying convs — the gluon model-zoo's
    BottleneckV1 conv1/conv3 default use_bias=True — out of the kernels)."""
    jnp = _jnp()
    b32 = b.astype(jnp.float32)
    s0, s1 = st[0], st[1]
    return jnp.stack([s0 + count * b32,
                      s1 + 2.0 * b32 * s0 + count * jnp.square(b32)])


def _bn_params(bn):
    return [bn.gamma, bn.beta, bn.running_mean, bn.running_var]


def _build_spec(net, fuse_from=1):
    """Walk the model once: a MODULE PREFIX (stem + stages before
    ``fuse_from``, executed through the normal layer path so XLA's conv
    pipeline handles the narrow-channel shapes) plus the flat parameter
    list and static structure for the fused trailing stages."""
    from ..gluon.nn import GlobalAvgPool2D, HybridSequential
    params = []
    prefix = []     # modules called as-is, in order
    stages = []     # fused stage specs with param indices
    bns = []        # fused-part BatchNorm quadruples, in aux-update order

    def add(p):
        params.append(p)
        return len(params) - 1

    stage_i = 0
    for child in net.features._children.values():
        if isinstance(child, GlobalAvgPool2D):
            if not stages:
                prefix.append(child)   # nothing fused: pool via the module
            continue
        if not isinstance(child, HybridSequential):
            prefix.append(child)       # stem layer (conv/bn/relu/maxpool)
            continue
        stage_i += 1
        if stage_i < fuse_from:
            prefix.append(child)
            continue
        blocks = []
        for blk in child._children.values():
            bs = _block_spec(blk)
            entry = {
                "stride": bs["stride"],
                "w": [add(c.weight) for c in bs["convs"]],
                "b": [None if c.bias is None else add(c.bias)
                      for c in bs["convs"]],
                "bn": [], "down": None,
            }
            for bn in bs["bns"]:
                gi = [add(p) for p in _bn_params(bn)]
                bns.append((bn, gi))
                entry["bn"].append((gi, bn._momentum, bn._eps,
                                    bn._use_global_stats))
            if bs["down"] is not None:
                dconv, dbn = bs["down"]
                wd = add(dconv.weight)
                bd = None if dconv.bias is None else add(dconv.bias)
                gi = [add(p) for p in _bn_params(dbn)]
                bns.append((dbn, gi))
                entry["down"] = (wd, bd, (gi, dbn._momentum, dbn._eps,
                                          dbn._use_global_stats))
            blocks.append(entry)
        stages.append(blocks)
    if stages:
        head_w = add(net.output.weight)
        head_b = add(net.output.bias) if net.output.bias is not None \
            else None
    else:
        head_w = head_b = None
    return {"params": params, "prefix": prefix, "stages": stages,
            "head": (head_w, head_b), "bns": bns}


def _apply_bn(raws, gi, mom, eps, use_global, stats, count, training, auxes):
    """scale/shift for one BN + (training) collect running-stat updates."""
    jnp = _jnp()
    gamma, beta, rmean, rvar = (raws[i] for i in gi)
    if training and not use_global:
        scale, shift, mean, var = bn_affine(stats, count, gamma, beta, eps)
        auxes.append(mean)
        auxes.append(var)
        return scale, shift
    return _global_affine(rmean, rvar, gamma, beta, eps)


def _fuse_from():
    """First ResNet stage taken by the fused Pallas trunk; the stem and
    stages before it run the normal layer path (XLA's own conv pipeline,
    which wins at the narrow-channel early shapes — stage 1's C=64 leaves
    the MXU mostly idle, measured in benchmark/r50_stage_sweep.py).
    Tunable via MXNET_R50_FUSE_STAGES: "all" (=1), "none", or a contiguous
    trailing set like "2,3,4" / "4"; default = fastest measured on v5e
    (benchmark/r50_roofline.md).  Returns 5 for "none" (no fused stages)."""
    import os
    env = os.environ.get("MXNET_R50_FUSE_STAGES", "").strip().lower()
    if env in ("", "auto"):
        return 4
    if env == "all":
        return 1
    if env == "none":
        return 5
    try:
        stages = sorted({int(t) for t in env.split(",") if t.strip()})
    except ValueError:
        raise ValueError(
            f"MXNET_R50_FUSE_STAGES={env!r}: expected 'all', 'none', "
            f"'auto', or a comma-separated trailing stage set like '2,3,4'")
    if not stages:
        return 5
    if stages[0] < 1 or stages != list(range(stages[0], 5)):
        raise ValueError(
            f"MXNET_R50_FUSE_STAGES={env!r}: the fused trunk takes over "
            f"from one stage onward, so the set must be a contiguous "
            f"trailing run ending at stage 4 (e.g. '2,3,4' or '4')")
    return stages[0]


def _fused_fn(spec, training, x, *raws):
    """The fused trunk (stages >= fuse_from, pooling, classifier head) as
    one pure function of (stage input, params).  ``x`` is the NCHW
    activation produced by the module prefix (stem + earlier stages)."""
    import jax
    from jax import lax
    jnp = _jnp()
    auxes = []

    x = jnp.transpose(x, (0, 2, 3, 1))
    N, H, W, C = x.shape
    a = x.reshape(-1, C)

    # ---- bottleneck stages ----
    for blocks in spec["stages"]:
        for blk in blocks:
            s = blk["stride"]
            if s > 1:
                a_in = subsample2d(a, H, W, s)
                H, W = -(-H // s), -(-W // s)  # ceil: x[::s] keeps ceil(n/s)
            else:
                a_in = a
            R = a_in.shape[0]
            w1 = raws[blk["w"][0]][:, :, 0, 0].T        # (Cin, Cq)
            w2 = jnp.transpose(raws[blk["w"][1]], (2, 3, 1, 0))  # HWIO
            w3 = raws[blk["w"][2]][:, :, 0, 0].T        # (Cq, C)

            b1, b2, b3 = (None if i is None else raws[i] for i in blk["b"])

            z1, st1 = matmul_stats(a_in, w1)
            if b1 is not None:
                st1 = _bias_stats(st1, b1, R)
            sc1, sh1 = _apply_bn(raws, *blk["bn"][0], stats=st1, count=R,
                                 training=training, auxes=auxes)
            if b1 is not None:
                sh1 = sh1 + b1.astype(jnp.float32) * sc1
            z2, st2 = conv3x3_stats(z1, w2, H, W, scale=sc1, shift=sh1,
                                    relu=True)
            if b2 is not None:
                st2 = _bias_stats(st2, b2, R)
            sc2, sh2 = _apply_bn(raws, *blk["bn"][1], stats=st2, count=R,
                                 training=training, auxes=auxes)
            if b2 is not None:
                sh2 = sh2 + b2.astype(jnp.float32) * sc2
            z3, st3 = matmul_stats(z2, w3, scale=sc2, shift=sh2, relu=True)
            if b3 is not None:
                st3 = _bias_stats(st3, b3, R)
            sc3, sh3 = _apply_bn(raws, *blk["bn"][2], stats=st3, count=R,
                                 training=training, auxes=auxes)
            if b3 is not None:
                sh3 = sh3 + b3.astype(jnp.float32) * sc3

            if blk["down"] is not None:
                wd = raws[blk["down"][0]][:, :, 0, 0].T
                bd = None if blk["down"][1] is None else raws[blk["down"][1]]
                zd, std = matmul_stats(a_in, wd)
                if bd is not None:
                    std = _bias_stats(std, bd, R)
                scd, shd = _apply_bn(raws, *blk["down"][2], stats=std,
                                     count=R, training=training, auxes=auxes)
                if bd is not None:
                    shd = shd + bd.astype(jnp.float32) * scd
                a = block_epilogue(z3, sc3, sh3, zd, scd, shd)
            else:
                a = block_epilogue(z3, sc3, sh3, a)

    # ---- head ----
    C = a.shape[1]
    feat = a.reshape(N, H * W, C).astype(jnp.float32).mean(axis=1)
    hw, hb = spec["head"]
    logits = feat.astype(a.dtype) @ raws[hw].T
    if hb is not None:
        logits = logits + raws[hb]
    return logits, auxes


def fused_resnet_forward(net, x):
    """NDArray-facing fused forward: the module prefix (stem + pre-fuse
    stages) runs the normal layer path, then the fused trunk registers one
    tape node and routes BatchNorm moving-stat updates through
    mark_aux_update."""
    from .. import autograd
    from ..gluon.block import mark_aux_update
    from ..ndarray.ndarray import NDArray, apply_op

    fuse_from = _fuse_from()
    cached = getattr(net, "_fused_spec", None)
    if cached is None or cached[0] != fuse_from:
        cached = (fuse_from, _build_spec(net, fuse_from))
        net._fused_spec = cached
    spec = cached[1]
    training = autograd.is_training()

    # resolve fused-trunk params FIRST: on deferred init this raises before
    # the prefix modules run (so the caller's layer-path fallback does not
    # double-apply prefix BN running-stat updates)
    param_nds = [p.data() for p in spec["params"]]
    h = x
    for mod in spec["prefix"]:
        h = mod(h)
    if not spec["stages"]:
        return net.output(h)

    fn = functools.partial(_fused_fn, spec, training)
    out, auxes = apply_op(fn, h, *param_nds, op_name="fused_resnet",
                          has_aux=True)
    if training:
        i = 0
        for bn, gi in spec["bns"]:
            if bn._use_global_stats:
                continue
            mean, var = NDArray(auxes[i]), NDArray(auxes[i + 1])
            i += 2
            m = bn._momentum
            mark_aux_update(bn.running_mean,
                            bn.running_mean.data() * m + mean * (1 - m))
            mark_aux_update(bn.running_var,
                            bn.running_var.data() * m + var * (1 - m))
    return out
