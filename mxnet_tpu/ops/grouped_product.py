"""Grouped product over the held experts, a row tile that follows the load.

``jax.lax.ragged_dot`` on a TPU (jax 0.9.0) is XLA's own Mosaic grouped
matmul, and it picks its tiles from the shapes alone: the row tile is the
largest power of two up to 512 that divides the row count, the weights
come in blocks of 512 x 512.  A decode step's 512 sorted pairs are then
one row tile, which every one of 64 experts visits and multiplies whole
for its 8 rows: 64 times the rows the pairs need, and the call is bound by
the matrix unit while the weights' bytes ride along (1.36 ms a call at
LFM2's widths on a v5e, 0.73 when the same call is given 576 rows and so
a tile of 64; my chip runs, PR 36).

Here the product is ``jax.experimental.pallas.ops.tpu.megablox.gmm``, the
same algorithm (a row tile is visited once by each expert that has a row
in it, and what is not the expert's is masked at the store) with the
tiles this module chooses from the static shapes:

- the row tile follows the load (:func:`row_tile`): 32 rows up to a mean
  of 32 pairs an expert, a decode step's load; above it, a prefill's,
  XLA's own tiling stays;
- the weights come in whole rows of an expert's matrix, contiguous in
  HBM, and the whole contraction where VMEM holds it
  (:func:`weight_block`), so an expert's matrix is one or a few reads of
  megabytes: 0.59 ms a call at LFM2's widths, 0.50 at DeepSeek's, 0.54 at
  Keye's, where ``ragged_dot`` took 1.36, 1.22 and 0.85.

Of three forms timed on a v5e (``chiprun_out/pr36/grouped_bench*.json``)
this one stayed: ``ragged_dot`` over pairs padded to a row count that
steers XLA's tile was 10-40 % behind, and a kernel of the repo's own over
pairs padded to whole row tiles an expert within 5 % either way, with
more code.
"""
from __future__ import annotations

# VMEM a core gives a kernel by default is 16 MiB; what is left of it for
# Mosaic's own scratch when the blocks below are sized
_VMEM_BUDGET = int(14.5 * 1024 * 1024)


# pairs an expert, on average, up to which the kernel runs: a decode
# step's load (8, 32 and 2.5 in the three cells)
_MEAN_LOAD_MOST = 32


def row_tile(pairs, count):
    """Rows a tile for ``pairs`` sorted pairs over ``count`` held experts,
    or None where XLA's own tiling stays (``ragged_dot``).

    Up to a mean load of 32 pairs an expert the tile is 32, halved until
    it divides ``pairs`` (None under 8): a tile is one visit of an
    expert's weights, and from 8 to 64 rows a visit costs the same.  Above
    that, a prefill's load, None: at a tile of 64-256 its products gain
    too (1.6 -> 0.85 ms a call at LFM2's 4,096 pairs, 4.1 -> 2.4 at Keye's
    65,536; my chip runs, PR 36), but every further program that holds the
    kernel costs an engine's start 0.7 s of tracing and lowering, and a
    prefill is a few per cent of a serving window."""
    if -(-pairs // count) > _MEAN_LOAD_MOST:
        return None
    tm = 32
    while tm >= 8 and pairs % tm:
        tm //= 2
    return tm if tm >= 8 else None


def weight_block(tm, k, n, itemsize):
    """``(tk, tn)`` of the weights' block: whole rows of an expert's
    matrix (``tn = n``), contiguous in HBM, and of the contraction the
    largest ``k / 2**i`` in whole lanes whose two buffers, beside the
    rows' and the result's, stay inside VMEM."""
    tk = k
    while (2 * tk * n * itemsize + 2 * tm * tk * itemsize + 3 * tm * n * 4
           > _VMEM_BUDGET) and tk % 256 == 0:
        tk //= 2
    return tk, n


def xla_row_tile(pairs):
    """The row tile XLA's ``ragged_dot`` takes on a TPU for ``pairs``
    rows: the largest power of two up to 512 that divides them."""
    tm = 1
    while tm < 512 and pairs % (2 * tm) == 0:
        tm *= 2
    return tm


def rows_visited(sizes, tm):
    """Rows a grouped product multiplies at a row tile of ``tm`` over
    sorted groups of ``sizes`` [count] rows: an expert visits, whole,
    every tile that holds a row of its own."""
    import jax.numpy as jnp
    end = jnp.cumsum(sizes)
    visits = jnp.where(sizes > 0, (end - 1) // tm - (end - sizes) // tm + 1, 0)
    return visits.sum() * tm


def grouped_product(xs, w, sizes, tm, *, interpret=False):
    """``xs[rows of expert e] @ w[e]`` for sorted ``xs`` [pairs, k] and
    ``w`` [count, k, n], in float32: ``ragged_dot``'s mathematics
    (operands as they come, float32 accumulation).  Rows past
    ``sizes.sum()`` belong to no expert and are not results."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    tk, tn = weight_block(tm, xs.shape[1], w.shape[2],
                          jnp.dtype(w.dtype).itemsize)
    return gmm(xs, w, sizes, preferred_element_type=jnp.float32,
               tiling=(tm, tk, tn), interpret=interpret)


def kernel_tile(pairs, count, d, hidden, dtype):
    """The row tile the kernel runs at for ``pairs`` sorted pairs over
    stacks ``[count, d, hidden]`` and ``[count, hidden, d]`` of ``dtype``,
    or None where ``ragged_dot`` runs: on a CPU, under a mesh, in an ONNX
    export, for shapes no tile divides, or where the chip's compiler
    refuses the variant (kept in ``kernel_report()``)."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    tm = row_tile(pairs, count)
    if tm is None or d % 128 or hidden % 128 \
            or not kernel_dispatch_allowed():
        return None

    def compile_fn():
        # what the compiler may refuse is a grid step's blocks in VMEM,
        # which the pairs do not change: one tile of rows, and both
        # orientations in one program
        def both(xs, w_in, h, w_out, sizes):
            return (grouped_product(xs, w_in, sizes, tm),
                    grouped_product(h, w_out, sizes, tm))

        def shape(*dims, dt=dtype):
            return jax.ShapeDtypeStruct(dims, jnp.dtype(dt))
        jax.jit(both).lower(
            shape(tm, d), shape(count, d, hidden), shape(tm, hidden),
            shape(count, hidden, d), shape(count, dt=jnp.int32)).compile()

    signature = (count, d, hidden, str(jnp.dtype(dtype)), tm)
    return tm if probe_compile("grouped_product", signature,
                               compile_fn) else None
