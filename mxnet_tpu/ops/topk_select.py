"""The ``k`` largest scores of each row as a mask, found without a sort.

The indexer of :mod:`mxnet_tpu.models.deepseek` and ``keye`` keeps, a
query, the ``k`` valid positions of largest score.  The program uses the
set and never its order, and the set is fixed by one number a row, the
k-th largest score.  ``lax.top_k`` sorts the whole row to find it and
returns indices that a mask is then built from; here the number is found
exactly by bisection and the mask is a comparison:

- a score becomes an order-preserving int32 key: its bits, the magnitude
  bits flipped where the sign is set; ``-0.0`` is made ``+0.0`` first, so
  that equal scores have equal keys.  An invalid position gets
  :data:`INVALID`, below every score's key, and never counts;
- 32 passes over the row's keys fix the k-th largest key ``t`` a bit at a
  time from the top, each pass counting the keys at or above a candidate;
- of the keys equal to ``t``, ``need = k - count(key > t)`` are kept.
  Where more tie (the row's *tie* flag), up to ``ceil(log2 N)`` passes
  over positions find the smallest cut that keeps ``need`` of them, the
  lowest positions first;
- ``mask = valid & (key > t | key == t & position <= cut)``: exactly
  ``min(k, n_valid)`` positions, and ``valid`` itself where a row has no
  more than ``k``.

Wherever the k-th score is not tied this is ``top_k``'s set; among exactly
equal scores at the k-th it keeps the lowest positions, where ``top_k``
on a TPU keeps an order of its own.

On a TPU the passes run in one Pallas call over a grid of row blocks
(:func:`topk_select`), each block's keys held in VMEM, a pass a loop over
column chunks, and the positional passes only in a block where some row
has a tie.  :func:`topk_select_xla` is the same algorithm in ``jnp``
(``lax.fori_loop`` passes): the form on a CPU or under a mesh, and the
kernel's reference, equal to it bit for bit.
"""
from __future__ import annotations

import functools

__all__ = ["topk_select", "topk_select_xla", "threshold_xla", "mask_at",
           "keys", "kernel_rows", "pick_rows", "pick_chunk", "INVALID",
           "ROW_BYTES", "CHUNKS"]

# the least int32: the key of an invalid position, which no score has
INVALID = -2 ** 31

# keys of a row block in VMEM, each of the pipeline's two buffers, beside
# the block's mask (a byte a position) in two more
ROW_BYTES = 1 << 21

# columns a step of a pass's loop, the first that divides the row: a
# [rows, chunk] float32 count of 8 or 16 vregs beside the chunk's keys
CHUNKS = (512, 256, 128)


def pick_rows(R, N):
    """Rows a block: the most that divides ``R``, is a multiple of 8 (or
    ``R`` itself) and keeps a block's int32 keys within
    :data:`ROW_BYTES`; None where no such block exists."""
    most = max(ROW_BYTES // (4 * N), 1)
    if R <= most:
        return R
    for rows in range(most - most % 8, 0, -8):
        if R % rows == 0:
            return rows
    return None


def pick_chunk(rows, N):
    """Columns a step of a pass: the first of :data:`CHUNKS` that divides
    ``N`` and keeps ``rows x chunk`` within 16 vregs (16 K numbers), else
    the whole row."""
    for c in CHUNKS:
        if N % c == 0 and rows * c <= 16 * 1024:
            return c
    return 128 if N % 128 == 0 else N


def keys(scores, valid):
    """int32 keys [..., N] that order as ``scores`` (float32) do, and
    :data:`INVALID` where ``valid`` is false."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    bits = jnp.where(bits == INVALID, 0, bits)          # -0.0 is +0.0
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    # only a NaN's bits map to INVALID: it joins the least key instead
    return jnp.where(valid, jnp.maximum(key, INVALID + 1), INVALID)


def _position_bits(N):
    return max((N - 1).bit_length(), 1)


def threshold_xla(key, k):
    """The selection of ``k`` of each row of int32 ``key`` [R, N] (see
    :func:`keys`) by the kernel's algorithm in ``jnp``: ``(t [R, 1], cut
    [R, 1], tie [R] bool)``, the k-th largest key (:data:`INVALID` where a
    row has fewer than ``k`` valid), the last position kept among the keys
    equal to it, and whether more than those tie; :func:`mask_at` makes
    the mask of them."""
    import jax
    import jax.numpy as jnp
    R, N = key.shape
    i32 = jnp.int32

    def count(hit):
        return hit.astype(i32).sum(-1, keepdims=True)

    def value_bit(i, carry):
        # candidates as unsigned bit patterns; the sign bit flipped to
        # compare them as the signed keys they stand for
        t, at_t = carry
        cand = t | jax.lax.shift_left(i32(1), 31 - i)
        c = count(key >= (cand ^ INVALID))
        ok = c >= k
        return jnp.where(ok, cand, t), jnp.where(ok, c, at_t)

    t, at_t = jax.lax.fori_loop(
        0, 32, value_bit, (jnp.zeros((R, 1), i32), jnp.full((R, 1), N, i32)))
    ts = t ^ INVALID
    eq = key == ts
    # the k-th key is a valid one (t != 0) and more than k keys reach it
    tie = (at_t > k) & (t != 0)
    need = k - at_t + count(eq)
    pos = jax.lax.broadcasted_iota(i32, (R, N), 1)
    nbits = _position_bits(N)

    def position_bit(i, p):
        cand = p + jax.lax.shift_left(i32(1), nbits - 1 - i)
        return jnp.where(count(eq & (pos < cand)) < need, cand, p)

    cut = jax.lax.cond(
        tie.any(),
        lambda: jnp.where(tie, jax.lax.fori_loop(
            0, nbits, position_bit, jnp.zeros((R, 1), i32)), N),
        lambda: jnp.full((R, 1), N, i32))
    return ts, cut, tie[:, 0]


def mask_at(key, t, cut):
    """``valid & (key > t | key == t & position <= cut)`` [R, N]."""
    import jax
    import jax.numpy as jnp
    pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    return (key != INVALID) & ((key > t) | ((key == t) & (pos <= cut)))


def topk_select_xla(key, k):
    """``(mask [R, N] bool, tie [R] bool)`` of :func:`threshold_xla`:
    :func:`topk_select`'s result on any backend."""
    t, cut, tie = threshold_xla(key, k)
    return mask_at(key, t, cut), tie


def _kernel(k, chunk, key_ref, mask_ref, tie_ref, t_ref, cut_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32, i32 = jnp.float32, jnp.int32
    rows, N = key_ref.shape
    shape = (rows, chunk)

    def cols(j):
        return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)

    def position(j):
        return j * chunk + jax.lax.broadcasted_iota(i32, shape, 1)

    def count(hit, steps):
        """Per row, float32, how many of the first ``steps`` chunks' keys
        ``hit(j, keys)`` [rows, chunk] holds for (exact: fewer than 2**24)."""
        def body(j, acc):
            return acc + hit(j, key_ref[:, cols(j)]).astype(i32)
        acc = jax.lax.fori_loop(0, steps, body, jnp.zeros(shape, i32))
        return acc.astype(f32).sum(-1, keepdims=True)

    def scalar_max(x):
        return jnp.max(x.astype(f32))

    # the valid keys a row holds, and the chunks up to the block's last
    # valid one: the passes read no further
    def valid_pass(j, carry):
        n, last = carry
        valid = key_ref[:, cols(j)] != INVALID
        return n + valid.astype(i32), jnp.maximum(
            last, jnp.where(valid, position(j), -1))
    n, last = jax.lax.fori_loop(
        0, N // chunk, valid_pass,
        (jnp.zeros(shape, i32), jnp.full(shape, -1, i32)))
    n_valid = n.astype(f32).sum(-1, keepdims=True)
    steps = (scalar_max(last) // chunk + 1).astype(i32)
    t_ref[...] = jnp.zeros(t_ref.shape, i32)
    cut_ref[...] = jnp.full(cut_ref.shape, N, i32)
    tie_ref[...] = jnp.zeros(tie_ref.shape, i32)

    @pl.when(scalar_max(n_valid) > k)
    def _():
        # a row is settled once the keys at or above its candidate are
        # exactly k (the set is then fixed) or it has no more than k
        def unsettled(carry):
            i, _t, at_t = carry
            return (i < 32) & (scalar_max((at_t != k) & (n_valid > k)) > 0)

        def value_bit(carry):
            i, t, at_t = carry
            cand = t | jax.lax.shift_left(i32(1), 31 - i)
            c = count(lambda j, kb, s=cand ^ INVALID: kb >= s, steps)
            ok = c >= k
            return i + 1, jnp.where(ok, cand, t), jnp.where(ok, c, at_t)

        _i, t, at_t = jax.lax.while_loop(
            unsettled, value_bit,
            (i32(0), jnp.zeros((rows, 1), i32),
             jnp.full((rows, 1), N, f32)))
        t_ref[...] = t
        tie = (at_t > k) & (t != 0)
        tie_ref[...] = tie.astype(i32)

        @pl.when(scalar_max(tie) > 0)
        def _():
            ts = t ^ INVALID
            need = k - at_t + count(lambda j, kb: kb == ts, steps)
            nbits = _position_bits(N)

            def position_bit(i, p):
                cand = p + jax.lax.shift_left(i32(1), nbits - 1 - i)
                c = count(lambda j, kb: (kb == ts) & (position(j) < cand),
                          steps)
                return jnp.where(c < need, cand, p)
            p = jax.lax.fori_loop(0, nbits, position_bit,
                                  jnp.zeros((rows, 1), i32))
            cut_ref[...] = jnp.where(tie, p, N)

    ts, cut = t_ref[...] ^ INVALID, cut_ref[...]

    @pl.loop(0, N // chunk)
    def _(j):
        kb = key_ref[:, cols(j)]
        mask_ref[:, cols(j)] = (kb != INVALID) & (
            (kb > ts) | ((kb == ts) & (position(j) <= cut)))


def topk_select(key, k, *, rows=None, chunk=None, interpret=False):
    """``(mask [R, N] bool, tie [R] bool)`` from int32 ``key`` [R, N] (see
    :func:`keys`) in one Pallas call: :func:`topk_select_xla`'s result,
    bit for bit."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, N = key.shape
    rows = pick_rows(R, N) if rows is None else rows
    if rows is None or R % rows:
        raise ValueError(f"no block of rows divides {R} at {N} columns")
    chunk = pick_chunk(rows, N) if chunk is None else chunk
    block = pl.BlockSpec((rows, N), lambda r: (r, 0))
    mask, tie = pl.pallas_call(
        functools.partial(_kernel, int(k), chunk),
        grid=(R // rows,),
        in_specs=[block],
        out_specs=[block, pl.BlockSpec((rows, 1), lambda r: (r, 0))],
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        out_shape=[jax.ShapeDtypeStruct((R, N), jnp.bool_),
                   jax.ShapeDtypeStruct((R, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=(32 + 2) * 3 * R * N, transcendentals=0,
            bytes_accessed=R * N * 5 + R * 4),
        name="topk_select",
        interpret=interpret,
    )(key)
    return mask, tie[:, 0] != 0


def kernel_rows(R, N, k):
    """The rows a block the kernel runs at for keys [R, N], or None where
    :func:`topk_select_xla` runs: on a CPU, under a mesh, in an ONNX
    export, where no block of rows fits, or where the chip's compiler
    refuses the variant (kept in ``kernel_report()``)."""
    import jax
    import jax.numpy as jnp
    from .flash_attention import kernel_dispatch_allowed, probe_compile
    rows = pick_rows(R, N)
    if rows is None or not kernel_dispatch_allowed():
        return None

    def compile_fn():
        jax.jit(functools.partial(topk_select, k=k, rows=rows)).lower(
            jax.ShapeDtypeStruct((R, N), jnp.int32)).compile()

    return rows if probe_compile("topk_select", (R, N, int(k), rows),
                                 compile_fn) else None
