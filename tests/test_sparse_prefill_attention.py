"""``ops.sparse_prefill_attention`` on the CPU: the kernel through Pallas'
interpreter against the XLA form that ``parts.sparse_block_attend`` runs
where no kernel does, at DeepSeek's geometry (one query head a key head,
keys wider than values) and Keye's (eight query heads a key head), and
against a plain softmax in float64.  (``run_full`` of both models with the
kernel forced is in ``test_deepseek.py`` and ``test_keye.py``; the kernel
through the chip's compiler at the cells' widths in
``test_latent_attention.py``: one file describes the chip.)"""
import functools

import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.models import parts
from mxnet_tpu.ops import sparse_prefill_attention as spa

B, BQ, L, BLOCK = 2, 16, 64, 16

# (key heads, query heads a key head, key width, value width)
GEOMETRY = {"deepseek": (4, 1, 24, 16), "keye": (2, 8, 16, 16)}


def _force_kernel(patch, block=BLOCK):
    """``sparse_block_attend`` takes the kernel, through the interpreter, at
    key blocks of ``block``: what a TPU decides from its backend is decided
    here by the test."""
    patch.setattr(spa, "kernel_block", lambda *a: block)
    patch.setattr(spa, "sparse_prefill_attention", functools.partial(
        spa.sparse_prefill_attention, interpret=True))


@pytest.fixture
def forced_kernel(monkeypatch):
    _force_kernel(monkeypatch)


def _case(geometry, block_i, keep, dtype, seed=0):
    """Operands of query block ``block_i``: q [B, KV, G * BQ, Dk], k, v and
    a causal mask [B, BQ, L] that keeps a share ``keep`` of each query's
    earlier positions and always the query itself."""
    kv, g, dk, dv = GEOMETRY[geometry]
    rs = onp.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, kv, g * BQ, dk), dtype)
    k = jnp.asarray(rs.randn(B, kv, L, dk), dtype)
    v = jnp.asarray(rs.randn(B, kv, L, dv), dtype)
    at = block_i * BQ + onp.arange(BQ)
    causal = onp.arange(L)[None] <= at[:, None]
    kept = (rs.rand(B, BQ, L) < keep) | (onp.arange(L)[None] == at[:, None])
    return q, k, v, jnp.asarray(causal & kept), block_i * BQ


def _both_forms(monkeypatch, *args, scale=0.3):
    """``(kernel, XLA form)``."""
    want = parts.sparse_block_attend(*args, scale)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        return parts.sparse_block_attend(*args, scale), want


def _plain(q, k, v, mask, scale=0.3):
    """The softmax a head, a query, in float64 loops: what both forms are."""
    q, k, v = (onp.asarray(a.astype(jnp.float32), "float64") for a in (q, k, v))
    kv, rows, _dk = q.shape[1:]
    dv = v.shape[-1]
    g = rows // BQ
    out = onp.zeros((B, BQ, kv, g, dv))
    for b in range(B):
        for t in range(BQ):
            keep = onp.asarray(mask[b, t])
            for h in range(kv):
                for j in range(g):
                    sc = k[b, h][keep] @ q[b, h, j * BQ + t] * scale
                    p = onp.exp(sc - sc.max())
                    out[b, t, h, j] = p / p.sum() @ v[b, h][keep]
    return out.reshape(B, BQ, kv * g * dv)


def _diff(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# float32: the kernel's blocks add up in another order than the einsum;
# bfloat16: the probabilities are rounded before the sum a block in the
# kernel and after the division in the XLA form, outputs reach 2 to 4
TOLERANCE = {"float32": 2e-5, "bfloat16": 2 ** -5}


@pytest.mark.parametrize("geometry", ["deepseek", "keye"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("block_i,keep", [
    (0, 1.0),       # the first block, where only causality masks
    (1, 0.25),      # off the diagonal: a quarter of the earlier positions
    (3, 0.25),      # the last block, every key block read
    (2, 1.0),       # causal alone past the first block
], ids=["first_causal", "second_quarter", "last_quarter", "third_causal"])
def test_kernel_is_the_masked_softmax_of_the_xla_form(
        monkeypatch, geometry, dtype, block_i, keep):
    args = _case(geometry, block_i, keep, dtype)
    got, want = _both_forms(monkeypatch, *args)
    kv, g, _dk, dv = GEOMETRY[geometry]
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == want.shape == (B, BQ, kv * g * dv)
    assert _diff(got, want) < TOLERANCE[dtype]
    # and no further from the plain softmax than the XLA form is
    exact = _plain(*args[:4])
    assert onp.abs(onp.asarray(got) - exact).max() < TOLERANCE[dtype]
    assert onp.abs(onp.asarray(want) - exact).max() < TOLERANCE[dtype]


@pytest.mark.parametrize("geometry", ["deepseek", "keye"])
@pytest.mark.parametrize("block_i", [0, 1, 2])
def test_key_blocks_past_the_query_block_are_neither_read_nor_computed(
        forced_kernel, geometry, block_i):
    q, k, v, mask, q_start = _case(geometry, block_i, 0.5, "float32")
    want = parts.sparse_block_attend(q, k, v, mask, q_start, 0.3)
    past = (block_i + 1) * BQ
    poisoned = [jnp.asarray(onp.asarray(a).copy()).at[:, :, past:].set(
        onp.nan) for a in (k, v)]
    got = parts.sparse_block_attend(q, *poisoned, mask, q_start, 0.3)
    assert onp.isfinite(onp.asarray(got)).all()
    assert (onp.asarray(got) == onp.asarray(want)).all()


def test_a_query_whose_mask_keeps_nothing_gets_zeros(forced_kernel):
    q, k, v, mask, q_start = _case("keye", 2, 0.5, "float32")
    mask = mask.at[1, 5].set(False)
    got = onp.asarray(parts.sparse_block_attend(q, k, v, mask, q_start, 0.3))
    assert (got[1, 5] == 0).all() and onp.isfinite(got).all()
    assert onp.abs(got[0, 5]).min() > 0 and onp.abs(got[1, 4]).min() > 0


@pytest.mark.parametrize("length,block", [
    (2816, 256), (3072, 1024), (7168, 1024), (8192, 1024), (384, 128),
    (24, None), (2800, None)])
def test_pick_block(length, block):
    assert spa.pick_block(length) == block


@pytest.mark.parametrize("kv_heads,groups,heads", [
    (128, 1, 8), (4, 8, 1), (4, 1, 4), (2, 8, 1), (6, 1, 6), (2, 16, 1)])
def test_a_grid_step_takes_eight_query_heads_at_most(kv_heads, groups,
                                                     heads):
    assert spa._head_block(kv_heads, groups) == heads


def test_no_kernel_on_a_cpu_or_for_a_sequence_no_block_divides():
    assert spa.kernel_block(1, 128, 1, 256, 2816, 192, 128,
                            "bfloat16") is None               # the CPU
    q, k, v, mask, q_start = _case("deepseek", 1, 0.5, "float32")
    with pytest.raises(ValueError, match="no block"):
        spa.sparse_prefill_attention(q, k, v, mask, q_start, 0.3)  # 64
