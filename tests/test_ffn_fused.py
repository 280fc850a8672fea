"""Fused FFN (Dense -> GELU/ReLU -> Dense -> Dropout) Pallas kernel parity.

TPU-only (the CI CPU mesh skips this file).  Run on a TPU host
(`python -m pytest tests/test_ffn_fused.py` with JAX_PLATFORMS unset) —
the parity gate for the FFN layout BERT/Transformer actually train
through.  Reference semantics: GluonNLP PositionwiseFFN
(fully_connected.cc + activation.cc chain).
"""
import importlib
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

ff = importlib.import_module("mxnet_tpu.ops.ffn_fused")

pytestmark = pytest.mark.skipif(
    jax.devices()[0].platform == "cpu",
    reason="fused FFN pallas kernels are TPU-only")


def _inputs(B=4, L=512, d=768, h=3072, dtype=jnp.bfloat16, seed=0):
    rng = onp.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, L, d) * 0.5, dtype)
    w1 = jnp.asarray(rng.randn(h, d) * 0.03, dtype)
    b1 = jnp.asarray(rng.randn(h) * 0.01, dtype)
    w2 = jnp.asarray(rng.randn(d, h) * 0.03, dtype)
    b2 = jnp.asarray(rng.randn(d) * 0.01, dtype)
    return x, w1, b1, w2, b2


@pytest.mark.parametrize("act", ["gelu", "relu"])
def test_forward_matches_reference(act):
    x, w1, b1, w2, b2 = _inputs()
    y = jax.jit(lambda *a: ff.ffn_gelu(*a, 0.0, None, act))(
        x, w1, b1, w2, b2)
    ref = ff.ffn_gelu_ref(x, w1, b1, w2, b2, act)
    err = onp.abs(onp.asarray(y, onp.float32)
                  - onp.asarray(ref, onp.float32)).max()
    scale = onp.abs(onp.asarray(ref, onp.float32)).max()
    # bf16 ulp at the output magnitude (the fp32 reference runs exact
    # under the TPU suite's highest-precision pin; the kernel is bf16)
    assert err <= 0.008 * max(scale, 1.0), (err, scale)


@pytest.mark.parametrize("act", ["gelu", "relu"])
def test_grads_match_xla_composition(act):
    x, w1, b1, w2, b2 = _inputs()

    def comp(x, w1, b1, w2, b2):
        u = jax.lax.dot_general(
            x, w1, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + b1.astype(jnp.float32)
        u = u.astype(jnp.bfloat16).astype(jnp.float32)
        if act == "gelu":
            g = 0.5 * u * (1 + jax.lax.erf(u * 0.7071067811865476))
        else:
            g = jnp.maximum(u, 0.0)
        g = g.astype(jnp.bfloat16)
        y = jax.lax.dot_general(
            g, w2, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + b2.astype(jnp.float32)
        return y.astype(jnp.bfloat16)

    def gradfn(f):
        return jax.jit(jax.grad(
            lambda *a: (f(*a).astype(jnp.float32) ** 2).mean(),
            argnums=(0, 1, 2, 3, 4)))

    gf = gradfn(lambda *a: ff.ffn_gelu(*a, 0.0, None, act))(
        x, w1, b1, w2, b2)
    gr = gradfn(comp)(x, w1, b1, w2, b2)
    for name, a, b in zip(("dx", "dw1", "db1", "dw2", "db2"), gf, gr):
        a = onp.asarray(a, onp.float32)
        b = onp.asarray(b, onp.float32)
        scale = onp.abs(b).max() + 1e-9
        rel = onp.abs(a - b).max() / scale
        assert rel <= 0.02, (name, rel)


def test_dropout_deterministic_and_scaled():
    """Same seed -> same mask (fwd/bwd consistency is what custom_vjp
    relies on); mean is approximately preserved by the 1/(1-p) scale."""
    x, w1, b1, w2, b2 = _inputs(B=2, L=256)
    seed = jnp.asarray([1234], jnp.int32)
    f = jax.jit(lambda *a: ff.ffn_gelu(*a, 0.3, seed))
    y1 = onp.asarray(f(x, w1, b1, w2, b2), onp.float32)
    y2 = onp.asarray(f(x, w1, b1, w2, b2), onp.float32)
    onp.testing.assert_array_equal(y1, y2)
    y0 = onp.asarray(
        jax.jit(lambda *a: ff.ffn_gelu(*a, 0.0, None))(x, w1, b1, w2, b2),
        onp.float32)
    kept = y1 != 0
    assert 0.6 <= kept.mean() <= 0.8           # ~70% kept
    # kept entries are the no-dropout values scaled by 1/(1-p)
    ratio = y1[kept] / onp.where(y0[kept] == 0, 1, y0[kept])
    assert onp.isfinite(ratio).all()
    onp.testing.assert_allclose(onp.median(ratio), 1.0 / 0.7, rtol=0.05)


def test_dropout_gradient_uses_same_mask():
    """d/dx of sum(ffn) with dropout: zeroed outputs contribute no
    gradient; the backward must regenerate the identical mask."""
    x, w1, b1, w2, b2 = _inputs(B=2, L=256)
    seed = jnp.asarray([77], jnp.int32)

    def loss(xx):
        y = ff.ffn_gelu(xx, w1, b1, w2, b2, 0.5, seed)
        return (y.astype(jnp.float32) ** 2).sum()

    g1 = onp.asarray(jax.jit(jax.grad(loss))(x), onp.float32)
    g2 = onp.asarray(jax.jit(jax.grad(loss))(x), onp.float32)
    onp.testing.assert_array_equal(g1, g2)
    assert onp.abs(g1).max() > 0


def test_model_level_fused_matches_layer_path_eval():
    """PositionwiseFFN (the BERT/Transformer building block) produces the
    same eval-mode outputs fused and unfused."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.bert import PositionwiseFFN

    rng = onp.random.RandomState(0)
    x = rng.randn(2, 256, 768).astype("float32")

    outs = {}
    for flag in ("1", "0"):
        os.environ["MXNET_FUSED_FFN"] = flag
        try:
            mx.random.seed(0)
            blk = PositionwiseFFN(768, 3072, dropout=0.1)
            blk.initialize()
            blk.cast("bfloat16")
            outs[flag] = blk(nd.array(x).astype("bfloat16")) \
                .astype("float32").asnumpy()
        finally:
            os.environ.pop("MXNET_FUSED_FFN", None)
    err = onp.abs(outs["1"] - outs["0"]).max()
    scale = onp.abs(outs["0"]).max()
    assert err <= 0.008 * max(scale, 1.0), (err, scale)


def test_mixed_dtype_falls_back_to_the_layer_path():
    """float32 parameters under bfloat16 activations: the kernel's dtype
    gate sends the block down the layer path, and a training step through
    it is finite instead of failing at the first call."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.models.bert import PositionwiseFFN

    mx.random.seed(0)
    ffn = PositionwiseFFN(units=256, hidden_size=1024, dropout=0.1)
    ffn.initialize()
    x = nd.array(onp.random.RandomState(0).randn(8, 128, 256)
                 .astype("float32")).astype("bfloat16")
    with autograd.record():
        loss = ffn(x).astype("float32").sum()
    loss.backward()
    assert onp.isfinite(float(loss.asnumpy()))
