"""NDArray semantics (reference: tests/python/unittest/test_ndarray.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


def test_creation():
    a = nd.array([[1, 2], [3, 4]])
    assert a.shape == (2, 2)
    assert a.dtype == onp.float32
    assert nd.zeros((2, 3)).sum().asscalar() == 0
    assert nd.ones((2, 3)).sum().asscalar() == 6
    assert nd.full((2,), 7).asnumpy().tolist() == [7, 7]
    assert nd.arange(0, 6, 2).asnumpy().tolist() == [0, 2, 4]
    assert nd.eye(3).asnumpy().trace() == 3


def test_arith_broadcast():
    a = nd.array([[1., 2.], [3., 4.]])
    b = nd.array([10., 20.])
    assert_almost_equal((a + b).asnumpy(), a.asnumpy() + b.asnumpy())
    assert_almost_equal((a * b).asnumpy(), a.asnumpy() * b.asnumpy())
    assert_almost_equal((a - 1).asnumpy(), a.asnumpy() - 1)
    assert_almost_equal((2 / a).asnumpy(), 2 / a.asnumpy())
    assert_almost_equal((a ** 2).asnumpy(), a.asnumpy() ** 2)
    assert_almost_equal((-a).asnumpy(), -a.asnumpy())


def test_inplace():
    a = nd.ones((3,))
    a += 2
    assert a.asnumpy().tolist() == [3, 3, 3]
    a *= 2
    assert a.asnumpy().tolist() == [6, 6, 6]
    a[1] = 0
    assert a.asnumpy().tolist() == [6, 0, 6]
    a[:] = 1
    assert a.asnumpy().tolist() == [1, 1, 1]


def test_indexing():
    a = nd.array(onp.arange(24).reshape(2, 3, 4))
    assert a[1].shape == (3, 4)
    assert a[:, 1].shape == (2, 4)
    assert a[1, 2, 3].asscalar() == 23
    assert a[:, :, ::2].shape == (2, 3, 2)
    idx = nd.array([0, 1])
    assert a[idx.astype('int32')].shape == (2, 3, 4)


def test_reshape_specials():
    a = nd.zeros((2, 3, 4))
    assert a.reshape(-1).shape == (24,)
    assert a.reshape(0, -1).shape == (2, 12)
    assert a.reshape((4, 6)).shape == (4, 6)
    assert a.flatten().shape == (2, 12)
    assert a.expand_dims(0).shape == (1, 2, 3, 4)
    assert a.transpose().shape == (4, 3, 2)
    assert a.swapaxes(0, 2).shape == (4, 3, 2)


def test_reduce_methods():
    a = nd.array([[1., 2.], [3., 4.]])
    assert a.sum().asscalar() == 10
    assert a.mean(axis=0).asnumpy().tolist() == [2, 3]
    assert a.max().asscalar() == 4
    assert a.min(axis=1).asnumpy().tolist() == [1, 3]
    assert a.argmax(axis=1).asnumpy().tolist() == [1, 1]
    assert_almost_equal(a.norm().asscalar(), onp.linalg.norm(a.asnumpy()),
                        rtol=1e-5)


def test_dtype_cast():
    a = nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.asnumpy().dtype == onp.int32
    bf = a.astype("bfloat16")
    assert str(bf._data.dtype) == "bfloat16"
    back = bf.astype("float32")
    assert back.asnumpy().tolist() == [1.5, 2.5]


def test_save_load(tmp_path):
    f = str(tmp_path / "arrs")
    d = {"w": nd.array([[1., 2.]]), "b": nd.arange(0, 3)}
    nd.save(f, d)
    loaded = nd.load(f)
    assert set(loaded) == {"w", "b"}
    assert_almost_equal(loaded["w"].asnumpy(), d["w"].asnumpy())
    nd.save(f, [nd.ones((2, 2))])
    as_list = nd.load(f)
    assert isinstance(as_list, list) and as_list[0].shape == (2, 2)


def test_context_placement():
    a = nd.ones((2,), ctx=mx.cpu(0))
    assert a.context.device_type == "cpu"
    b = a.as_in_context(mx.cpu(0))
    assert b.context == mx.cpu(0)
    c = a.copyto(mx.cpu(0))
    assert c.shape == a.shape


def test_scalar_conversions():
    a = nd.array([3.5])
    assert float(a) == 3.5
    assert int(a) == 3
    assert bool(nd.array([1.0]))
    with pytest.raises(mx.MXNetError):
        bool(nd.ones((2,)))


def test_iter_len():
    a = nd.array(onp.arange(6).reshape(3, 2))
    assert len(a) == 3
    rows = [r.asnumpy().tolist() for r in a]
    assert rows[0] == [0, 1]


def test_waitall_and_wait_to_read():
    a = nd.ones((8, 8))
    b = nd.dot(a, a)
    b.wait_to_read()
    nd.waitall()
    assert b.asnumpy()[0, 0] == 8


def test_npx_namespace():
    """mx.npx: the numpy-extension op surface (reference _npx_* ops) routes
    into the shared registry; mode switches record and reverse."""
    import mxnet_tpu as mx
    x = mx.np.array(onp.array([[1.0, 2.0, 3.0], [0.0, 0.0, 0.0]]))
    s = mx.npx.softmax(x, axis=-1).asnumpy()
    assert abs(s[0].sum() - 1.0) < 1e-6 and abs(s[1, 0] - 1 / 3) < 1e-6
    w = mx.np.array(onp.eye(3, dtype="float32"))
    y = mx.npx.fully_connected(x, w, num_hidden=3, no_bias=True)
    assert onp.allclose(y.asnumpy(), x.asnumpy())
    assert mx.npx.pick(x, mx.np.array([2, 0])).asnumpy().tolist() == [3.0, 0.0]
    assert not mx.npx.is_np_array()
    mx.npx.set_np()
    assert mx.npx.is_np_array() and mx.npx.is_np_shape()
    mx.npx.reset_np()
    assert not mx.npx.is_np_shape()

    @mx.npx.use_np
    def f(a):
        return a + 1
    assert f(1) == 2


def test_np_expanded_surface():
    """Spot-check the wider mx.np coverage (reference _npi_* matrix)."""
    np = mx.np
    a = np.array([[1., 2.], [3., 4.]])
    assert float(np.trace(a).asnumpy()) == 5.0
    assert np.tril(a).asnumpy().tolist() == [[1, 0], [3, 4]]
    assert np.vstack([a, a]).shape == (4, 2)
    gx, gy = np.meshgrid(np.array([1., 2.]), np.array([3., 4., 5.]))
    assert gx.shape == (3, 2) and gy.shape == (3, 2)
    h, edges = np.histogram(np.array([1., 2., 2., 3.]), bins=3)
    assert int(h.asnumpy().sum()) == 4 and edges.shape == (4,)
    l, r = np.hsplit(a, 2)
    assert l.shape == (2, 1)
    assert float(np.percentile(a, 50).asnumpy()) == 2.5
    assert float(np.average(a).asnumpy()) == 2.5
    assert np.swapaxes(a, 0, 1).asnumpy().tolist() == [[1, 3], [2, 4]]
    assert np.roll(a, 1, axis=1).asnumpy().tolist() == [[2, 1], [4, 3]]
    # gradients flow through the tape-routed ones
    from mxnet_tpu import autograd
    x = np.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = (np.tril(np.outer(x, x))).sum()
    y.backward()
    assert x.grad.asnumpy().tolist() == [4.0, 5.0]


def test_np_linalg_family():
    np = mx.np
    rng = onp.random.RandomState(0)
    a = np.array(rng.randn(4, 4).astype("float32"))
    sym = np.matmul(a, np.transpose(a)) + 4 * np.eye(4)
    L = np.linalg.cholesky(sym)
    assert_almost_equal(np.matmul(L, np.transpose(L)).asnumpy(),
                        sym.asnumpy(), atol=1e-4, rtol=1e-4)
    sgn, logdet = np.linalg.slogdet(sym)
    assert float(sgn.asnumpy()) == 1.0
    u, s, vt = np.linalg.svd(sym)
    assert u.shape == (4, 4) and s.shape == (4,)
    x = np.linalg.solve(sym, np.ones((4,)))
    assert_almost_equal(np.matmul(sym, x).asnumpy(), onp.ones(4),
                        atol=1e-4, rtol=1e-4)
    w, v = np.linalg.eigh(sym)
    assert (w.asnumpy() > 0).all()
    # differentiable through the tape
    from mxnet_tpu import autograd
    m = np.array(rng.randn(3, 3).astype("float32") + 3 * onp.eye(3,
                                                                 dtype="f4"))
    m._requires_grad = True
    m.attach_grad()
    with autograd.record():
        out = np.linalg.norm(m)
    out.backward()
    assert m.grad.shape == (3, 3)


@pytest.mark.slow
def test_np_random_distributions():
    np = mx.np
    mx.random.seed(0)
    for name, args, kw in [("beta", (2.0, 5.0), {}),
                           ("chisquare", (3.0,), {}),
                           ("laplace", (0.0, 1.0), {}),
                           ("gumbel", (0.0, 1.0), {}),
                           ("pareto", (3.0,), {}),
                           ("weibull", (2.0,), {}),
                           ("rayleigh", (1.0,), {}),
                           ("lognormal", (0.0, 0.5), {}),
                           ("f", (4.0, 6.0), {}),
                           ("standard_t", (5.0,), {})]:
        x = getattr(np.random, name)(*args, size=(64,), **kw)
        assert x.shape == (64,)
        assert onp.isfinite(x.asnumpy()).all(), name
    # statistical sanity: beta(2,5) mean ~ 2/7
    b = np.random.beta(2.0, 5.0, size=(4000,))
    assert abs(float(b.asnumpy().mean()) - 2 / 7) < 0.03
    mn = np.random.multinomial(20, np.array(onp.array([0.3, 0.7], "f4")),
                               size=(5,))
    assert mn.shape == (5, 2)
    assert (mn.asnumpy().sum(-1) == 20).all()
    pm = np.random.permutation(10)
    assert sorted(pm.asnumpy().tolist()) == list(range(10))
    c = np.random.choice(np.arange(100), size=(7,))
    assert c.shape == (7,)


def test_np_boolean_fancy_indexing():
    np = mx.np
    a = np.array(onp.arange(12, dtype="float32").reshape(3, 4))
    mask = a > 5
    sel = a[mask]
    assert sel.asnumpy().tolist() == [6.0, 7.0, 8.0, 9.0, 10.0, 11.0]
    row_mask = np.array(onp.array([True, False, True]))
    assert a[row_mask].shape == (2, 4)
    a[a > 9] = 0.0
    assert float(a.asnumpy().max()) == 9.0
    idx = np.where(a == 9.0)
    assert (int(idx[0].asnumpy()[0]), int(idx[1].asnumpy()[0])) == (2, 1)


def test_np_long_tail_ops():
    np = mx.np
    a = np.array(onp.array([3.0, 1.0, 2.0, onp.nan], "f4"))
    assert float(np.nanmax(a).asnumpy()) == 3.0
    assert int(np.nanargmin(a).asnumpy()) == 1
    assert float(np.ptp(np.array(onp.array([1.0, 5.0], "f4"))).asnumpy()) \
        == 4.0
    s = np.searchsorted(np.array(onp.array([1.0, 2.0, 4.0], "f4")),
                        np.array(onp.array([3.0], "f4")))
    assert int(s.asnumpy()[0]) == 2
    cc = np.corrcoef(np.array(onp.arange(5, dtype="f4")),
                     np.array(onp.arange(5, dtype="f4") * 2))
    assert abs(float(cc.asnumpy()[0, 1]) - 1.0) < 1e-5
    g = np.gradient(np.array(onp.array([1.0, 2.0, 4.0], "f4")))
    assert g.shape == (3,)
    import jax as _jax
    if _jax.devices()[0].platform == "cpu":
        # FFT: CPU-only, see test_operator.py
        f = np.fft.fft(np.array(onp.ones(8, "f4")))
        assert f.shape == (8,)
        assert abs(float(np.real(f).asnumpy()[0]) - 8.0) < 1e-5
    assert np.allclose(np.array(onp.ones(3, "f4")),
                       np.array(onp.ones(3, "f4")))
    import tempfile, os as _os
    pth = _os.path.join(tempfile.mkdtemp(), "a.npy")
    np.save(pth, np.array(onp.arange(4, dtype="f4")))
    back = np.load(pth)
    assert back.asnumpy().tolist() == [0.0, 1.0, 2.0, 3.0]
