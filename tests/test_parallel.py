"""SPMD distribution over an 8-device CPU mesh (reference analogue:
tests/python/gpu/test_nccl.py + dist kvstore nightly tests — here the mesh
IS the comm backend, SURVEY.md §5.8)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu import parallel
from mxnet_tpu.test_utils import assert_almost_equal, rand_ndarray


def test_make_mesh():
    import jax
    n = len(jax.devices())
    if n >= 8:
        mesh = parallel.make_mesh({"data": 4, "model": 2})
        assert mesh.shape == {"data": 4, "model": 2}
    mesh2 = parallel.make_mesh({"data": -1})
    assert mesh2.shape["data"] == n


def test_shard_and_replicate():
    mesh = parallel.make_mesh({"data": 8})
    x = nd.array(onp.arange(16, dtype="float32").reshape(8, 2))
    xs = parallel.shard(x, mesh, ("data", None))
    assert xs.shape == (8, 2)
    assert_almost_equal(xs.asnumpy(), x.asnumpy())
    r = parallel.replicate(x, mesh)
    assert_almost_equal(r.asnumpy(), x.asnumpy())


def test_spmd_trainer_matches_single_device():
    """DP over 8 shards must produce the same update as single-device."""
    def build():
        mx.random.seed(5)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=4),
                nn.Dense(2, in_units=16))
        net.initialize()
        return net

    x_np = onp.random.RandomState(0).randn(16, 4).astype("float32")
    y_np = onp.random.RandomState(1).randn(16, 2).astype("float32")
    lossfn = gloss.L2Loss()

    # single-device reference
    net1 = build()
    tr1 = mx.gluon.Trainer(net1.collect_params(), "sgd",
                           {"learning_rate": 0.1})
    with autograd.record():
        l = lossfn(net1(nd.array(x_np)), nd.array(y_np))
    l.backward()
    tr1.step(16)
    ref_w = net1[0].weight.data().asnumpy()
    ref_loss = float(l.mean().asscalar())

    # SPMD over the mesh.  Match Trainer semantics: grad of mean loss with
    # rescale 1/batch -> use rescale_grad = batch to cancel... instead use
    # optimizer lr directly on mean-loss grads (Trainer divides by batch;
    # SPMD computes grad of mean loss, so set rescale_grad accordingly).
    net2 = build()
    mesh = parallel.make_mesh({"data": 8})
    from mxnet_tpu import optimizer as opt
    sgd = opt.SGD(learning_rate=0.1)
    sgd.rescale_grad = 1.0
    tr2 = parallel.SPMDTrainer(net2, lossfn, sgd, mesh)
    loss2 = tr2.step(nd.array(x_np), nd.array(y_np))
    got_w = net2[0].weight.data().asnumpy()

    # Trainer: w -= lr * grad_sum/16 where l.backward() seeds ones over the
    # 16 per-sample losses.  SPMD: grad of MEAN over samples => identical.
    assert abs(float(loss2.asnumpy()) - ref_loss) < 1e-5
    assert_almost_equal(got_w, ref_w, rtol=1e-4, atol=1e-5)


def test_spmd_trainer_multi_step_convergence():
    mx.random.seed(2)
    net = nn.Dense(1, in_units=3)
    net.initialize()
    mesh = parallel.make_mesh({"data": 8})
    from mxnet_tpu import optimizer as opt
    tr = parallel.SPMDTrainer(net, gloss.L2Loss(), opt.SGD(learning_rate=0.2),
                              mesh)
    w_true = onp.array([[1.0, -2.0, 0.5]], dtype="float32")
    rng = onp.random.RandomState(3)
    for _ in range(150):
        x = rng.randn(32, 3).astype("float32")
        y = x @ w_true.T
        tr.step(nd.array(x), nd.array(y))
    assert_almost_equal(net.weight.data().asnumpy(), w_true, rtol=5e-2,
                        atol=2e-2)


def test_tensor_parallel_sharding_rules():
    mesh = parallel.make_mesh({"data": 2, "model": 4})
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16), nn.Dense(16, in_units=32))
    net.initialize()
    # Megatron pattern: first layer column-parallel, second row-parallel
    parallel.shard_params(net, mesh, rules=[
        (r"0\.weight", ("model", None)),
        (r"1\.weight", (None, "model")),
    ])
    p0 = list(net._collect_params_with_prefix().values())[0]
    assert p0._sharding is not None
    # eager forward with sharded params: input must live on the mesh too
    x = parallel.replicate(rand_ndarray((4, 16)), mesh)
    out = net(x)
    assert out.shape == (4, 16)


def test_spmd_trainer_with_tp():
    mx.random.seed(9)
    mesh = parallel.make_mesh({"data": 2, "model": 4})
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=8),
            nn.Dense(4, in_units=32))
    net.initialize()
    parallel.shard_params(net, mesh, rules=[
        (r"0\.weight", ("model", None)),
        (r"0\.bias", ("model",)),
        (r"1\.weight", (None, "model")),
    ])
    from mxnet_tpu import optimizer as opt
    tr = parallel.SPMDTrainer(net, gloss.L2Loss(), opt.SGD(learning_rate=0.1),
                              mesh)
    x = rand_ndarray((8, 8))
    y = rand_ndarray((8, 4))
    l1 = float(tr.step(x, y).asnumpy())
    for _ in range(20):
        l2 = float(tr.step(x, y).asnumpy())
    assert l2 < l1


def test_ring_attention_matches_dense():
    import jax
    mesh = parallel.make_mesh({"seq": 4})
    B, L, H, D = 2, 16, 2, 8
    q = rand_ndarray((B, L, H, D))
    k = rand_ndarray((B, L, H, D))
    v = rand_ndarray((B, L, H, D))

    out_ring = parallel.ring_attention_fn and None  # namespacing check
    from mxnet_tpu.parallel.ring_attention import ring_self_attention
    out = ring_self_attention(q, k, v, mesh, seq_axis="seq")

    qn, kn, vn = q.asnumpy(), k.asnumpy(), v.asnumpy()
    s = onp.einsum("bqhd,bkhd->bhqk", qn, kn) / onp.sqrt(D)
    e = onp.exp(s - s.max(-1, keepdims=True))
    a = e / e.sum(-1, keepdims=True)
    dense = onp.einsum("bhqk,bkhd->bqhd", a, vn)
    assert_almost_equal(out.asnumpy(), dense, rtol=1e-3, atol=1e-4)


def test_ring_attention_causal():
    mesh = parallel.make_mesh({"seq": 4})
    B, L, H, D = 1, 8, 1, 4
    q = rand_ndarray((B, L, H, D))
    k = rand_ndarray((B, L, H, D))
    v = rand_ndarray((B, L, H, D))
    from mxnet_tpu.parallel.ring_attention import ring_self_attention
    out = ring_self_attention(q, k, v, mesh, seq_axis="seq", causal=True)
    qn, kn, vn = q.asnumpy(), k.asnumpy(), v.asnumpy()
    s = onp.einsum("bqhd,bkhd->bhqk", qn, kn) / onp.sqrt(D)
    mask = onp.tril(onp.ones((L, L), bool))
    s = onp.where(mask[None, None], s, -1e30)
    e = onp.exp(s - s.max(-1, keepdims=True))
    a = e / e.sum(-1, keepdims=True)
    dense = onp.einsum("bhqk,bkhd->bqhd", a, vn)
    assert_almost_equal(out.asnumpy(), dense, rtol=1e-3, atol=1e-4)


def test_sync_batchnorm_runs():
    net = nn.SyncBatchNorm(in_channels=4)
    net.initialize()
    x = rand_ndarray((8, 4, 2, 2))
    with autograd.record():
        y = net(x)
    assert y.shape == x.shape


def test_spmd_trainer_deferred_init_bf16():
    """Deferred-shape params (in_channels=0) + cast('bfloat16'): the trainer
    must complete deferred init abstractly and keep weight/state dtypes
    stable across steps (no recompile, donation stays valid)."""
    from mxnet_tpu import optimizer as opt
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1), nn.Activation("relu"),
            nn.GlobalAvgPool2D(), nn.Dense(4))
    net.initialize()
    net.cast("bfloat16")
    assert any(p._nd is None
               for p in net._collect_params_with_prefix().values())
    mesh = parallel.make_mesh({"data": 8})
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    tr = parallel.SPMDTrainer(
        net, lambda o, l: lossfn(o.astype("float32"), l),
        opt.SGD(learning_rate=0.05, momentum=0.9), mesh)
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(16, 3, 8, 8).astype("float32")).astype("bfloat16")
    y = nd.array(rng.randint(0, 4, (16,)).astype("float32"))
    losses = [float(tr.step(x, y).astype("float32").asnumpy())
              for _ in range(6)]
    assert all(onp.isfinite(losses))
    assert losses[-1] < losses[0]
    for p in tr._params:
        assert str(p._nd._data.dtype) == "bfloat16", p.name
    for st in tr._states:
        for s in st:
            assert str(s.dtype) == "bfloat16"


def test_zero1_state_sharding():
    """ZeRO-1: optimizer states are sharded (not replicated) over the data
    axis, per-device state memory drops ~1/N, and training matches the
    replicated-state trainer."""
    import jax

    def build():
        onp.random.seed(5)
        mx.random.seed(5)
        net = nn.Dense(64, in_units=64)
        net.initialize()
        return net

    mesh = parallel.make_mesh({"data": 8})
    x = rand_ndarray((16, 64))
    y = rand_ndarray((16, 64))

    losses = {}
    for zero1 in (False, True):
        from mxnet_tpu import optimizer as opt_mod
        tr = parallel.SPMDTrainer(build(), lambda o, t: ((o - t) ** 2).mean(),
                                  opt_mod.Adam(learning_rate=1e-2), mesh,
                                  zero1=zero1)
        ls = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        losses[zero1] = ls
        if not zero1:
            continue
        n_sharded = 0
        for p, st in zip(tr._params, tr._states):
            for s in st:
                if getattr(s, "ndim", 0) == 0:
                    continue
                spec = s.sharding.spec
                if p.shape[0] % 8 == 0:
                    # sharded over the data axis...
                    assert "data" in tuple(spec), \
                        f"state for {p.name} not zero1-sharded: {spec}"
                    # ...and the local shard really is 1/8 of the tensor
                    shard = s.addressable_shards[0]
                    assert shard.data.size == s.size // 8
                    n_sharded += 1
        assert n_sharded >= 2  # adam m and v for the weight at least
    # same training trajectory either way (fp reassociation tolerance)
    for a, b in zip(losses[False], losses[True]):
        assert abs(a - b) < 1e-4 * max(1.0, abs(a))


def _zero_build(seed=5):
    onp.random.seed(seed)
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu", in_units=64),
            nn.Dense(32, in_units=64))
    net.initialize()
    return net


def test_zero2_grad_shard_update_matches_replicated():
    """ZeRO-2: gradients reduce-scatter over the data axis, each replica
    updates only its optimizer-state shard, fresh params all-gather
    in-step — same trajectory as the replicated trainer, params still
    replicated at rest."""
    from mxnet_tpu import optimizer as opt_mod
    mesh = parallel.make_mesh({"data": 8})
    x = rand_ndarray((16, 64))
    y = rand_ndarray((16, 32))
    losses = {}
    for mode in ("rep", "zero2"):
        tr = parallel.SPMDTrainer(
            _zero_build(), lambda o, t: ((o - t) ** 2).mean(),
            opt_mod.Adam(learning_rate=1e-2), mesh,
            zero2=(mode == "zero2"))
        losses[mode] = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        if mode != "zero2":
            continue
        n_sharded = 0
        for p, st in zip(tr._params, tr._states):
            for s in st:
                if getattr(s, "ndim", 0) == 0 or p.shape[0] % 8:
                    continue
                assert "data" in tuple(s.sharding.spec), \
                    f"state for {p.name} not zero2-sharded"
                assert s.addressable_shards[0].data.size == s.size // 8
                n_sharded += 1
        assert n_sharded >= 2
        # params remain replicated at rest (full copy on every device)
        for p in tr._params:
            w = p._nd._data
            assert w.addressable_shards[0].data.size == w.size, p.name
    for a, b in zip(losses["rep"], losses["zero2"]):
        assert abs(a - b) < 1e-4 * max(1.0, abs(a))


def test_zero3_params_sharded_at_rest():
    """ZeRO-3: parameters live sharded at rest (1/N per device); XLA
    all-gathers a block's weights at its use sites.  Trajectory matches
    the replicated trainer and data() still reads back the full tensor."""
    from mxnet_tpu import optimizer as opt_mod
    mesh = parallel.make_mesh({"data": 8})
    x = rand_ndarray((16, 64))
    y = rand_ndarray((16, 32))
    losses = {}
    for mode in ("rep", "zero3"):
        tr = parallel.SPMDTrainer(
            _zero_build(), lambda o, t: ((o - t) ** 2).mean(),
            opt_mod.Adam(learning_rate=1e-2), mesh,
            zero3=(mode == "zero3"))
        losses[mode] = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        if mode != "zero3":
            continue
        n_sharded = 0
        for p in tr._params:
            if p.shape[0] % 8:
                continue
            w = p._nd._data
            assert "data" in tuple(w.sharding.spec), p.name
            assert w.addressable_shards[0].data.size == w.size // 8
            n_sharded += 1
        assert n_sharded >= 2
        full = tr._params[0].data().asnumpy()
        assert full.shape == tuple(tr._params[0].shape)
    for a, b in zip(losses["rep"], losses["zero3"]):
        assert abs(a - b) < 1e-4 * max(1.0, abs(a))


@pytest.mark.parametrize("zero", [1, 2, 3])
def test_zero_per_device_bytes_from_shardings(zero):
    """What each ZeRO level leaves on a device, counted from shardings
    (``parallel.dryrun._per_device_footprint``: params and optimizer
    state from addressable shards, gradients from the shardings the step
    pinned): 1/dp of every tensor the level shards, the whole of the
    rest.  Every tensor of this net divides by 8, so the ladder is exact:
    zero1 P + G + S/8, zero2 P + (G + S)/8, zero3 (P + G + S)/8."""
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel.dryrun import _per_device_footprint
    dp = 8
    mesh = parallel.make_mesh({"data": dp})
    tr = parallel.SPMDTrainer(
        _zero_build(), lambda o, t: ((o - t) ** 2).mean(),
        opt_mod.Adam(learning_rate=1e-2), mesh,
        zero1=(zero == 1), zero2=(zero == 2), zero3=(zero == 3))
    tr.step(rand_ndarray((16, 64)), rand_ndarray((16, 32)))
    mb = sum(int(onp.prod(p.shape)) for p in tr._params) * 4 / 2 ** 20
    got = _per_device_footprint(tr)
    want = {"param_mb": mb / (dp if zero >= 3 else 1),
            "grad_mb": mb / (dp if zero >= 2 else 1),
            "state_mb": 2 * mb / dp}          # adam m and v
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-6), (k, got)
    assert got["total_mb"] == pytest.approx(sum(want.values()), rel=1e-6)


def test_zero_diag_norms_bit_identical():
    """PR-14 diagnostics tail under zero2/zero3: per-block square-sums
    fold across the mesh inside the program, so the host-read diag
    vector is bit-for-bit equal to the replicated trainer's under zero2,
    and under zero3 in every entry that fold produces (below)."""
    from mxnet_tpu import optimizer as opt_mod
    mesh = parallel.make_mesh({"data": 8})
    diags = {}
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(16, 64).astype("float32"))
    y = nd.array(rng.randn(16, 32).astype("float32"))
    for mode in ("rep", "zero2", "zero3"):
        tr = parallel.SPMDTrainer(
            _zero_build(), lambda o, t: ((o - t) ** 2).mean(),
            opt_mod.Adam(learning_rate=1e-2), mesh,
            zero2=(mode == "zero2"), zero3=(mode == "zero3"))
        # compare the FIRST update's diag vector: all three trainers see
        # bit-identical params and batch, so any diag difference can only
        # come from the sharded square-sum fold itself
        args = tr._prepare_step_args(x, y, 1)
        if tr._diag_spec is None:
            pytest.skip("step diagnostics disabled in this environment")
        diags[mode] = onp.asarray(tr._step_fn(*args)[5])
    # layout: [loss, gsq, wsq, dsq, nonfinite] + per-block (gsq, wsq, dsq).
    # zero2 must be bit-identical across the WHOLE vector: its gradients
    # come off the same all-reduce association as the replicated program,
    # and the diag fold itself is pinned (gather-then-reduce, see the
    # optimization_barrier in the trainer's diag wrapper).  zero3 keeps
    # what that fold pins bit-exact: the param norms and the nonfinite
    # count.  Its gradients are produced by the param all-gather's
    # transpose — a true reduce-scatter whose summation order
    # legitimately differs in the last ulp — so its grad-norm and
    # update-delta entries get a tight allclose.  Its loss is held to
    # 1 ulp: the forward runs on parameters the partitioner gathers where
    # it uses them, so the batch-mean's partial sums associate as the
    # partitioner chose for THAT program (1.7190754 against 1.7190753 on
    # XLA:CPU).  Pinning it would mean gathering every parameter up front
    # behind a barrier, which is the memory zero3 exists to save.
    n = len(diags["rep"])
    n_blocks = (n - 5) // 3
    grad_or_delta = {1, 3} | {5 + 3 * b for b in range(n_blocks)} \
        | {5 + 3 * b + 2 for b in range(n_blocks)}
    exact3 = [i for i in range(1, n) if i not in grad_or_delta]
    assert diags["zero2"].shape == diags["rep"].shape
    assert (diags["zero2"] == diags["rep"]).all(), \
        (diags["zero2"], diags["rep"])
    assert (diags["zero3"][exact3] == diags["rep"][exact3]).all(), \
        (diags["zero3"], diags["rep"])
    onp.testing.assert_array_max_ulp(diags["zero3"][0], diags["rep"][0],
                                     maxulp=1)
    onp.testing.assert_allclose(diags["zero3"][sorted(grad_or_delta)],
                                diags["rep"][sorted(grad_or_delta)],
                                rtol=1e-5)


def test_spmd_trainer_pipeline_stages():
    """pipeline_stages=N promotes GPipe wiring to a trainer config: the
    constructor attaches the mesh, shards the stacked params P('pipe'),
    and validates the stage count against the mesh axis."""
    from mxnet_tpu import optimizer as opt
    mx.random.seed(7)
    S, D = 2, 8
    mesh = parallel.make_mesh({"pipe": S, "data": 2})
    net = nn.HybridSequential()
    net.add(nn.Dense(D, in_units=D, flatten=False),
            parallel.GPipe(nn.Dense(D, activation="tanh", in_units=D,
                                    flatten=False),
                           num_stages=S, num_microbatches=2,
                           data_axis="data"),
            nn.Dense(2, in_units=D, flatten=False))
    net.initialize()
    lossfn = gloss.L2Loss()
    tr = parallel.SPMDTrainer(net, lambda o, t: lossfn(o, t),
                              opt.SGD(learning_rate=0.05), mesh,
                              data_axis="data", pipeline_stages=S)
    gp = net[1]
    assert gp._mesh is mesh
    w = gp._stacked["weight"]
    assert w._sharding is not None and "pipe" in tuple(w._sharding.spec)
    rng = onp.random.RandomState(3)
    x = rng.randn(8, D).astype("float32")
    y = rng.randn(8, 2).astype("float32")
    losses = [float(tr.step(nd.array(x), nd.array(y)).asnumpy())
              for _ in range(8)]
    assert losses[-1] < losses[0], losses
    assert all(onp.isfinite(l) for l in losses)
    # stage-count mismatch with the mesh config is rejected up front
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError):
        parallel.SPMDTrainer(net, lambda o, t: lossfn(o, t),
                             opt.SGD(learning_rate=0.05), mesh,
                             data_axis="data", pipeline_stages=S + 1)


def test_spmd_trainer_ring_attention():
    """ring_attention=True routes full-sequence self-attention through
    the sequence-parallel ring kernel inside the captured step; the
    trajectory matches the dense-attention trainer (and composes with
    zero3)."""
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.models.bert import MultiHeadAttention

    def build():
        onp.random.seed(13)
        mx.random.seed(13)
        net = nn.HybridSequential()
        net.add(MultiHeadAttention(16, 2, dropout=0.0),
                nn.Dense(4, in_units=16, flatten=False))
        net.initialize()
        return net

    B, L = 8, 16
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(B, L, 16).astype("float32"))
    y = nd.array(rng.randn(B, L, 4).astype("float32"))
    lossfn = gloss.L2Loss()
    losses = {}
    for mode in ("dense", "ring", "ring_zero3"):
        mesh = parallel.make_mesh({"data": 2, "seq": 4})
        tr = parallel.SPMDTrainer(
            build(), lambda o, t: lossfn(o, t),
            opt.SGD(learning_rate=0.05), mesh, data_axis="data",
            ring_attention=(mode != "dense"),
            zero3=(mode == "ring_zero3"))
        losses[mode] = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
    for mode in ("ring", "ring_zero3"):
        for a, b in zip(losses["dense"], losses[mode]):
            assert abs(a - b) < 5e-4 * max(1.0, abs(a))
