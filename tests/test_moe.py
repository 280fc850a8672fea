"""MoE / expert-parallelism tests (SURVEY §2.3 EP — greenfield capability).

Follows the reference test pattern (SURVEY §4): numeric oracle against a
straightforward python reference implementation + distributed semantics on
the virtual CPU mesh.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, parallel
from mxnet_tpu.parallel import moe


def _reference_moe(x, gate_w, w1, b1, w2, b2, k, capacity, act="gelu"):
    """Slow loop-based reference: same routing semantics as moe_dispatch."""
    import scipy.special as sp
    T, d = x.shape
    E = gate_w.shape[0]
    probs = sp.softmax(x @ gate_w.T, axis=-1)
    # slot-by-slot assignment, tokens in order, capacity drop
    p = probs.copy()
    counts = onp.zeros(E, int)
    gates = onp.zeros((T, E))
    for s in range(k):
        idx = p.argmax(-1)
        for t in range(T):
            e = idx[t]
            if counts[e] < capacity:
                gates[t, e] = p[t, e]
            counts[e] += 1
            p[t, e] = 0.0
        # recompute counts per slot in token order: done above sequentially
    denom = gates.sum(-1, keepdims=True) + 1e-9
    gates = gates / denom
    y = onp.zeros_like(x)
    for t in range(T):
        for e in range(E):
            if gates[t, e] > 0:
                h = x[t] @ w1[e] + b1[e]
                if act == "relu":
                    h = onp.maximum(h, 0)
                else:
                    h = h * 0.5 * (1 + sp.erf(h / onp.sqrt(2.0)))
                y[t] += gates[t, e] * (h @ w2[e] + b2[e])
    return y


def test_moe_dispatch_capacity_and_loadbalance():
    import jax.numpy as jnp
    rng = onp.random.RandomState(0)
    T, E, k, cap = 16, 4, 2, 5
    probs = onp.abs(rng.rand(T, E)) + 1e-3
    probs = probs / probs.sum(-1, keepdims=True)
    combine, aux = moe.moe_dispatch(jnp.asarray(probs, jnp.float32), k, cap)
    combine = onp.asarray(combine)
    # every token contributes to <= k experts, each slot index < cap
    assert combine.shape == (T, E, cap)
    per_tok_experts = (combine.sum(-1) > 0).sum(-1)
    assert (per_tok_experts <= k).all()
    # no expert slot is used twice
    slot_use = (combine > 0).sum(0)          # [E, cap]
    assert (slot_use <= 1).all()
    # each expert received at most cap tokens
    assert ((combine.sum(-1) > 0).sum(0) <= cap).all()
    assert float(aux) > 0


def test_moe_layer_matches_reference():
    rng = onp.random.RandomState(1)
    T, d, h, E, k = 12, 8, 16, 4, 2
    layer = moe.MoE(units=d, hidden_size=h, num_experts=E, k=k,
                    capacity_factor=8.0)  # big capacity: no drops
    layer.initialize()
    x = nd.array(rng.randn(T, d).astype("float32"))
    y = layer(x)
    ref = _reference_moe(
        x.asnumpy(),
        layer.gate_weight.data().asnumpy(),
        layer.expert_w1.data().asnumpy(), layer.expert_b1.data().asnumpy(),
        layer.expert_w2.data().asnumpy(), layer.expert_b2.data().asnumpy(),
        k, layer.capacity(T))
    onp.testing.assert_allclose(y.asnumpy(), ref, rtol=2e-4, atol=2e-5)


def test_moe_capacity_drops_tokens():
    # tiny capacity: overflowing tokens produce zero output rows
    rng = onp.random.RandomState(2)
    T, d, h, E = 32, 4, 8, 2
    layer = moe.MoE(units=d, hidden_size=h, num_experts=E, k=1,
                    capacity_factor=0.25)
    layer.initialize()
    cap = layer.capacity(T)
    assert cap < T // E
    x = nd.array(rng.randn(T, d).astype("float32"))
    y = layer(x).asnumpy()
    zero_rows = (onp.abs(y).sum(-1) < 1e-12).sum()
    assert zero_rows >= T - E * cap - 1  # most overflow rows are zeroed


def test_moe_grad_flows_and_aux_loss():
    rng = onp.random.RandomState(3)
    B, S, d = 2, 6, 8
    layer = moe.MoE(units=d, hidden_size=16, num_experts=4, k=2)
    layer.initialize()
    x = nd.array(rng.randn(B, S, d).astype("float32"))
    with moe.aux_loss_scope() as aux_losses:
        with autograd.record():
            y = layer(x)
            loss = (y * y).mean() + 0.01 * moe.collected_aux_loss(aux_losses)
        loss.backward()
    g = layer.gate_weight.grad().asnumpy()
    assert onp.isfinite(g).all() and onp.abs(g).sum() > 0
    gw1 = layer.expert_w1.grad().asnumpy()
    assert onp.isfinite(gw1).all() and onp.abs(gw1).sum() > 0


def test_moe_expert_parallel_training_step():
    """EP over a 4-device 'expert' axis x 2-device dp, full SPMDTrainer step."""
    import jax
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import nn

    mesh = parallel.make_mesh({"data": 2, "expert": 4})
    rng = onp.random.RandomState(4)
    d = 8

    net = nn.HybridSequential()
    net.add(nn.Dense(d, in_units=d))
    net.add(moe.MoE(units=d, hidden_size=16, num_experts=8, k=2))
    net.initialize()
    parallel.shard_params(net, mesh, rules=moe.moe_sharding_rules("expert"))

    def loss_fn(out, label):
        return ((out - label) ** 2).mean()

    trainer = parallel.SPMDTrainer(net, loss_fn, opt.Adam(learning_rate=1e-3),
                                   mesh)
    x = nd.array(rng.randn(8, d).astype("float32"))
    y = nd.array(rng.randn(8, d).astype("float32"))
    l0 = float(trainer.step(x, y).asnumpy())
    for _ in range(5):
        l = float(trainer.step(x, y).asnumpy())
    assert onp.isfinite(l) and l < l0
    # expert weights really live sharded over the expert axis
    sh = net[1].expert_w1._nd._data.sharding
    assert "expert" in sh.spec


def test_moe_grouped_matches_ungrouped():
    """GShard token groups: with capacity ample enough that no group
    drops, grouped routing must produce exactly the ungrouped outputs
    (same experts, same gates — only the slot bookkeeping differs)."""
    rng = onp.random.RandomState(5)
    T, d, h, E, k = 32, 8, 16, 4, 2
    kw = dict(units=d, hidden_size=h, num_experts=E, k=k,
              capacity_factor=8.0)   # ample: no drops in any group
    mx.random.seed(7)
    ref = moe.MoE(**kw)
    ref.initialize()
    mx.random.seed(7)
    grp = moe.MoE(num_groups=4, **kw)
    grp.initialize()
    x = nd.array(rng.randn(T, d).astype("float32"))
    y_ref = ref(x).asnumpy()
    y_grp = grp(x).asnumpy()
    onp.testing.assert_allclose(y_grp, y_ref, rtol=2e-4, atol=2e-5)


def test_moe_groups_fall_back_when_indivisible():
    rng = onp.random.RandomState(6)
    T, d = 30, 8   # not divisible by 4 -> silently runs ungrouped
    layer = moe.MoE(units=d, hidden_size=16, num_experts=4, k=2,
                    num_groups=4)
    layer.initialize()
    y = layer(nd.array(rng.randn(T, d).astype("float32")))
    assert y.shape == (T, d)


def test_moe_capture_compatibility():
    """The MoE layer must trace cleanly under jax.jit capture (abstract
    tokens through gate/dispatch/combine — the same mechanism the fused
    SPMDTrainer step uses) and the captured program must reproduce the
    eager forward."""
    import jax
    from mxnet_tpu.ndarray.ndarray import NDArray, unwrap
    rng = onp.random.RandomState(7)
    T, d = 16, 8
    layer = moe.MoE(units=d, hidden_size=16, num_experts=4, k=2,
                    capacity_factor=2.0)
    layer.initialize()
    ps = list(layer._collect_params_with_prefix().values())
    x = rng.randn(T, d).astype("float32")
    eager = layer(nd.array(x)).asnumpy()

    def fn(x_raw, *param_raws):
        olds = [p._nd for p in ps]
        try:
            for p, r in zip(ps, param_raws):
                p._nd = NDArray(r)
            return unwrap(layer(NDArray(x_raw)))
        finally:
            for p, o in zip(ps, olds):
                p._nd = o

    jitted = jax.jit(fn)
    raws = [unwrap(p.data()) for p in ps]
    out = onp.asarray(jitted(x, *raws))
    onp.testing.assert_allclose(out, eager, rtol=1e-5, atol=1e-6)
    # fresh batch through the SAME capture (no retrace, no stale closure)
    x2 = rng.randn(T, d).astype("float32")
    out2 = onp.asarray(jitted(x2, *raws))
    onp.testing.assert_allclose(out2, layer(nd.array(x2)).asnumpy(),
                                rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_moe_expert_parallel_zero2_step():
    """Heavyweight composition check: EP sharding rules + zero2 sharded
    weight update in one captured step program."""
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import nn

    mesh = parallel.make_mesh({"data": 2, "expert": 4})
    rng = onp.random.RandomState(8)
    d = 8
    net = nn.HybridSequential()
    net.add(nn.Dense(d, in_units=d))
    net.add(moe.MoE(units=d, hidden_size=16, num_experts=8, k=2))
    net.initialize()
    parallel.shard_params(net, mesh, rules=moe.moe_sharding_rules("expert"))
    trainer = parallel.SPMDTrainer(
        net, lambda o, t: ((o - t) ** 2).mean(),
        opt.Adam(learning_rate=1e-3), mesh, zero2=True)
    x = nd.array(rng.randn(8, d).astype("float32"))
    y = nd.array(rng.randn(8, d).astype("float32"))
    l0 = float(trainer.step(x, y).asnumpy())
    for _ in range(5):
        l = float(trainer.step(x, y).asnumpy())
    assert onp.isfinite(l) and l < l0
    sh = net[1].expert_w1._nd._data.sharding
    assert "expert" in sh.spec


@pytest.mark.parametrize("holders", [[(0, 16), (16, 16), (32, 16), (48, 16)],
                                     [(0, 64)], [(0, 40), (40, 24)]])
def test_lfm2_routing_shares_add_up_to_the_whole_layer(holders):
    """LFM2's selection (one group, no shared expert, top-4 of 64 by score
    + bias, gates over ``sum + 1e-6``) through the layer DeepSeek shares:
    the parts the holders give add up to ``held=(0, 64)`` and to the plain
    reference's whole layer (``chipbench/reference/lfm2.py``)."""
    import os
    import sys
    import jax.numpy as jnp
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench.reference import lfm2 as ref
    mx.random.seed(9)
    d, f, E, k = 16, 8, 64, 4
    args = dict(route_scale=1, shared_experts=0, norm_eps=1e-6)
    whole = moe.DroplessMoE(d, f, E, k, held=(0, E), **args)
    whole.initialize()
    whole.select_bias.set_data(nd.array(
        0.05 * onp.random.RandomState(1).randn(E).astype("float32")))
    w = {n: p.data()._data for n, p in whole._reg_params.items()}
    x = jnp.asarray(onp.random.RandomState(3).randn(50, d), jnp.float32)
    want, idx, scores = whole.apply(x)
    total = 0.0
    for first, count in holders:
        part = moe.DroplessMoE(d, f, E, k, held=(first, count), **args)
        part.initialize()
        for name, p in part._reg_params.items():
            p.set_data(w[name][first:first + count]
                       if name.startswith("held_") else w[name])
        y, idx_r, _ = part.apply(x)
        assert (onp.asarray(idx_r) == onp.asarray(idx)).all()
        total = total + y
    assert onp.abs(onp.asarray(total - want)).max() < 1e-5
    cfg = {"num_experts_per_tok": k, "routed_scaling_factor": 1}
    y_ref, s_ref, idx_ref = ref.feed_forward(
        cfg, {"ffn." + n: v for n, v in w.items()}, x)
    assert onp.abs(onp.asarray(y_ref - want)).max() < 1e-5
    assert onp.abs(onp.asarray(s_ref - scores)).max() < 1e-6
    assert (onp.sort(onp.asarray(idx_ref), -1)
            == onp.sort(onp.asarray(idx), -1)).all()
    # the gates' guard: they sum to just under the scale
    chosen = onp.take_along_axis(onp.asarray(scores), onp.asarray(idx), -1)
    _i, gates = moe.noaux_route(scores, w["select_bias"], k, norm_eps=1e-6)
    assert onp.allclose(onp.asarray(gates).sum(-1),
                        chosen.sum(-1) / (chosen.sum(-1) + 1e-6), atol=1e-6)


@pytest.mark.parametrize("holders", [[(0, 32), (32, 32), (64, 32), (96, 32)],
                                     [(0, 128)]])
def test_keye_routing_shares_add_up_to_the_whole_layer(holders):
    """Keye's selection (a float32 softmax over all 128, the 8 largest
    probabilities, gates renormalised over the chosen, no bias, no shared
    expert) through the layer DeepSeek and LFM2 share, ``scoring="softmax"``:
    the parts the holders give add up to ``held=(0, 128)`` and to the plain
    reference's whole layer (``chipbench/reference/keye.py``)."""
    import os
    import sys
    import jax.numpy as jnp
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench.reference import keye as ref
    mx.random.seed(9)
    d, f, E, k = 16, 8, 128, 8
    args = dict(shared_experts=0, scoring="softmax", select_bias=False)
    whole = moe.DroplessMoE(d, f, E, k, held=(0, E), **args)
    whole.initialize()
    assert "select_bias" not in whole._reg_params
    w = {n: p.data()._data for n, p in whole._reg_params.items()}
    x = jnp.asarray(onp.random.RandomState(3).randn(50, d), jnp.float32)
    want, idx, scores = whole.apply(x)
    assert onp.allclose(onp.asarray(scores).sum(-1), 1.0, atol=1e-6)
    total = 0.0
    for first, count in holders:
        part = moe.DroplessMoE(d, f, E, k, held=(first, count), **args)
        part.initialize()
        for name, p in part._reg_params.items():
            p.set_data(w[name][first:first + count]
                       if name.startswith("held_") else w[name])
        y, idx_r, _ = part.apply(x)
        assert (onp.asarray(idx_r) == onp.asarray(idx)).all()
        total = total + y
    assert onp.abs(onp.asarray(total - want)).max() < 1e-5
    dims = ref.dims_of({
        "sa_config": {"indexer_num_kv_heads": 1, "indexer_num_heads": 1,
                      "indexer_head_dim": 1, "topk": 1},
        "rope_scaling": {"mrope_section": [1]}, "head_dim": 1,
        "num_attention_heads": 1, "num_key_value_heads": 1, "rope_theta": 1,
        "rms_norm_eps": 1e-6, "num_experts": E, "num_experts_per_tok": k})
    y_ref, s_ref, idx_ref = ref.feed_forward(
        dims, {"ffn." + n: v for n, v in w.items()}, x)
    assert onp.abs(onp.asarray(y_ref - want)).max() < 1e-5
    assert onp.abs(onp.asarray(s_ref - scores)).max() < 1e-6
    assert (onp.sort(onp.asarray(idx_ref), -1)
            == onp.sort(onp.asarray(idx), -1)).all()
    # the gates are the chosen probabilities renormalised
    _i, gates = moe.noaux_route(scores, jnp.zeros((E,)), k)
    assert onp.allclose(onp.asarray(gates).sum(-1), 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="neither sigmoid nor softmax"):
        moe.dropless_moe(x, w, k=k, first=0, scoring="tanh")
