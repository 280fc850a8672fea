"""``ops.delta_rule_step`` on the CPU: the kernel through Pallas'
interpreter against the XLA form that ``parts.delta_rule_step`` runs where
no kernel does and against the rule in float64 NumPy, an inactive slot's
state kept bit for bit, the state aliased in place, and the dispatch that
chooses between the two forms.  (The kernel through the chip's compiler at
Solar's widths is in ``test_latent_attention.py``: one file describes the
chip.)"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.models import parts
from mxnet_tpu.ops import delta_rule_step as drs

# the module: ``mxnet_tpu.ops`` exports a function of the same name
fa = sys.modules["mxnet_tpu.ops.flash_attention"]


def _force_kernel(patch, heads):
    """``parts.delta_rule_step`` takes the kernel, through the interpreter,
    at ``heads`` a block: what a TPU decides from its backend is decided
    here by the test."""
    patch.setattr(drs, "kernel_heads", lambda *a: heads)
    patch.setattr(drs, "delta_rule_step", functools.partial(
        drs.delta_rule_step, interpret=True))


def _case(S, H, K, V, seed=0, a_max=16.0, beta=None, inactive=()):
    """Inputs as a KDA layer makes them: q and k L2-normed (q scaled), a
    log decay ``-A softplus(.)`` with ``A`` up to ``a_max`` a head, beta in
    (0, 2) (or ``beta`` everywhere), a drawn state, and ``act`` 0 at the
    slots ``inactive``."""
    rs = onp.random.RandomState(seed)

    def unit(a):
        return a / onp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = unit(rs.randn(S, H, K)) * K ** -0.5
    k = unit(rs.randn(S, H, K))
    v = rs.randn(S, H, V)
    A = rs.uniform(1.0, a_max, (H, 1))
    g = -A * onp.log1p(onp.exp(rs.randn(S, H, K)))
    b = 2 / (1 + onp.exp(-rs.randn(S, H))) if beta is None \
        else onp.full((S, H), beta)
    state = rs.randn(S, H, K, V) * 0.5
    act = onp.ones(S, "int32")
    act[list(inactive)] = 0
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, b, state)) \
        + (jnp.asarray(act),)


def _plain(q, k, v, g, beta, state, act):
    """The rule a slot and a head in float64 loops: ``S' = (I - beta k
    k^T) Diag(exp g) S + beta k v^T`` and ``o = S'^T q``, read off the new
    state; an inactive slot's state stays."""
    q, k, v, g, beta, state = (onp.asarray(a, "float64")
                               for a in (q, k, v, g, beta, state))
    S, H = q.shape[:2]
    o = onp.zeros(v.shape)
    new = state.copy()
    for s in range(S):
        for h in range(H):
            s1 = onp.exp(g[s, h])[:, None] * state[s, h]
            u = beta[s, h] * (v[s, h] - s1.T @ k[s, h])
            s2 = s1 + onp.outer(k[s, h], u)
            o[s, h] = s2.T @ q[s, h]
            if act[s]:
                new[s, h] = s2
    return o, new


def _kernel(*args, heads):
    return drs.delta_rule_step(*args, heads=heads, interpret=True)


def _diff(a, b):
    return float(onp.abs(onp.asarray(a, "float64")
                         - onp.asarray(b, "float64")).max())


# (slots, heads, K, V, heads a block): one block and several, square and
# not, the tiny model's widths and the published head's
SHAPES = [(3, 4, 8, 8, 4), (2, 8, 16, 16, 2), (4, 16, 8, 24, 8),
          (1, 2, 128, 128, 2)]


@pytest.mark.parametrize("S,H,K,V,heads", SHAPES,
                         ids=["one_block", "four_blocks", "wide_v",
                              "published_head"])
@pytest.mark.parametrize("regime", ["drawn", "steep", "beta_near_0",
                                    "beta_near_2"])
def test_kernel_is_the_xla_form_and_the_rule(S, H, K, V, heads, regime):
    """``o`` and ``S'`` of the kernel against the XLA form (the same float32
    arithmetic summed in another order: a few ulps) and against the rule in
    float64 (the float32 rounding of the products over ``K``), with decays
    down to ``A`` = 16 a step, and beta at the edges of (0, 2)."""
    kw = {"drawn": {"a_max": 2.0}, "steep": {"a_max": 16.0, "seed": 5},
          "beta_near_0": {"a_max": 2.0, "beta": 1e-4},
          "beta_near_2": {"a_max": 2.0, "beta": 1.9999}}
    args = _case(S, H, K, V, **kw[regime])
    o, new = _kernel(*args, heads=heads)
    want_o, want_s, passes = parts.delta_rule_step(*args)
    assert passes == 3                      # no kernel on a CPU
    assert o.dtype == new.dtype == jnp.float32
    assert o.shape == (S, H, V) and new.shape == (S, H, K, V)
    assert _diff(o, want_o) < 2e-6
    assert _diff(new, want_s) < 2e-6
    plain_o, plain_s = _plain(*args)
    assert _diff(o, plain_o) < 2e-5
    assert _diff(new, plain_s) < 2e-5


@pytest.mark.parametrize("inactive", [(0,), (1, 3), (0, 1, 2, 3)],
                         ids=["first", "two", "all"])
def test_an_inactive_slot_keeps_its_state_bit_for_bit(inactive):
    """A slot with ``act`` 0 gets back the very bits it had; the others
    move on as the XLA form moves them; ``o`` is every slot's, as the XLA
    form computes it."""
    args = _case(4, 8, 16, 16, seed=2, inactive=inactive)
    o, new = _kernel(*args, heads=4)
    state = onp.asarray(args[5])
    want_o, want_s, _passes = parts.delta_rule_step(*args)
    for s in range(4):
        if s in inactive:
            assert (onp.asarray(new[s]) == state[s]).all()
        else:
            assert not (onp.asarray(new[s]) == state[s]).all()
    assert _diff(new, want_s) < 2e-6
    assert _diff(o, want_o) < 2e-6


def test_steps_chained_are_the_rule_chained():
    """Five steps, each on the last one's state, one slot sitting out
    every other step: the kernel keeps to the rule, step after step."""
    args = list(_case(3, 4, 8, 8, seed=7))
    state = want = args[5]
    for t in range(5):
        step = _case(3, 4, 8, 8, seed=10 + t, inactive=(t % 2,))
        o, state = _kernel(*step[:5], state, step[6], heads=2)
        plain_o, want = _plain(*step[:5], want, step[6])
        assert _diff(o, plain_o) < 2e-5
        assert _diff(state, want) < 2e-5


def test_the_state_is_aliased_in_place():
    """One pallas_call whose state operand is its second output: no second
    state-sized buffer comes out of the kernel.  (What the chip's compiler
    makes of it is in ``test_latent_attention.py``.)"""
    args = _case(2, 4, 8, 8)
    fn = functools.partial(_kernel, heads=2)
    calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    (call,) = calls
    assert tuple(call.params["input_output_aliases"]) == ((6, 1),)
    assert [tuple(v.aval.shape) for v in call.outvars] == [(2, 4, 8),
                                                           (2, 4, 8, 8)]
    # operand 6 is the state: act comes first, ahead of the grid
    assert tuple(call.invars[6].aval.shape) == (2, 4, 8, 8)


def test_parts_takes_the_kernel_where_it_is_chosen(monkeypatch):
    """``parts.delta_rule_step`` with the kernel chosen: the kernel's
    numbers, two passes over the state where the XLA form counts three."""
    args = _case(3, 4, 8, 8, seed=3, inactive=(1,))
    want_o, want_s, passes = parts.delta_rule_step(*args)
    assert passes == 3
    with monkeypatch.context() as patch:
        _force_kernel(patch, 2)
        o, new, passes = parts.delta_rule_step(*args)
    assert passes == 2
    assert _diff(o, want_o) < 2e-6 and _diff(new, want_s) < 2e-6
    assert (onp.asarray(new[1]) == onp.asarray(args[5][1])).all()


def test_parts_keeps_a_state_of_another_type_in_its_type():
    """A bfloat16 state (a control's, never the configuration's) runs the
    XLA form, in float32 inside, and comes back in bfloat16; an inactive
    slot keeps its bits."""
    args = list(_case(2, 4, 8, 8, seed=4, inactive=(0,)))
    args[5] = args[5].astype(jnp.bfloat16)
    o, new, passes = parts.delta_rule_step(*args)
    assert passes == 3 and new.dtype == jnp.bfloat16 and o.dtype == jnp.float32
    assert (onp.asarray(new[0]) == onp.asarray(args[5][0])).all()


@pytest.mark.parametrize("heads,block", [(64, 16), (32, 16), (24, 8),
                                         (8, 8), (12, None), (4, None)])
def test_pick_heads(heads, block):
    assert drs.pick_heads(heads) == block


def test_no_kernel_on_a_cpu_for_another_type_or_a_ragged_head_count(
        monkeypatch):
    """The dispatch answers None on a CPU; where it may dispatch, for a
    state that is not float32 and for a head count no block divides."""
    assert drs.kernel_heads(128, 64, 128, 128, jnp.float32) is None
    monkeypatch.setattr(fa, "kernel_dispatch_allowed", lambda: True)
    assert drs.kernel_heads(128, 64, 128, 128, jnp.bfloat16) is None
    assert drs.kernel_heads(128, 12, 128, 128, jnp.float32) is None


def test_a_block_that_does_not_divide_the_heads_is_refused():
    with pytest.raises(ValueError, match="no block of heads"):
        drs.delta_rule_step(*_case(1, 12, 8, 8), heads=8, interpret=True)
