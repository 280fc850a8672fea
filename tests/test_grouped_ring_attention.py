"""``ops.grouped_ring_attention`` on the CPU: the kernel through Pallas'
interpreter against the XLA form that ``parts.grouped_ring_attend`` runs
where no kernel does, ``lfm2.decode`` and ``keye.decode`` with the kernel
forced against the same in the XLA form, and the counter that says which
form ran.  (The kernel through the chip's compiler at the cells' widths is
in ``test_latent_attention.py``: one file describes the chip.)"""
import functools

import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import keye, lfm2, parts, tiny_keye, tiny_lfm2
from mxnet_tpu.ops import grouped_ring_attention as gra

S, G, M, BLOCK = 4, 2, 32, 8


def _force_kernel(patch):
    """``grouped_ring_attend`` takes the kernel, through the interpreter,
    at blocks of 8: what a TPU decides from its backend is decided here by
    the test."""
    patch.setattr(gra, "kernel_block", lambda *a: BLOCK)
    patch.setattr(gra, "grouped_ring_attention", functools.partial(
        gra.grouped_ring_attention, interpret=True))


@pytest.fixture
def forced_kernel(monkeypatch):
    _force_kernel(monkeypatch)


def _case(n_valid, kv, d, q_dtype, ring_dtype, n_selected=None, seed=0):
    """A query [S, H, D], two rings [S, M, KV * D] and, with
    ``n_selected``, a mask of that many positions (all, where fewer are
    valid) drawn among each slot's first ``n_valid``."""
    rs = onp.random.RandomState(seed)
    q = jnp.asarray(rs.randn(S, kv * G, d), q_dtype)
    ring_k = jnp.asarray(rs.randn(S, M, kv * d), ring_dtype)
    ring_v = jnp.asarray(rs.randn(S, M, kv * d), ring_dtype)
    mask = None
    if n_selected is not None:
        mask = onp.zeros((S, M), bool)
        for s, nv in enumerate(n_valid):
            mask[s, rs.permutation(nv)[:n_selected]] = True
        mask = jnp.asarray(mask)
    return q, ring_k, ring_v, jnp.asarray(n_valid, jnp.int32), mask


def _both_forms(monkeypatch, *args):
    """``(kernel, XLA form)``, each ``(out, rows read)``."""
    want = parts.grouped_ring_attend(*args)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        return parts.grouped_ring_attend(*args), want


def _diff(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def _plain(q, ring_k, ring_v, n_valid, mask):
    """The softmax a head, a slot, in float64 loops: what both forms are."""
    q, ring_k, ring_v = (onp.asarray(a.astype(jnp.float32), "float64")
                         for a in (q, ring_k, ring_v))
    heads, d = q.shape[1:]
    out = onp.zeros((S, heads, d))
    for s in range(S):
        keep = onp.arange(M) < int(n_valid[s]) if mask is None \
            else onp.asarray(mask[s])
        for h in range(heads):
            at = slice(h // G * d, (h // G + 1) * d)
            sc = ring_k[s][keep][:, at] @ q[s, h] * d ** -0.5
            p = onp.exp(sc - sc.max()) if keep.any() else sc
            out[s, h] = (p / max(p.sum(), 1e-30)) @ ring_v[s][keep][:, at]
    return out.reshape(S, heads * d)


# float32 queries: the kernel's blocks add up in another order than the
# einsum; bfloat16: the probabilities are rounded before the sum a block
# and after the division in the XLA form, a step of 2**-8 of values near 1
TOLERANCE = {"float32": 2e-5, "bfloat16": 2 ** -6}


@pytest.mark.parametrize("kv,d", [(8, 64), (4, 128)], ids=["8x64", "4x128"])
@pytest.mark.parametrize("q_dtype,ring_dtype", [
    ("float32", "float32"), ("bfloat16", "bfloat16"),
    ("bfloat16", "float8_e4m3fn")], ids=["f32", "bf16", "fp8_ring"])
@pytest.mark.parametrize("n_selected", [None, 6], ids=["valid", "mask"])
@pytest.mark.parametrize("n_valid", [
    [8, 16, 24, 32],        # on block edges, the last the whole ring
    [5, 12, 20, 27],        # inside a block
    [1, 1, 1, 1],           # nothing but the row just written
    [32, 32, 32, 32],       # the whole ring, every block
], ids=["on_edge", "inside", "one_row", "whole_ring"])
def test_kernel_is_the_masked_softmax_of_the_xla_form(
        monkeypatch, n_valid, n_selected, q_dtype, ring_dtype, kv, d):
    args = _case(n_valid, kv, d, q_dtype, ring_dtype, n_selected)
    (got, got_rows), (want, want_rows) = _both_forms(monkeypatch, *args)
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == want.shape == (S, kv * G * d)
    assert _diff(got, want) < TOLERANCE[q_dtype]
    # and no further from the plain softmax than the XLA form is
    exact = _plain(*args)
    assert onp.abs(onp.asarray(got) - exact).max() < TOLERANCE[q_dtype]
    assert onp.abs(onp.asarray(want) - exact).max() < TOLERANCE[q_dtype]
    assert onp.asarray(want_rows).tolist() == [M] * S
    assert onp.asarray(got_rows).tolist() \
        == [-(-n // BLOCK) * BLOCK for n in n_valid]


@pytest.mark.parametrize("n_selected", [None, 6], ids=["valid", "mask"])
def test_blocks_past_n_valid_are_neither_fetched_nor_computed(
        forced_kernel, n_selected):
    n_valid = [1, 8, 13, 24]
    q, ring_k, ring_v, nv, mask = _case(n_valid, 2, 16, "float32", "float32",
                                        n_selected)
    want, rows = parts.grouped_ring_attend(q, ring_k, ring_v, nv, mask)
    assert onp.asarray(rows).tolist() == [8, 8, 16, 24]
    poisoned = []
    for ring in (ring_k, ring_v):
        ring = onp.asarray(ring).copy()
        for s, n in enumerate(onp.asarray(rows)):
            ring[s, n:] = onp.nan
        poisoned.append(jnp.asarray(ring))
    got, _rows = parts.grouped_ring_attend(q, *poisoned, nv, mask)
    assert onp.isfinite(onp.asarray(got)).all()
    assert (onp.asarray(got) == onp.asarray(want)).all()


def test_a_slot_whose_mask_keeps_nothing_gets_zeros(forced_kernel):
    q, ring_k, ring_v, nv, mask = _case([4, 9, 17, 32], 2, 16, "float32",
                                        "float32", 6)
    mask = mask.at[2].set(False)
    got = onp.asarray(parts.grouped_ring_attend(q, ring_k, ring_v, nv,
                                                mask)[0])
    assert (got[2] == 0).all() and onp.isfinite(got).all()
    assert onp.abs(got[[0, 1, 3]]).min() > 0


@pytest.mark.parametrize("ring_len,block", [
    (5120, 512), (12288, 512), (768, 256), (384, 128), (32, None),
    (5100, None)])
def test_pick_block(ring_len, block):
    assert gra.pick_block(ring_len) == block


@pytest.mark.parametrize("masked", [False, True], ids=["valid", "mask"])
def test_no_kernel_on_a_cpu_or_for_a_ring_no_block_divides(masked):
    assert gra.kernel_block(128, 32, 512, 5120, "bfloat16", "bfloat16",
                            masked) is None             # the CPU
    q, ring_k, ring_v, nv, mask = _case([8] * 4, 2, 16, "float32",
                                        "float32", 6 if masked else None)
    wide = jnp.zeros((S, 2 * G, 32), jnp.float32)
    with pytest.raises(ValueError, match="no block"):
        gra.grouped_ring_attention(wide, ring_k, ring_v, nv, 0.3, mask)


# -- decode -------------------------------------------------------------------
def _rings(net, slots, ring_len, dtype="float32", seed=1):
    rs = onp.random.RandomState(seed)
    return [tuple(jnp.asarray(rs.randn(slots, *shape) * 0.5, dtype)
                  for _kind, shape, _dt in layer)
            for layer in net.cache_spec(ring_len)]


def _decode(module, net, tok, rings, pos, active=None):
    return module.decode(
        net.config, net.raw_weights(), jnp.asarray(tok, jnp.int32), rings,
        jnp.asarray(pos, jnp.int32),
        None if active is None else jnp.asarray(active, jnp.float32),
        want_selections=True)


def _net(tiny, seed=3, **kw):
    mx.random.seed(seed)
    net = tiny(**kw)
    net.initialize()
    return net


def _same_step(got, want, skip):
    """Logits, caches and selections of two decode steps agree, and every
    counter but ``skip``; returns both steps' counters by name."""
    assert _diff(got[0], want[0]) < 2e-5
    for layer_got, layer_want in zip(got[1], want[1]):
        for a, b in zip(layer_got, layer_want):
            assert _diff(a, b) < 2e-5
    for key in got[3]:
        for a, b in zip(got[3][key], want[3][key]):
            if "scores" in key:
                assert _diff(jnp.where(jnp.isfinite(a), a, 0),
                             jnp.where(jnp.isfinite(b), b, 0)) < 2e-5
            else:
                assert (onp.asarray(a) == onp.asarray(b)).all()
    assert len(got[2]) == len(want[2])
    return [{k: v for k, v in enumerate(onp.asarray(c).tolist())
             if k != skip} for c in (got[2], want[2])]


@pytest.mark.parametrize("active", [None, [1.0, 0.0, 1.0]],
                         ids=["all_ride", "one_sits_out"])
def test_lfm2_decode_with_the_kernel_is_decode_in_the_xla_form(
        monkeypatch, active):
    names = [name for name, _help in lfm2.STEP_COUNTERS]
    assert names[5] == "kv_rows_read" and len(names) == 6
    net = _net(tiny_lfm2)
    rings = _rings(net, 3, 32)
    tok, pos = [5, 6, 7], [3, 17, 40]       # 40 has wrapped a ring of 32
    want = _decode(lfm2, net, tok, rings, pos, active)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        got = _decode(lfm2, net, tok, rings, pos, active)
    got_n, want_n = _same_step(got, want, skip=5)
    assert got_n == want_n
    riders = [0, 1, 2] if active is None else [0, 2]
    # two attention layers; n_valid 4, 18, 32: blocks of 8 up to them
    assert int(want[2][5]) == 2 * 32 * len(riders)
    assert int(got[2][5]) == 2 * sum([8, 24, 32][s] for s in riders)
    if active is not None:
        for (old_k, old_v), (new_k, new_v) in (
                (rings[i], got[1][i]) for i in (1, 4)):
            assert (onp.asarray(new_k)[1] == onp.asarray(old_k)[1]).all()
            assert (onp.asarray(new_v)[1] == onp.asarray(old_v)[1]).all()
            assert not (onp.asarray(new_k)[0, 3]
                        == onp.asarray(old_k)[0, 3]).all()


@pytest.mark.parametrize("active", [None, [1.0, 0.0, 1.0]],
                         ids=["all_ride", "one_sits_out"])
def test_keye_decode_with_the_kernel_is_decode_in_the_xla_form(
        monkeypatch, active):
    names = [name for name, _help in keye.STEP_COUNTERS]
    at = names.index("kv_rows_read")
    net = _net(tiny_keye)
    rings = _rings(net, 3, 32)
    tok, pos = [5, 6, 7], [3, 17, 40]   # 4 valid: fewer than top-k's 8
    want = _decode(keye, net, tok, rings, pos, active)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        got = _decode(keye, net, tok, rings, pos, active)
    got_n, want_n = _same_step(got, want, skip=at)
    assert got_n == want_n
    riders = [0, 1, 2] if active is None else [0, 2]
    assert int(want[2][at]) == 3 * 32 * len(riders)     # three layers
    assert int(got[2][at]) == 3 * sum([8, 24, 32][s] for s in riders)


@pytest.mark.parametrize("module,tiny", [(lfm2, tiny_lfm2),
                                         (keye, tiny_keye)],
                         ids=["lfm2", "keye"])
def test_the_kernel_reads_rings_stored_in_another_type(forced_kernel, module,
                                                       tiny):
    net = _net(tiny)
    rings = _rings(net, 2, 16, dtype="bfloat16")
    logits, new, _counts, _sel = _decode(module, net, [5, 6], rings, [9, 30])
    assert logits.dtype == jnp.float32
    assert all(r.dtype == jnp.bfloat16 for layer in new for r in layer)
    as_f32 = [tuple(r.astype(jnp.float32) for r in layer) for layer in rings]
    want = _decode(module, net, [5, 6], as_f32, [9, 30])[0]
    # the rows a step writes are rounded to the rings' type in one and not
    # in the other
    assert _diff(logits, want) < 2e-2
