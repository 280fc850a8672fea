"""``ops.grouped_product`` on the CPU: the kernel through Pallas'
interpreter against plain ``ragged_dot`` over the loads that matter, the
tile rules over the benchmark's shapes, ``dropless_experts`` and the three
models' decode steps with the kernel forced against the ``ragged_dot``
form, and the counter that says what the product multiplied.  The kernel
through the chip's compiler (no chip) is in ``test_latent_attention.py``,
beside the one fixture that describes a v5e."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import deepseek, keye, lfm2
from mxnet_tpu.models import tiny_keye, tiny_lfm2, tiny_v32
from mxnet_tpu.ops import grouped_product as gp
from mxnet_tpu.parallel import moe

TM = 8


def _ragged(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, jnp.asarray(sizes, jnp.int32),
                              preferred_element_type=jnp.float32)


# (pairs, count, sizes): what a decode step of each cell looks like, and
# the edges of the tiles
LOADS = {
    "eight_pairs_an_expert": (64, 8, [8] * 8),
    # DeepSeek's case: 8 of 64 pairs on held experts, the others sorted last
    "most_empty_unheld_last": (64, 16, [0, 2, 0, 0, 2, 0, 0, 0,
                                       2, 0, 0, 0, 0, 2, 0, 0]),
    "one_expert_above_a_tile": (64, 8, [18, 1, 0, 16, 0, 9, 0, 3]),
    "no_held_pair": (64, 8, [0] * 8),
    "group_ends_on_a_tile_edge": (64, 8, [16, 16, 0, 32, 0, 0, 0, 0]),
    "keye_320_over_128": (320, 128, None),
}


@pytest.mark.parametrize("tm", [8, 32])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_kernel_is_ragged_dot(load, tm):
    """bfloat16 operands, float32 results: the held rows within 1e-5 of
    ``ragged_dot``'s, at a row tile under the loads and one over them."""
    rs = onp.random.RandomState(0)
    pairs, count, sizes = LOADS[load]
    sizes = onp.asarray(rs.multinomial(pairs, [1.0 / count] * count)
                        if sizes is None else sizes, "int32")
    held = int(sizes.sum())
    k, n = 256, 128
    xs = jnp.asarray(rs.randn(pairs, k), jnp.bfloat16)
    w = jnp.asarray(rs.randn(count, k, n) * 0.05, jnp.bfloat16)
    want = onp.asarray(_ragged(xs, w, sizes))
    out = gp.grouped_product(xs, w, jnp.asarray(sizes), tm, interpret=True)
    assert out.dtype == jnp.float32 and out.shape == (pairs, n)
    if held:
        assert onp.abs(onp.asarray(out)[:held] - want[:held]).max() < 1e-5


def test_the_contraction_in_blocks_is_the_whole_one(monkeypatch):
    """Where VMEM does not hold an expert's whole matrix the contraction
    comes in blocks, accumulated in float32."""
    rs = onp.random.RandomState(3)
    sizes = jnp.asarray([5, 0, 11, 16], jnp.int32)
    xs = jnp.asarray(rs.randn(32, 512), jnp.bfloat16)
    w = jnp.asarray(rs.randn(4, 512, 256) * 0.05, jnp.bfloat16)
    want = gp.grouped_product(xs, w, sizes, 8, interpret=True)
    monkeypatch.setattr(gp, "_VMEM_BUDGET", 200 * 1024)
    assert gp.weight_block(8, 512, 256, 2) == (128, 256)
    got = gp.grouped_product(xs, w, sizes, 8, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(got - _ragged(xs, w, sizes)).max()) < 1e-5


# the shapes the benchmark runs: (pairs, held experts, d, hidden) -> rows a
# tile, and the weights' blocks of the two orientations
@pytest.mark.parametrize("pairs,count,d,hidden,tile,blocks", [
    # lfm2_24b.decode_rollout: 8 pairs an expert
    (512, 64, 2048, 1536, 32, [(2048, 1536), (1536, 2048)]),
    # deepseek_v32.decode_long: 32 of 512 held; an expert is 29 MB
    (512, 16, 7168, 2048, 32, [(896, 2048), (256, 7168)]),
    # keye_vl2.decode_doc: 2.5
    (320, 128, 2048, 768, 32, [(2048, 768), (768, 2048)]),
    # the prefills stay with XLA's own tiling: LFM2's at 768 and 1,024
    # (48 and 64 pairs an expert), DeepSeek's at 3,072 (1,536), Keye's at
    # 8,192 (512)
    (3072, 64, 2048, 1536, None, None),
    (4096, 64, 2048, 1536, None, None),
    (24576, 16, 7168, 2048, None, None),
    (65536, 128, 2048, 768, None, None),
])
def test_the_tiles_follow_the_load(pairs, count, d, hidden, tile, blocks):
    assert gp.row_tile(pairs, count) == tile
    if tile is None:
        return
    assert [gp.weight_block(tile, d, hidden, 2),
            gp.weight_block(tile, hidden, d, 2)] == blocks
    for (k, n), (tk, tn) in zip(((d, hidden), (hidden, d)), blocks):
        assert k % tk == 0 and tk % 128 == 0 and tn == n


def test_a_row_tile_divides_the_pairs_or_there_is_none():
    assert gp.row_tile(96, 16) == 32
    assert gp.row_tile(24, 16) == 8
    assert gp.row_tile(12, 16) is None
    assert gp.row_tile(512, 16) == 32 and gp.row_tile(544, 16) is None
    assert gp.xla_row_tile(512) == gp.xla_row_tile(1024) == 512
    assert [gp.xla_row_tile(n) for n in (520, 528, 576, 640, 320)] \
        == [8, 16, 64, 128, 64]


def test_rows_visited():
    """An expert multiplies every tile it has a row in."""
    ones = jnp.ones((64,), jnp.int32)
    # XLA's tile of 512: every expert visits all the pairs
    assert int(gp.rows_visited(ones * 8, 512)) == 64 * 512
    # 320 rows in tiles of 64: four experts have rows in two of them
    assert int(gp.rows_visited(ones * 5, 64)) == (64 + 4) * 64
    # 8 pairs an expert in tiles of 32: four experts a tile, none astride
    assert int(gp.rows_visited(ones * 8, 32)) == 64 * 32
    two = jnp.zeros((16,), jnp.int32).at[jnp.asarray([1, 4, 9, 12])].set(2)
    assert int(gp.rows_visited(two, 512)) == 4 * 512
    assert int(gp.rows_visited(two, 32)) == 4 * 32
    assert int(gp.rows_visited(jnp.zeros((16,), jnp.int32), 32)) == 0


def test_on_a_cpu_ragged_dot_runs():
    assert gp.kernel_tile(512, 64, 2048, 1536, "bfloat16") is None


# -- dropless_experts ---------------------------------------------------------
_interpreted = functools.partial(gp.grouped_product, interpret=True)


def _poisoned(xs, w, sizes, tm):
    """The kernel through the interpreter, the rows past the held pairs as
    a chip may leave them: whatever was there."""
    out = _interpreted(xs, w, sizes, tm)
    row = jnp.arange(out.shape[0])[:, None]
    return jnp.where(row < sizes.sum(), out, jnp.nan)


def _force_kernel(patch, tm=TM):
    """What a TPU decides from its backend is decided here by the test."""
    patch.setattr(gp, "kernel_tile", lambda *a: tm)
    patch.setattr(gp, "grouped_product", _poisoned)


def _visits(sizes, tm):
    end = onp.cumsum(sizes)
    return int(onp.where(sizes > 0,
                         (end - 1) // tm - (end - sizes) // tm + 1, 0).sum())


@pytest.mark.parametrize("first,count", [(0, 16), (4, 4), (12, 4)],
                         ids=["all_held", "a_quarter", "the_last_quarter"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dropless_experts_with_the_kernel_is_the_ragged_dot_form(
        monkeypatch, first, count, dtype):
    rs = onp.random.RandomState(1)
    T, k, E, d, hidden = 24, 4, 16, 32, 16
    x = jnp.asarray(rs.randn(T, d), dtype)
    idx = jnp.asarray(onp.stack([rs.permutation(E)[:k] for _ in range(T)]),
                      jnp.int32)
    gates = jnp.asarray(rs.rand(T, k), jnp.float32)
    w1, w3 = (jnp.asarray(rs.randn(count, d, hidden) * 0.2, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rs.randn(count, hidden, d) * 0.2, dtype)
    want = moe.dropless_experts(x, idx, gates, w1, w3, w2, first)
    rows_want = moe.rows_computed(idx, first, w1)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        got = moe.dropless_experts(x, idx, gates, w1, w3, w2, first)
        rows_got = moe.rows_computed(idx, first, w1)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5
    local = onp.asarray(idx).reshape(-1) - first
    sizes = onp.bincount(local[(local >= 0) & (local < count)],
                         minlength=count)
    assert int(rows_got) == _visits(sizes, TM) * TM
    # 96 pairs: XLA's tile is 32
    assert int(rows_want) == _visits(sizes, 32) * 32


def test_nothing_held_adds_nothing(monkeypatch):
    rs = onp.random.RandomState(2)
    x = jnp.asarray(rs.randn(8, 32), jnp.float32)
    idx = jnp.asarray(rs.randint(0, 8, (8, 2)), jnp.int32)
    gates = jnp.ones((8, 2), jnp.float32)
    w1, w3 = (jnp.asarray(rs.randn(4, 32, 16), jnp.float32) for _ in "13")
    w2 = jnp.asarray(rs.randn(4, 16, 32), jnp.float32)
    _force_kernel(monkeypatch)
    got = moe.dropless_experts(x, idx, gates, w1, w3, w2, first=8)
    assert float(jnp.abs(got).max()) == 0.0
    assert int(moe.rows_computed(idx, 8, w1)) == 0


# -- the three models' decode steps -------------------------------------------
MODELS = {
    "tiny_v32": (deepseek, lambda: tiny_v32(held=(4, 8))),
    "tiny_lfm2": (lfm2, tiny_lfm2),
    "tiny_keye": (keye, tiny_keye),
}


def _caches(net, slots, ring_len, seed=1):
    rs = onp.random.RandomState(seed)
    return [tuple(jnp.asarray(rs.randn(slots, *shape) * 0.5, jnp.float32)
                  for _kind, shape, _dt in layer)
            for layer in net.cache_spec(ring_len)]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_decode_step_with_the_kernel_is_the_ragged_dot_form(monkeypatch,
                                                              name):
    """Logits, caches and counts of one step over four slots, one of
    them sitting out; ``expert_rows_computed`` says which form ran."""
    module, build = MODELS[name]
    mx.random.seed(3)
    net = build()
    net.initialize()
    c, w = net.config, net.raw_weights()
    caches = _caches(net, 4, 32)
    args = (jnp.asarray([5, 6, 7, 8], jnp.int32), caches,
            jnp.asarray([3, 17, 30, 9], jnp.int32),
            jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32))
    want = module.decode(c, w, *args, want_selections=True)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        got = module.decode(c, w, *args, want_selections=True)
    assert float(jnp.abs(got[0] - want[0]).max()) < 2e-5
    for layer_got, layer_want in zip(got[1], want[1]):
        for a, b in zip(layer_got, layer_want):
            assert float(jnp.abs(a - b).max()) < 2e-5
    names = [n for n, _help in module.STEP_COUNTERS]
    assert len(got[2]) == len(want[2]) == len(names)
    got_n, want_n = (dict(zip(names, onp.asarray(x[2]))) for x in (got, want))
    rows_got, rows_want = (n.pop("expert_rows_computed")
                           for n in (got_n, want_n))
    assert got_n == want_n
    first, count = c.held
    at_tm, at_xla = 0, 0
    for idx in want[3]["experts"]:
        local = onp.asarray(idx).reshape(-1) - first
        sizes = onp.bincount(local[(local >= 0) & (local < count)],
                             minlength=count)
        at_tm += _visits(sizes, TM) * TM
        at_xla += _visits(sizes, 16) * 16       # 16 pairs: XLA's tile is 16
    assert rows_got == at_tm
    assert rows_want == at_xla


# -- the dispatch's probe ------------------------------------------------------
def test_a_probe_that_compiled_is_not_paid_again_by_the_next_process(
        tmp_path, monkeypatch):
    """A success is kept beside the compile cache, by toolchain and chip;
    a refusal is probed, and warned of, in every process."""
    import importlib
    from mxnet_tpu import compile as mx_compile
    fa = importlib.import_module("mxnet_tpu.ops.flash_attention")
    monkeypatch.setattr(mx_compile, "cache_root", lambda: str(tmp_path))
    monkeypatch.setattr(mx_compile, "persistent_cache_enabled", lambda: True)
    calls = []

    def fine():
        calls.append("fine")

    def refused():
        calls.append("refused")
        raise ValueError("no")

    def restart():
        monkeypatch.setattr(fa, "_KERNEL_PROBES", {})
        monkeypatch.setattr(fa, "_PROBE_MEMO", fa._ProbeMemo())
    restart()
    assert fa.probe_compile("k", (1, "bfloat16"), fine)
    with pytest.warns(UserWarning, match="refused by the compiler"):
        assert not fa.probe_compile("k", (2, "bfloat16"), refused)
    assert fa.probe_compile("k", (1, "bfloat16"), fine)
    assert calls == ["fine", "refused"]
    restart()
    assert fa.probe_compile("k", (1, "bfloat16"), fine)
    with pytest.warns(UserWarning):
        assert not fa.probe_compile("k", (2, "bfloat16"), refused)
    assert calls == ["fine", "refused", "refused"]
    assert [r["compiled"] for r in fa.kernel_report()] == [True, False]
    # another toolchain's verdicts are not this one's
    restart()
    monkeypatch.setattr(mx_compile, "version_stamp", lambda: {"jax": "next"})
    assert fa.probe_compile("k", (1, "bfloat16"), fine)
    assert calls[-1] == "fine"
    # and with the compile cache off nothing is kept
    restart()
    monkeypatch.setattr(mx_compile, "persistent_cache_enabled", lambda: False)
    assert fa.probe_compile("k", (3, "bfloat16"), fine)
    restart()
    assert fa.probe_compile("k", (3, "bfloat16"), fine)
    assert calls[-2:] == ["fine", "fine"]
