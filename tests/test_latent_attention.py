"""``ops.latent_ring_attention`` on the CPU: the kernel through Pallas'
interpreter against the plain masked softmax, ``deepseek.decode`` with the
kernel forced against ``decode`` in the XLA form, the counter that says
which form ran, and the kernels of the decode steps at the benchmark's
widths through the chip's compiler (no chip)."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import deepseek, tiny_v32
from mxnet_tpu.ops import latent_ring_attention as lra

COUNTERS = [name for name, _help in deepseek.STEP_COUNTERS]
S, H, KVR, R, M, STRIDE, BLOCK = 4, 4, 16, 4, 32, 128, 8


def _case(n_valid, n_selected, q_dtype, ring_dtype, seed=0):
    """Queries, a ring whose rows are (c_kv, k_rope, zeros), and a mask of
    ``n_selected`` positions (all, where fewer are valid) drawn among each
    slot's first ``n_valid``."""
    rs = onp.random.RandomState(seed)
    q_abs = jnp.asarray(rs.randn(S, H, KVR), q_dtype)
    q_rope = jnp.asarray(rs.randn(S, H, R), q_dtype)
    ring = rs.randn(S, M, STRIDE).astype("float32")
    ring[..., KVR + R:] = 0
    mask = onp.zeros((S, M), bool)
    for s, nv in enumerate(n_valid):
        mask[s, rs.permutation(nv)[:n_selected]] = True
    return (q_abs, q_rope, jnp.asarray(ring, ring_dtype), jnp.asarray(mask),
            jnp.asarray(n_valid, jnp.int32))


def _kernel(q_abs, q_rope, ring, mask, n_valid, scale=0.3):
    return lra.latent_ring_attention(q_abs, q_rope, ring, mask, n_valid,
                                     scale, block=BLOCK, interpret=True)


def _diff(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# bfloat16: outputs reach 2 to 4, where one step of the type is 2**-6; the
# XLA form itself reads one to two steps from float32 there
TOLERANCE = {"float32": 2e-5, "bfloat16": 2 ** -5}


@pytest.mark.parametrize("q_dtype,ring_dtype", [
    ("float32", "float32"), ("bfloat16", "bfloat16"),
    ("float32", "bfloat16")])
@pytest.mark.parametrize("n_valid,n_selected", [
    ([8, 16, 24, 32], 6),       # on block edges, the last a wrapped ring
    ([1, 7, 9, 31], 6),         # off them, one slot with a single row
    ([32, 32, 32, 32], 32),     # every row of a wrapped ring selected
    ([5, 12, 20, 27], 5),       # the selection equal to a valid prefix
    ([1, 1, 1, 1], 6),          # nothing but the row just written
], ids=["on_edge", "off_edge", "wrapped_all", "equal_to_valid", "one_row"])
def test_kernel_is_the_masked_softmax(n_valid, n_selected, q_dtype,
                                      ring_dtype):
    q_abs, q_rope, ring, mask, nv = _case(n_valid, n_selected, q_dtype,
                                          ring_dtype)
    got = _kernel(q_abs, q_rope, ring, mask, nv)
    assert got.dtype == q_abs.dtype and got.shape == (S, H, KVR)
    want = lra.latent_ring_attention_ref(q_abs, q_rope, ring, mask, 0.3)
    assert _diff(got, want) < TOLERANCE[q_dtype]
    if q_dtype == "bfloat16":
        # and no further from float32 than the XLA form is
        exact = lra.latent_ring_attention_ref(
            *(a.astype(jnp.float32) for a in (q_abs, q_rope, ring)), mask,
            0.3)
        assert _diff(got, exact) < TOLERANCE[q_dtype]
        assert _diff(want, exact) < TOLERANCE[q_dtype]


def test_blocks_past_n_valid_are_neither_fetched_nor_computed():
    n_valid = [1, 8, 13, 24]
    q_abs, q_rope, ring, mask, nv = _case(n_valid, 6, "float32", "float32")
    want = _kernel(q_abs, q_rope, ring, mask, nv)
    poisoned = onp.asarray(ring).copy()
    for s, n in enumerate(n_valid):
        poisoned[s, int(lra.rows_visited(n, BLOCK)):] = onp.nan
    got = _kernel(q_abs, q_rope, jnp.asarray(poisoned), mask, nv)
    assert onp.isfinite(onp.asarray(got)).all()
    assert (onp.asarray(got) == onp.asarray(want)).all()
    assert [int(lra.rows_visited(n, BLOCK)) for n in n_valid] \
        == [8, 8, 16, 24]


def test_a_slot_with_nothing_selected_gets_zeros():
    q_abs, q_rope, ring, mask, nv = _case([4, 9, 17, 32], 6, "float32",
                                          "float32")
    mask = mask.at[2].set(False)
    got = onp.asarray(_kernel(q_abs, q_rope, ring, mask, nv))
    assert (got[2] == 0).all() and onp.isfinite(got).all()
    assert onp.abs(got[[0, 1, 3]]).min() > 0


@pytest.mark.parametrize("ring_len,block", [
    (6144, 1024), (5632, 512), (768, 256), (384, 128), (32, None),
    (6100, None)])
def test_pick_block(ring_len, block):
    assert lra.pick_block(ring_len) == block


def test_no_kernel_on_a_cpu_or_for_a_ring_no_block_divides():
    assert lra.kernel_block(64, 128, 512, 64, 6144, 640, "bfloat16",
                            "bfloat16") is None       # the CPU
    with pytest.raises(ValueError, match="no block"):
        lra.latent_ring_attention(*_case([8] * 4, 6, "float32", "float32"),
                                  0.3)                 # 32 rows


# -- decode -------------------------------------------------------------------
def _net(seed=3):
    mx.random.seed(seed)
    net = tiny_v32(config={"index_n_heads": 16})
    net.initialize()
    return net


def _rings(net, slots, ring_len, seed=1, dtype="float32"):
    rs = onp.random.RandomState(seed)
    out = []
    for layer in net.cache_spec(ring_len):
        rings = []
        for kind, shape, _dt in layer:
            a = rs.randn(slots, *shape).astype("float32") * 0.5
            if kind == "latent":
                a[..., net.config.kv_lora_rank
                  + net.config.qk_rope_head_dim:] = 0
            rings.append(jnp.asarray(a, dtype))
        out.append(tuple(rings))
    return out


def _force_kernel(patch):
    """``decode`` takes the kernel, through the interpreter, at blocks of
    8: what a TPU decides from its backend is decided here by the test."""
    patch.setattr(lra, "kernel_block", lambda *a: BLOCK)
    patch.setattr(lra, "latent_ring_attention", functools.partial(
        lra.latent_ring_attention, interpret=True))


@pytest.fixture
def forced_kernel(monkeypatch):
    _force_kernel(monkeypatch)


def _decode(net, tok, rings, pos, active=None):
    return deepseek.decode(
        net.config, net.raw_weights(), jnp.asarray(tok, jnp.int32), rings,
        jnp.asarray(pos, jnp.int32),
        None if active is None else jnp.asarray(active, jnp.float32),
        want_selections=True)


@pytest.mark.parametrize("ring_len", [32, 128], ids=["wrapped", "lanes"])
def test_decode_with_the_kernel_is_decode_in_the_xla_form(monkeypatch,
                                                          ring_len):
    net = _net()
    rings = _rings(net, 3, ring_len)
    # 40 has wrapped a ring of 32; a ring of 128 is whole lanes
    tok, pos = [5, 6, 7], [3, 17, 40]
    want = _decode(net, tok, rings, pos)
    with monkeypatch.context() as patch:
        _force_kernel(patch)
        got = _decode(net, tok, rings, pos)
    assert _diff(got[0], want[0]) < 2e-5
    # the first layer's rows are written before any attention: the same
    # bits; a later layer's follow an input that differs in the last ones
    for a, b in zip(got[1][0], want[1][0]):
        assert (onp.asarray(a) == onp.asarray(b)).all()
    for layer_got, layer_want in zip(got[1], want[1]):
        for a, b in zip(layer_got, layer_want):
            assert _diff(a, b) < 2e-5
    for key in ("positions", "experts"):
        for a, b in zip(got[3][key], want[3][key]):
            assert (onp.asarray(a) == onp.asarray(b)).all()
    for key in ("index_scores", "router_scores"):
        for a, b in zip(got[3][key], want[3][key]):
            assert _diff(jnp.where(jnp.isfinite(a), a, 0),
                         jnp.where(jnp.isfinite(b), b, 0)) < 2e-5
    got_n, want_n = (dict(zip(COUNTERS, onp.asarray(c))) for c in
                     (got[2], want[2]))
    assert len(got[2]) == len(want[2]) == len(deepseek.STEP_COUNTERS) == 9
    for name in COUNTERS:
        if name != "latent_rows_read":
            assert got_n[name] == want_n[name]
    layers, K = 3, net.config.index_topk
    # n_valid 4, 18 and 32 or 41: the gather reads min(n_valid, K) rows a
    # slot, the kernel the whole blocks of 8 that hold the valid rows
    assert want_n["latent_rows_read"] == layers * (4 + K + K) \
        == want_n["index_selected_positions"]
    assert got_n["latent_rows_read"] == layers * (
        8 + 24 + {32: 32, 128: 48}[ring_len])


def test_the_kernel_reads_a_ring_stored_in_another_type(forced_kernel):
    net = _net()
    rings = _rings(net, 2, 16, dtype="bfloat16")
    logits, new, _counts, _sel = _decode(net, [5, 6], rings, [9, 30])
    assert logits.dtype == jnp.float32
    assert all(r.dtype == jnp.bfloat16 for layer in new for r in layer)
    as_f32 = [tuple(r.astype(jnp.float32) for r in layer) for layer in rings]
    want = _decode(net, [5, 6], as_f32, [9, 30])[0]
    # the row a step writes is rounded to the ring's type in one and not
    # in the other: one bfloat16 row among the valid ones
    assert _diff(logits, want) < 2e-2


def test_an_inactive_slot_with_the_kernel(forced_kernel):
    net = _net()
    rings = _rings(net, 2, 16)
    logits, new, counts, _sel = _decode(net, [5, 6], rings, [3, 4],
                                        active=[0.0, 1.0])
    assert onp.isfinite(onp.asarray(logits)).all()
    for (old_l, old_i), (new_l, new_i) in zip(rings, new):
        assert (onp.asarray(new_l)[0] == onp.asarray(old_l)[0]).all()
        assert (onp.asarray(new_i)[0] == onp.asarray(old_i)[0]).all()
        assert not (onp.asarray(new_l)[1, 4] == onp.asarray(old_l)[1, 4]).all()
    counts = dict(zip(COUNTERS, onp.asarray(counts)))
    assert counts["index_valid_positions"] == 3 * 5
    assert counts["latent_rows_read"] == 3 * 8
    assert counts["routed_pairs"] == 2 * 4


# -- the chip's compiler, no chip ---------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_a_v5e_at_the_benchmarks_widths(one_chip):
    """64 slots x 6,144 x 640 bfloat16, 128 heads: Mosaic takes the
    kernel, the ring goes in as it lies (no copy of it in the program) and
    nothing ring-sized comes out."""
    from jax.experimental.compilation_cache import compilation_cache
    slots, heads, ring_len = 64, 128, 6144

    def shape(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)
    # a program compiled for a described chip cannot be read back from
    # the persistent cache: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(functools.partial(
            lra.latent_ring_attention, scale=0.1)).lower(
            shape(slots, heads, 512), shape(slots, heads, 64),
            shape(slots, ring_len, 640), shape(slots, ring_len, dt=bool),
            shape(slots, dt=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{slots},{ring_len},640]" in text
    ring_bytes = slots * ring_len * 640 * 2
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < ring_bytes // 8
    assert stats.output_size_in_bytes == slots * heads * 512 * 2


# a decode step's attention of the two cells with grouped rings: (slots,
# ring positions, whether a selection masks them); 32 heads over rows of 512
@pytest.mark.parametrize("slots,ring_len,masked", [
    (128, 5120, False), (40, 12288, True)], ids=["lfm2", "keye"])
def test_the_grouped_ring_kernel_compiles_for_a_v5e_at_the_cells_widths(
        one_chip, slots, ring_len, masked):
    """Mosaic takes the kernel at the block the dispatch picks, both rings
    go in as they lie (no copy of either in the program) and nothing
    ring-sized comes out.  (Here and not in
    ``test_grouped_ring_attention.py``: one file describes the chip.)"""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops import grouped_ring_attention as gra
    heads, width = 32, 512

    def shape(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)
    ring = shape(slots, ring_len, width)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(functools.partial(
            gra.grouped_ring_attention, scale=0.1)).lower(
            shape(slots, heads, width), ring, ring,
            shape(slots, dt=jnp.int32),
            **({"mask": shape(slots, ring_len, dt=bool)} if masked else {})
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{slots},{ring_len},{width}]" in text
    ring_bytes = slots * ring_len * width * 2
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < ring_bytes // 8
    assert stats.output_size_in_bytes == slots * heads * width * 4


# one query block of a prefill in the two sparse cells: (key heads, query
# heads a key head, query block, bucket, key width, value width)
@pytest.mark.parametrize("kv,groups,bq,length,dk,dv", [
    (128, 1, 256, 2816, 192, 128), (4, 8, 512, 8192, 128, 128)],
    ids=["deepseek_v32", "keye"])
def test_the_sparse_prefill_kernel_compiles_for_a_v5e_at_the_cells_widths(
        one_chip, kv, groups, bq, length, dk, dv):
    """Mosaic takes the kernel at the block the dispatch picks, inside the
    VMEM it asks for, and no score reaches memory: the program holds
    nothing near the XLA form's float32 scores of the block (369 MB in
    DeepSeek), and what comes out is the heads' outputs side by side.
    (Here and not in ``test_sparse_prefill_attention.py``: one file
    describes the chip.)"""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops import sparse_prefill_attention as spa
    block = spa.pick_block(length)

    def shape(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(functools.partial(
            spa.sparse_prefill_attention, scale=0.1, block_k=block)).lower(
            shape(1, kv, groups * bq, dk), shape(1, kv, length, dk),
            shape(1, kv, length, dv), shape(1, bq, length, dt=bool),
            shape(dt=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"f32[1,{kv},{groups * bq},{length}]" not in text
    stats = compiled.memory_analysis()
    # at most the keys laid out for the kernel (a width of 192 on whole
    # lanes) and the selection as a bias
    lanes = -(-dk // 128) * 128
    assert stats.temp_size_in_bytes <= (kv * length * lanes * 2
                                        + bq * length * 4)
    assert stats.output_size_in_bytes == bq * kv * groups * dv * 4


# a decode step of the three MoE cells: (pairs, held experts, d, hidden)
@pytest.mark.parametrize("pairs,count,d,hidden", [
    (512, 64, 2048, 1536), (512, 16, 7168, 2048), (320, 128, 2048, 768)],
    ids=["lfm2", "deepseek_v32", "keye"])
def test_the_grouped_product_compiles_for_a_v5e_at_the_cells_shapes(
        one_chip, pairs, count, d, hidden):
    """Both orientations of an expert layer's products at the tiles the
    rules give: Mosaic takes the kernel inside the default VMEM, the stack
    goes in as it lies and the program has no temporary the size of it.
    (Here and not in ``test_grouped_product.py``: one file describes the
    chip.)"""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops import grouped_product as gp
    tm = gp.row_tile(pairs, count)

    def shape(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for k, n in ((d, hidden), (hidden, d)):
            compiled = jax.jit(functools.partial(
                gp.grouped_product, tm=tm)).lower(
                shape(pairs, k), shape(count, k, n),
                shape(count, dt=jnp.int32)).compile()
            text = compiled.as_text()
            assert "tpu_custom_call" in text
            assert "ragged_dot_tiling" not in text     # not XLA's own
            assert f"bf16[{count},{k},{n}]" in text
            stats = compiled.memory_analysis()
            assert stats.temp_size_in_bytes < count * k * n * 2 // 8
            assert stats.output_size_in_bytes == pairs * n * 4
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_the_delta_rule_kernel_compiles_for_a_v5e_at_solars_widths(one_chip):
    """A decode step's delta rule of Solar's cell, 128 slots x 64 heads of
    128 x 128 float32 states, at the block of heads the dispatch picks:
    Mosaic takes the kernel, the donated state is its output (aliased, no
    copy of it in the program) and the program keeps no state-sized
    temporary.  (Here and not in ``test_delta_rule_kernel.py``: one file
    describes the chip.)"""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops import delta_rule_step as drs
    slots, heads, width = 128, 64, 128
    state = f"f32[{slots},{heads},{width},{width}]"

    def shape(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)
    row = shape(slots, heads, width)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(functools.partial(
            drs.delta_rule_step, heads=drs.pick_heads(heads)),
            donate_argnums=(5,)).lower(
            row, row, row, row, shape(slots, heads),
            shape(slots, heads, width, width),
            shape(slots, dt=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert state in text
    assert not [line for line in text.splitlines()
                if f"{state}" in line and " copy(" in line]
    state_bytes = slots * heads * width * width * 4
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == state_bytes
    assert stats.temp_size_in_bytes < state_bytes // 64
    # the state and o, and the outputs' tuple table
    o_bytes = slots * heads * width * 4
    assert 0 <= stats.output_size_in_bytes - state_bytes - o_bytes <= 4096


# the indexer's selection of the two sparse cells: (rows, ring positions
# or a prefill's columns) of a decode step and of a prefill's query block
@pytest.mark.parametrize("R,N", [
    (64, 6144), (40, 12288), (256, 2816), (512, 3072), (512, 7168),
    (512, 8192)], ids=["dsv32_decode", "keye_decode", "dsv32_prefill_2816",
                       "dsv32_prefill_3072", "keye_prefill_7168",
                       "keye_prefill_8192"])
def test_the_selection_kernel_compiles_for_a_v5e_at_the_cells_shapes(
        one_chip, R, N):
    """Mosaic takes ``topk_select`` at the rows and chunk the dispatch
    picks, in the VMEM it asks for: keys in, the mask and a flag a row out,
    and no sort and no temporary of the keys' size in the program.  (Here
    and not in ``test_topk_select.py``: one file describes the chip.)"""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.ops import topk_select as tks
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(functools.partial(
            tks.topk_select, k=2048, rows=tks.pick_rows(R, N))).lower(
            jax.ShapeDtypeStruct((R, N), jnp.int32,
                                 sharding=one_chip)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " sort(" not in text
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes <= R * N * 4
    assert stats.output_size_in_bytes <= R * N + R + 1024
