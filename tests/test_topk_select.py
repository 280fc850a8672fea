"""``ops.topk_select`` on the CPU: the indexer's selection of the ``k``
largest scores as a mask, by bisection over the bits of order-preserving
keys, against ``lax.top_k``'s set where no score ties at the k-th, against
its own rules where scores do (exactly ``min(k, n_valid)`` kept, none
below a dropped one, ties kept lowest position first), the Pallas kernel
through the interpreter against the ``jnp`` form bit for bit, and the
models' ``index_tie_rows`` counter."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import deepseek, keye, parts, tiny_keye, tiny_v32
from mxnet_tpu.ops import topk_select as tks

# (rows, columns, k, validity) at tiny sizes: the decode steps' rings
# (slots x ring positions, each slot's valid prefix) and the prefills'
# blocks of queries (a causal prefix a row)
SHAPES = {
    "dsv32_decode": (6, 48, 20, "ring"),
    "keye_decode": (5, 96, 24, "ring"),
    "decode_lanes": (8, 256, 64, "ring"),
    "dsv32_prefill": (16, 64, 24, "causal"),
    "keye_prefill": (32, 128, 40, "causal"),
}


def _valid(R, N, k, kind, seed=0):
    """Validity [R, N] as a prefix a row: for a ring, rows with nothing,
    fewer than k, exactly k, one more and all of N valid, the rest drawn;
    for a prefill block, the causal prefix of queries ``N - R .. N - 1``."""
    if kind == "causal":
        n = N - R + onp.arange(R) + 1
    else:
        n = onp.random.RandomState(seed).randint(0, N + 1, R)
        n[:5] = [0, k - 1, k, k + 1, N]
    return onp.arange(N)[None, :] < n[:, None], n


def _scores(R, N, kind, seed=0):
    rs = onp.random.RandomState(seed + 1)
    s = rs.randn(R, N).astype("float32")
    if kind == "tied":
        s = onp.round(s * 2) / 2          # a few distinct values: ties
    return s


def _select(scores, valid, k, form):
    key = tks.keys(jnp.asarray(scores), jnp.asarray(valid))
    if form == "kernel":
        mask, tie = tks.topk_select(key, k, rows=tks.pick_rows(*key.shape),
                                    interpret=True)
    else:
        mask, tie = tks.topk_select_xla(key, k)
    return onp.asarray(mask), onp.asarray(tie)


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_tie_free_scores_give_top_ks_set(shape, form):
    R, N, k, kind = SHAPES[shape]
    valid, n = _valid(R, N, k, kind)
    scores = _scores(R, N, "random")
    mask, tie = _select(scores, valid, k, form)
    vals, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    for r in range(R):
        want = onp.zeros(N, bool)
        want[onp.asarray(idx[r])[onp.asarray(vals[r]) > -onp.inf]] = True
        assert (mask[r] == want).all(), r
    assert (mask.sum(1) == onp.minimum(k, n)).all()
    assert not mask[~valid].any()
    assert not tie.any()


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_tied_scores_keep_exactly_k_and_the_lowest_positions(shape, form):
    R, N, k, kind = SHAPES[shape]
    valid, n = _valid(R, N, k, kind)
    scores = _scores(R, N, "tied")
    mask, tie = _select(scores, valid, k, form)
    assert (mask.sum(1) == onp.minimum(k, n)).all()
    assert not mask[~valid].any()
    for r in range(R):
        if n[r] <= k:
            assert (mask[r] == valid[r]).all() and not tie[r]
            continue
        kept, dropped = scores[r][mask[r]], scores[r][valid[r] & ~mask[r]]
        assert kept.min() >= dropped.max()
        kth = onp.sort(scores[r][valid[r]])[::-1][k - 1]
        at_kth = onp.nonzero(valid[r] & (scores[r] == kth))[0]
        # the ties straddle the cut exactly where more reach the k-th
        # score than k
        assert tie[r] == ((scores[r][valid[r]] >= kth).sum() > k)
        chosen = at_kth[mask[r][at_kth]]
        assert (chosen == at_kth[:len(chosen)]).all()
    assert tie.any()


REGIMES = ["random", "tied", "all_equal", "none_valid", "all_valid"]


@pytest.mark.parametrize("regime", REGIMES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_jnp_form_bit_for_bit(shape, regime):
    """Several blocks of rows and several chunks a pass, so that the
    kernel's early settling, its column limit and its positional passes
    all run where the jnp form runs all 32 passes over whole rows."""
    R, N, k, kind = SHAPES[shape]
    valid, _n = _valid(R, N, k, kind, seed=3)
    scores = _scores(R, N, "tied" if regime == "tied" else "random", seed=3)
    if regime == "all_equal":
        scores = onp.full_like(scores, 0.25)
    elif regime == "none_valid":
        valid = onp.zeros_like(valid)
    elif regime == "all_valid":
        valid = onp.ones_like(valid)
    key = tks.keys(jnp.asarray(scores), jnp.asarray(valid))
    want = tks.topk_select_xla(key, k)
    rows = 8 if R % 8 == 0 else R
    chunk = 16 if N % 16 == 0 else N
    got = tks.topk_select(key, k, rows=rows, chunk=chunk, interpret=True)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.bool_
        assert (onp.asarray(a) == onp.asarray(b)).all()


def test_keys_order_as_the_scores_and_signed_zeros_are_equal():
    x = onp.array([-onp.inf, -3e38, -1.5, -1e-45, -0.0, 0.0, 1e-45, 2.0,
                   3e38, onp.inf], "float32")
    key = onp.asarray(tks.keys(jnp.asarray(x), jnp.ones(x.shape, bool)))
    assert key.dtype == onp.int32
    assert (onp.diff(key[[0, 1, 2, 3, 4, 6, 7, 8, 9]]) > 0).all()
    assert key[4] == key[5] == 0
    assert key.min() > tks.INVALID
    invalid = onp.asarray(tks.keys(jnp.asarray(x), jnp.zeros(x.shape, bool)))
    assert (invalid == tks.INVALID).all()


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_signed_zeros_tie_and_infinite_scores_count(form):
    """-0.0 and +0.0 are one score, kept lowest position first; -inf is a
    valid score below every other, +inf above; an invalid position is
    never kept, whatever its score."""
    s = onp.array([[1.0, -0.0, 0.0, -onp.inf, onp.inf, -0.0, 3.0, -2.0],
                   [-onp.inf] * 6 + [5.0, 5.0]], "float32")
    valid = onp.ones(s.shape, bool)
    valid[1, 7] = False
    mask, tie = _select(s, valid, 5, form)
    # row 0: +inf, 3, 1, then two of the three zeros: positions 1 and 2
    assert onp.nonzero(mask[0])[0].tolist() == [0, 1, 2, 4, 6]
    # row 1: 5 and four of the six valid -inf, the lowest
    assert onp.nonzero(mask[1])[0].tolist() == [0, 1, 2, 3, 6]
    assert tie.tolist() == [True, True]


@pytest.mark.parametrize("R,N,rows", [
    (64, 6144, 64), (40, 12288, 40), (256, 2816, 128), (512, 3072, 128),
    (512, 8192, 64), (6, 48, 6), (12, 2 ** 20, None)])
def test_pick_rows(R, N, rows):
    assert tks.pick_rows(R, N) == rows
    if rows is not None:
        assert R % rows == 0 and (rows % 8 == 0 or rows == R)


@pytest.mark.parametrize("rows,N,chunk", [
    (64, 6144, 256), (40, 12288, 256), (128, 2816, 128), (64, 8192, 256),
    (8, 8192, 512), (6, 48, 48)])
def test_pick_chunk(rows, N, chunk):
    assert tks.pick_chunk(rows, N) == chunk


def test_no_kernel_on_a_cpu():
    assert tks.kernel_rows(64, 6144, 2048) is None
    with pytest.raises(ValueError, match="no block of rows"):
        tks.topk_select(jnp.zeros((12, 2 ** 20), jnp.int32), 4)


def test_topk_mask_keeps_the_leading_axes_and_everything_under_k():
    rs = onp.random.RandomState(5)
    scores = jnp.asarray(rs.randn(2, 3, 40).astype("float32"))
    valid = jnp.asarray(onp.arange(40)[None, None] < rs.randint(
        0, 41, (2, 3, 1)))
    mask, tie = parts.topk_mask(scores, valid, 10)
    assert mask.shape == (2, 3, 40) and tie.shape == (2, 3)
    flat, _ = tks.topk_select_xla(
        tks.keys(scores, valid).reshape(6, 40), 10)
    assert (onp.asarray(mask).reshape(6, 40) == onp.asarray(flat)).all()
    whole, none = parts.topk_mask(scores, valid, 40)
    assert whole is valid and not onp.asarray(none).any()


def test_mask_positions_lists_the_kept_positions_in_order():
    mask = onp.zeros((3, 12), bool)
    mask[0, [1, 4, 7]] = True
    mask[2, :] = True
    chosen, keep = parts.mask_positions(jnp.asarray(mask), 5)
    assert onp.asarray(keep).tolist() == [
        [True] * 3 + [False] * 2, [False] * 5, [True] * 5]
    assert onp.asarray(chosen)[0, :3].tolist() == [1, 4, 7]
    assert onp.asarray(chosen)[2].tolist() == [0, 1, 2, 3, 4]


# -- the models' counter ------------------------------------------------------
def _model(name):
    mx.random.seed(3)
    net = {"dsv32": functools.partial(tiny_v32,
                                      config={"index_n_heads": 16}),
           "keye": tiny_keye}[name]()
    net.initialize()
    return net, {"dsv32": deepseek, "keye": keye}[name]


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("name", ["dsv32", "keye"])
def test_index_tie_rows_counts_the_rows_that_ties_decided(name, tied):
    """Rings of random rows give distinct index scores: no row is decided
    by position.  An indexer ring whose rows are all one key gives every
    cached position one score: every riding slot past ``index_topk`` in
    every layer is, and a slot that sits out counts nothing."""
    net, module = _model(name)
    slots, M, layers = 3, 32, net.config.num_hidden_layers
    rs = onp.random.RandomState(1)
    caches = []
    for layer in net.cache_spec(M):
        rings = []
        for kind, shape, _dt in layer:
            a = rs.randn(slots, *shape).astype("float32") * 0.5
            if kind == "indexer" and tied:
                a[:] = a[0, 0]
            rings.append(jnp.asarray(a))
        caches.append(tuple(rings))
    names = [n for n, _help in module.STEP_COUNTERS]
    out = module.decode(net.config, net.raw_weights(),
                        jnp.asarray([5, 6, 7], jnp.int32), caches,
                        jnp.asarray([20, 25, 30], jnp.int32),
                        jnp.asarray([1.0, 1.0, 0.0], jnp.float32))
    counts = dict(zip(names, onp.asarray(out[2])))
    assert names[-1] == "index_tie_rows"
    assert counts["index_tie_rows"] == (2 * layers if tied else 0)
    assert counts["index_selected_positions"] \
        == 2 * layers * net.config.index_topk
