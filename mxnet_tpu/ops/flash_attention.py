"""Flash attention: O(L) memory fused attention (SURVEY.md §5.7).

The reference materializes O(L²) score matrices
(``_contrib_interleaved_matmul_selfatt_*``), capping BERT at seq 512.  Here:

- ``_scan_attention``: blockwise online-softmax attention in pure jax
  (``lax.scan`` over KV blocks) — differentiable, O(L·B_k) memory, runs on
  any backend.  This is also the backward path.
- ``_pallas_fwd``: TPU Pallas kernel for the forward — one grid cell per
  (batch·head, q-block), KV streamed through VMEM, accumulation in fp32.
- ``flash_attention``: custom_vjp wrapper that picks the Pallas kernel on
  TPU and the scan path elsewhere; backward uses the scan math by default
  (recompute-based, standard FA2 formulation — measured fastest on v5e),
  with optional Pallas dq/dkv kernels via ``MXNET_ATTN_PALLAS_BWD=1``.

Layout: (B, H, L, D).  ``flash_attention_nd`` is the NDArray-facing op.
"""
from __future__ import annotations

import functools
import json
import os

from ..base import MXNetError

# v5e-tuned: a 256-row q block amortizes KV streaming across twice the
# queries (measured ~20% faster fwd+bwd than 128x128 at BERT-base shapes);
# k stays 128 so the (bq, bk) score tile fits VMEM comfortably at any D.
_BLOCK_Q = 256
_BLOCK_K = 128


def _seed_arr(key):
    """Fold a jax PRNG key into a (1,) int32 seed for the in-kernel TPU
    PRNG (pltpu.prng_seed).  Per-(batch,head) decorrelation happens inside
    the kernels (seed * 1000003 + bh)."""
    import jax
    import jax.numpy as jnp
    if jnp.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    kd = key.ravel()
    if kd.shape[0] >= 2:
        return (kd[:1] ^ kd[1:2]).astype(jnp.int32)
    return kd[:1].astype(jnp.int32)


# exp2 base-folding: the VPU's native exponential is 2^x — XLA lowers
# exp(x) to exp2(x * log2e), one extra vmul per score element.  The Pallas
# kernels fold log2e into the qk scale instead (scores live in the base-2
# domain in-kernel); the STORED lse stays base-e so the (out, lse) contract
# with every consumer (scan path, ring attention) is unchanged.
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453


def _kernel_dropout_mult(dropout, sd_ref, bh, shape):
    """Regenerable in-kernel attention-prob dropout multiplier: seed the
    per-core PRNG from (step seed, batch*head), draw uint32 bits for the
    score tile, and return the {0, 1/(1-rate)} matrix.  Forward and
    backward call this with identical (seed, bh, shape), so the mask
    reproduces exactly without ever materializing in HBM."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    pltpu.prng_seed(sd_ref[0] * jnp.int32(1000003) + bh)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    thresh = jnp.uint32(min(2 ** 32 - 1, int(dropout * (2.0 ** 32))))
    return jnp.where(bits >= thresh,
                     jnp.full(shape, 1.0 / (1.0 - dropout), jnp.float32),
                     jnp.zeros(shape, jnp.float32))


# ONNX-export mode: force every model dispatch onto the dense decomposed
# attention path (plain dot_general/softmax primitives) so the traced
# jaxpr contains no pallas custom calls.  Set via onnx.export_model.
_FORCE_DENSE = False


def kernel_dispatch_allowed():
    """Shared gate for every fused-kernel dispatcher: False in ONNX-export
    mode (pallas has no ONNX lowering), on CPU (kernels are TPU-only),
    and under a >1-device SPMD mesh (pjit cannot auto-partition pallas
    custom calls; the dense/layer paths shard fine)."""
    import jax
    if _FORCE_DENSE or jax.default_backend() == "cpu":
        return False
    from ..parallel import active_mesh_size
    return active_mesh_size() <= 1


# Compile probes.  Pallas errors surface at compile time, after tracing,
# where a try/except around the traced call cannot see them — so every
# fused-kernel dispatcher compiles its kernel variant once per signature
# before choosing it.  A refusal makes the dispatcher take the XLA path,
# and is KEPT: kernel, signature and the compiler's message, warned once
# and readable through kernel_report() (chip_smoke.py fails on one).
_KERNEL_PROBES = {}     # (kernel, signature) -> None | refusal message


class _ProbeMemo:
    """The probes that compiled, kept beside the persistent compile cache
    (``kernel_probes.json`` in its root) so that a restart on the same
    toolchain and chip does not trace, lower and load them again: a probe
    is a second or two of a warm engine's start, the kernel's first import
    included.  Refusals are not kept: they are probed, and warned of, in
    every process.  Off where the compile cache is off."""

    def __init__(self):
        self._path = self._stamp = self._known = None

    def _load(self):
        from .. import compile as _compile
        if not _compile.persistent_cache_enabled():
            return False
        path = os.path.join(_compile.cache_root(), "kernel_probes.json")
        if path != self._path:
            import jax
            dev = jax.devices()[0]
            self._stamp = repr((sorted(_compile.version_stamp().items()),
                                dev.device_kind,
                                dev.client.platform_version))
            self._path, self._known = path, set()
            try:
                with open(path) as f:
                    self._known = set(json.load(f).get(self._stamp, ()))
            except (OSError, ValueError):
                pass
        return True

    def has(self, key):
        return self._load() and repr(key) in self._known

    def add(self, key):
        if not self._load():
            return
        self._known.add(repr(key))
        try:                    # best effort, as the cache itself is
            try:
                with open(self._path) as f:
                    every = json.load(f)
            except (OSError, ValueError):
                every = {}
            every[self._stamp] = sorted(
                self._known | set(every.get(self._stamp, ())))
            tmp = f"{self._path}.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(every, f)
            os.replace(tmp, self._path)
        except OSError:
            pass


_PROBE_MEMO = _ProbeMemo()


def probe_compile(kernel, signature, compile_fn):
    """True when ``compile_fn()`` (a ``jit(...).lower(...).compile()``
    of one kernel variant) succeeds; memoized per (kernel, signature) in
    the process, and a success beside the compile cache across them."""
    key = (kernel, signature)
    if key not in _KERNEL_PROBES:
        if _PROBE_MEMO.has(key):
            _KERNEL_PROBES[key] = None
            return True
        try:
            compile_fn()
            _KERNEL_PROBES[key] = None
            _PROBE_MEMO.add(key)
        except Exception as e:      # noqa: BLE001 — Mosaic, XLA and jax
            # lowering each raise their own types; all mean "refused"
            msg = f"{type(e).__name__}: {e}"
            _KERNEL_PROBES[key] = msg
            import warnings
            warnings.warn(f"Pallas kernel {kernel}{signature} refused by "
                          f"the compiler; dispatching the XLA path "
                          f"instead: {msg[:2000]}")
    return _KERNEL_PROBES[key] is None


def kernel_report():
    """Every compile probe this process ran:
    ``[{"kernel", "signature", "compiled", "message"}]``."""
    return [{"kernel": k, "signature": repr(sig), "compiled": msg is None,
             "message": msg}
            for (k, sig), msg in _KERNEL_PROBES.items()]


class force_dense_export:
    """Context manager: dispatchers pick the dense/unfused paths."""

    def __enter__(self):
        global _FORCE_DENSE
        self._saved = _FORCE_DENSE
        _FORCE_DENSE = True
        return self

    def __exit__(self, *exc):
        global _FORCE_DENSE
        _FORCE_DENSE = self._saved
        return False


def _use_pallas(q, k, v):
    if not kernel_dispatch_allowed():
        return False
    # q and k/v may differ in sequence length (cross-attention) and in
    # head count (GQA: fewer k/v heads, q heads a multiple — handled by
    # grouped grid cells in the whole-L kernels)
    if not (k.shape == v.shape and q.shape[0] == k.shape[0]
            and q.shape[1] % k.shape[1] == 0 and q.shape[3] == k.shape[3]):
        return False
    B, H, L, D = q.shape
    Lk = k.shape[2]
    # ragged lengths are padded up to the 128 tile by the dispatcher
    return L >= 8 and Lk >= 8 and D % 8 == 0


def _pad_len(L):
    return (L + _BLOCK_K - 1) // _BLOCK_K * _BLOCK_K


def _pad_attn(q, k, v, out=None, do=None, lse=None, valid_length=None):
    """Zero-pad ragged sequence lengths up to the 128 tile for the Pallas
    kernels; padded KEYS are masked via an (implicit) valid_length, padded
    QUERY rows produce don't-care outputs that the caller slices off (and
    contribute exactly zero to dk/dv in the backward because the padded
    ``do`` rows are zero)."""
    import jax.numpy as jnp
    Lq, Lk = q.shape[2], k.shape[2]
    Lqp, Lkp = _pad_len(Lq), _pad_len(Lk)

    def padq(x):
        return x if x is None or Lqp == Lq else \
            jnp.pad(x, ((0, 0), (0, 0), (0, Lqp - Lq), (0, 0)))

    def padk(x):
        return x if x is None or Lkp == Lk else \
            jnp.pad(x, ((0, 0), (0, 0), (0, Lkp - Lk), (0, 0)))

    vl = valid_length
    if Lkp != Lk and vl is None:
        vl = jnp.full((q.shape[0],), Lk, jnp.int32)
    lse_p = lse
    if lse is not None and Lqp != Lq:
        lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, Lqp - Lq)))
    return (padq(q), padk(k), padk(v), padq(out), padq(do), lse_p, vl, Lq)


def _pick_bq(L):
    """Largest q-block that tiles L exactly (guard ensures L % 128 == 0)."""
    return _BLOCK_Q if L % _BLOCK_Q == 0 else _BLOCK_K


# ---------------------------------------------------------------------------
# scan (reference/backward) implementation
# ---------------------------------------------------------------------------
def _scan_attention(q, k, v, causal, scale, valid_length=None,
                    block_k=_BLOCK_K, dropout=0.0, key=None):
    """Blockwise attention with online softmax; returns (out, lse).

    ``valid_length``: optional (B,) int — keys at positions >= valid_length
    are masked out per batch row (the reference's length-mask semantics,
    python/mxnet gluon attention cells), kept O(L·B_k) here instead of a
    materialized (B, L, L) mask."""
    import jax
    import jax.numpy as jnp

    B, H0, Lq0, D = q.shape
    Hkv = k.shape[1]
    gq = H0 // Hkv
    if gq > 1:
        # GQA: heads in a group share kv, so FOLD the group into the
        # query-length axis instead of repeating k/v (which would
        # materialize H/Hkv x the kv bytes — the opposite of GQA's point).
        # Heads are grouped consecutively (h = hkv*gq + g), matching the
        # whole-L kernels' grouped-cell convention.
        q = q.reshape(B, Hkv, gq * Lq0, D)
    H, Lq = Hkv, gq * Lq0
    Lk = k.shape[2]
    bk = min(block_k, Lk)
    nk = (Lk + bk - 1) // bk
    pad = nk * bk - Lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, nk, bk, D)
    vb = v.reshape(B, H, nk, bk, D)
    # dots run in the storage dtype with fp32 accumulation (bf16 MXU
    # passes are 4x the fp32 rate); softmax math stays fp32
    mm_dtype = q.dtype

    # folded rows keep their ORIGINAL query position for causal masking
    qpos = jnp.tile(jnp.arange(Lq0), gq)

    def body(carry, blk):
        o_acc, m_acc, l_acc = carry
        k_j, v_j, j = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_j,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * bk + jnp.arange(bk)
        valid = kpos < Lk
        if causal:
            mask = valid[None, :] & (qpos[:, None] >= kpos[None, :])
        else:
            mask = jnp.broadcast_to(valid[None, :], (Lq, bk))
        s = jnp.where(mask[None, None], s, -1e30)
        if valid_length is not None:
            vmask = kpos[None, :] < valid_length.astype(jnp.int32)[:, None]
            s = jnp.where(vmask[:, None, None, :], s, -1e30)
        m_b = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_acc, m_b)
        p = jnp.exp(s - m_new[..., None])
        l_b = jnp.sum(p, axis=-1)
        alpha = jnp.exp(m_acc - m_new)
        if dropout > 0.0 and key is not None:
            # dropout multiplies the normalized probs; l stays undropped,
            # so masking the unnormalized p before the PV product is exact
            keep = jax.random.bernoulli(jax.random.fold_in(key, j),
                                        1.0 - dropout, s.shape)
            p_pv = jnp.where(keep, p * (1.0 / (1.0 - dropout)), 0.0)
        else:
            p_pv = p
        o_b = jnp.einsum("bhqk,bhkd->bhqd", p_pv.astype(mm_dtype), v_j,
                         preferred_element_type=jnp.float32)
        o_new = o_acc * alpha[..., None] + o_b
        return (o_new, m_new, l_b + l_acc * alpha), None

    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0),
         jnp.arange(nk)))
    l = jnp.maximum(l, 1e-30)
    out = (o / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)
    if gq > 1:
        out = out.reshape(B, H0, Lq0, D)
        lse = lse.reshape(B, H0, Lq0)
    return out, lse


# ---------------------------------------------------------------------------
# whole-L pallas kernels (L <= _WHOLE_L_MAX)
#
# At BERT-ish lengths the entire (L, L) fp32 score tile fits VMEM, so
# blockwise online softmax is pure overhead: the blocked kernel's grid of
# (B*H, L/bq) tiny cells measured 2.1 ms for BERT-base fwd (ideal ~0.2) —
# dominated by per-cell pipeline latency at D=64. Here one grid cell
# processes G heads end-to-end: one QK^T dot, plain row softmax, one PV
# dot per head. bf16 MXU dots with fp32 accumulation throughout.
# ---------------------------------------------------------------------------
_WHOLE_L_MAX = 1024


def _whole_g(BH, gmax=8):
    for g in (8, 4, 2, 1):
        if g <= gmax and BH % g == 0:
            return g


def _use_whole(q, k, v):
    B, H, L, D = q.shape
    Lk = k.shape[2]
    return (k.shape == v.shape and q.shape[0] == k.shape[0]
            and q.shape[1] % k.shape[1] == 0 and q.shape[3] == k.shape[3]
            and L <= _WHOLE_L_MAX and Lk <= _WHOLE_L_MAX
            and L % 128 == 0 and Lk % 128 == 0 and D % 8 == 0)


def _pallas_fwd_whole(q, k, v, causal, scale, valid_length=None,
                      dropout=0.0, seed=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D = q.shape
    Lk = k.shape[2]
    Hkv = k.shape[1]
    BH = B * H
    shared_kv = Hkv != H            # GQA: one kv head serves H//Hkv q heads
    G = H // Hkv if shared_kv else _whole_g(BH)
    GK = 1 if shared_kv else G
    qf = q.reshape(BH, L, D)
    kf = k.reshape(B * Hkv, Lk, D)
    vf = v.reshape(B * Hkv, Lk, D)
    has_vl = valid_length is not None
    has_do = dropout > 0.0 and seed is not None
    scalars = []
    if has_vl:
        scalars.append(valid_length.astype(jnp.int32))
    if has_do:
        scalars.append(seed.astype(jnp.int32))

    def kernel(*refs):
        i = 0
        vl_ref = sd_ref = None
        if has_vl:
            vl_ref = refs[i]
            i += 1
        if has_do:
            sd_ref = refs[i]
            i += 1
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs[i:]
        cell = pl.program_id(0)

        def head(g, _):
            gk = 0 if shared_kv else g
            qg = q_ref[pl.ds(g, 1)][0]
            s = jax.lax.dot_general(
                qg, k_ref[pl.ds(gk, 1)][0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            if causal:
                qpos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 0)
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 1)
                s = jnp.where(qpos >= kpos, s, -1e30)
            if has_vl:
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 1)
                b = cell // Hkv if shared_kv else (cell * G + g) // H
                s = jnp.where(kpos < vl_ref[b], s, -1e30)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp2(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            if has_do:
                # seed by ABSOLUTE head index: the backward kernel uses a
                # different G and must regenerate the identical mask
                p = p * _kernel_dropout_mult(dropout, sd_ref, cell * G + g,
                                             (L, Lk))
            o = jax.lax.dot_general(
                p.astype(q_ref.dtype), v_ref[pl.ds(gk, 1)][0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            o_ref[pl.ds(g, 1)] = ((o / l).astype(o_ref.dtype))[None]
            lse_ref[pl.ds(g, 1)] = (
                (m + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2)[None]
            return 0

        jax.lax.fori_loop(0, G, head, 0)

    out_shape = [
        jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        jax.ShapeDtypeStruct((BH, L, 1), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((G, L, D), lambda i, *a: (i, 0, 0)),
        pl.BlockSpec((GK, Lk, D), lambda i, *a: (i, 0, 0)),
        pl.BlockSpec((GK, Lk, D), lambda i, *a: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((G, L, D), lambda i, *a: (i, 0, 0)),
        pl.BlockSpec((G, L, 1), lambda i, *a: (i, 0, 0)),
    ]
    if scalars:
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(BH // G,),
                in_specs=in_specs, out_specs=out_specs),
            out_shape=out_shape)(*scalars, qf, kf, vf)
    else:
        out, lse = pl.pallas_call(
            kernel, grid=(BH // G,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape)(qf, kf, vf)
    return out.reshape(B, H, L, D), lse.reshape(B, H, L)


def _pallas_bwd_whole(q, k, v, out, lse, do, causal, scale,
                      valid_length=None, dropout=0.0, seed=None):
    """Whole-L FA backward: one grid cell = G heads, all five dots per
    head on (L, L)/(L, D) tiles (p/ds in bf16 for the MXU, fp32 accum)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D = q.shape
    Lk = k.shape[2]
    Hkv = k.shape[1]
    BH = B * H
    shared_kv = Hkv != H
    # bwd streams 9 (G, L, D) blocks per cell (vs fwd's 5) — halve G to
    # stay inside the 16 MiB scoped-VMEM budget
    G = H // Hkv if shared_kv else _whole_g(BH, gmax=4)
    GK = 1 if shared_kv else G
    qf = q.reshape(BH, L, D)
    kf = k.reshape(B * Hkv, Lk, D)
    vf = v.reshape(B * Hkv, Lk, D)
    dof = do.reshape(BH, L, D)
    of = out.reshape(BH, L, D)
    lsef = lse.reshape(BH, L, 1)
    has_vl = valid_length is not None
    has_do = dropout > 0.0 and seed is not None
    scalars = []
    if has_vl:
        scalars.append(valid_length.astype(jnp.int32))
    if has_do:
        scalars.append(seed.astype(jnp.int32))

    def kernel(*refs):
        i = 0
        vl_ref = sd_ref = None
        if has_vl:
            vl_ref = refs[i]
            i += 1
        if has_do:
            sd_ref = refs[i]
            i += 1
        if shared_kv:
            (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
             dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs[i:]
        else:
            (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
             dq_ref, dk_ref, dv_ref) = refs[i:]
            dk_acc = dv_acc = None
        cell = pl.program_id(0)
        if shared_kv:
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def head(g, _):
            gk = 0 if shared_kv else g
            qg = q_ref[pl.ds(g, 1)][0]
            kg = k_ref[pl.ds(gk, 1)][0]
            vg = v_ref[pl.ds(gk, 1)][0]
            dog = do_ref[pl.ds(g, 1)][0]
            s = jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            if causal:
                qpos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 0)
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 1)
                s = jnp.where(qpos >= kpos, s, -1e30)
            if has_vl:
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, Lk), 1)
                b = cell // Hkv if shared_kv else (cell * G + g) // H
                s = jnp.where(kpos < vl_ref[b], s, -1e30)
            p = jnp.exp2(s - lse_ref[pl.ds(g, 1)][0] * _LOG2E)
            if has_do:
                # identical (seed, absolute-head, shape) as the forward
                mt = _kernel_dropout_mult(dropout, sd_ref, cell * G + g,
                                          (L, Lk))
                pm = p * mt
            else:
                mt = None
                pm = p
            pb = pm.astype(q_ref.dtype)
            # delta = rowsum(do * o)
            delta = jnp.sum(dog.astype(jnp.float32)
                            * o_ref[pl.ds(g, 1)][0].astype(jnp.float32),
                            axis=-1, keepdims=True)
            dv_g = jax.lax.dot_general(
                pb, dog, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            dp = jax.lax.dot_general(
                dog, vg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            if has_do:
                # ds = p o (M~ o dp - delta): rowsum(p o M~ o dp) == delta
                # still holds because delta = rowsum(do*o) and o used pm
                dp = dp * mt
            ds = (p * (dp - delta) * scale).astype(q_ref.dtype)
            dq_ref[pl.ds(g, 1)] = jax.lax.dot_general(
                ds, kg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT).astype(dq_ref.dtype)[None]
            dk_g = jax.lax.dot_general(
                ds, qg, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            if shared_kv:
                # one kv head serves the whole q-head group: accumulate
                dk_acc[...] += dk_g
                dv_acc[...] += dv_g
            else:
                dv_ref[pl.ds(g, 1)] = dv_g.astype(dv_ref.dtype)[None]
                dk_ref[pl.ds(g, 1)] = dk_g.astype(dk_ref.dtype)[None]
            return 0

        jax.lax.fori_loop(0, G, head, 0)
        if shared_kv:
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    fullq = pl.BlockSpec((G, L, D), lambda i, *a: (i, 0, 0))
    fullk = pl.BlockSpec((GK, Lk, D), lambda i, *a: (i, 0, 0))
    one = pl.BlockSpec((G, L, 1), lambda i, *a: (i, 0, 0))
    in_specs = [fullq, fullk, fullk, fullq, fullq, one]
    out_specs = [fullq, fullk, fullk]
    out_shape = [jax.ShapeDtypeStruct((BH, L, D), q.dtype),
                 jax.ShapeDtypeStruct((B * Hkv, Lk, D), k.dtype),
                 jax.ShapeDtypeStruct((B * Hkv, Lk, D), v.dtype)]
    operands = [qf, kf, vf, of, dof, lsef]
    scratch = [pltpu.VMEM((Lk, D), jnp.float32),
               pltpu.VMEM((Lk, D), jnp.float32)] if shared_kv else []
    if scalars:
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(BH // G,),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape)(*scalars, *operands)
    else:
        dq, dk, dv = pl.pallas_call(
            kernel, grid=(BH // G,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch)(*operands)
    return (dq.reshape(B, H, L, D), dk.reshape(B, Hkv, Lk, D),
            dv.reshape(B, Hkv, Lk, D))


def _pallas_whole_check(kind, q, k, v, causal, has_vl, has_do=False):
    """Compile-probe the whole-L kernels once per signature."""
    import jax
    import jax.numpy as jnp

    B, H, L, D = q.shape
    rate = 0.1 if has_do else 0.0

    def compile_fn():
        if kind == "fwd":
            args = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                    jax.ShapeDtypeStruct(k.shape, k.dtype),
                    jax.ShapeDtypeStruct(v.shape, v.dtype)]

            def fn(q_, k_, v_, *rest):
                vl = rest[0] if has_vl else None
                sd = rest[-1] if has_do else None
                return _pallas_fwd_whole(q_, k_, v_, causal, 1.0, vl,
                                         rate, sd)
        else:
            args = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                    jax.ShapeDtypeStruct(k.shape, k.dtype),
                    jax.ShapeDtypeStruct(v.shape, v.dtype),
                    jax.ShapeDtypeStruct(q.shape, q.dtype),       # out
                    jax.ShapeDtypeStruct((B, H, L), jnp.float32),  # lse
                    jax.ShapeDtypeStruct(q.shape, q.dtype)]       # do

            def fn(q_, k_, v_, o_, l_, do_, *rest):
                vl = rest[0] if has_vl else None
                sd = rest[-1] if has_do else None
                return _pallas_bwd_whole(q_, k_, v_, o_, l_, do_, causal,
                                         1.0, vl, rate, sd)
        if has_vl:
            args.append(jax.ShapeDtypeStruct((B,), jnp.int32))
        if has_do:
            args.append(jax.ShapeDtypeStruct((1,), jnp.int32))
        jax.jit(fn).lower(*args).compile()

    return probe_compile(
        "attention_whole_" + kind,
        (q.shape, k.shape, str(q.dtype), str(k.dtype), str(v.dtype),
         bool(causal), bool(has_vl), bool(has_do)), compile_fn)


# ---------------------------------------------------------------------------
# packed-2D whole-L kernels: q/k/v as (B*L, H*D) — the raw layout of a QKV
# projection — with one grid cell per (batch, head) pair. No (B,L,H,D) ->
# (B,H,L,D) transposes anywhere: the BlockSpec index map carves the
# (L, D) tile for head h straight out of the packed matrix. lse is
# (B*L, H) f32.
# ---------------------------------------------------------------------------
def _pallas_fwd_whole2d(q2, k2, v2, B, H, causal, scale,
                        valid_length=None, dropout=0.0, seed=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BL, HD = q2.shape
    L, D = BL // B, HD // H
    has_vl = valid_length is not None
    has_do = dropout > 0.0 and seed is not None
    scalars = []
    if has_vl:
        scalars.append(valid_length.astype(jnp.int32))
    if has_do:
        scalars.append(seed.astype(jnp.int32))

    def kernel(*refs):
        i = 0
        vl_ref = sd_ref = None
        if has_vl:
            vl_ref = refs[i]
            i += 1
        if has_do:
            sd_ref = refs[i]
            i += 1
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs[i:]
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            s = jax.lax.dot_general(
                q_ref[:, sl], k_ref[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            if causal:
                qpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
                s = jnp.where(qpos >= kpos, s, -1e30)
            if has_vl:
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
                s = jnp.where(kpos < vl_ref[pl.program_id(0)], s, -1e30)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp2(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            if has_do:
                p = p * _kernel_dropout_mult(
                    dropout, sd_ref, pl.program_id(0) * H + h, (L, L))
            o = jax.lax.dot_general(
                p.astype(q_ref.dtype), v_ref[:, sl],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            o_ref[:, sl] = (o / l).astype(o_ref.dtype)
            lse_ref[:, h:h + 1] = \
                (m + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2

    blk = lambda b, *a: (b, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((L, HD), blk)] * 3
    out_specs = [pl.BlockSpec((L, HD), blk),
                 pl.BlockSpec((L, H), blk)]
    out_shape = [jax.ShapeDtypeStruct((BL, HD), q2.dtype),
                 jax.ShapeDtypeStruct((BL, H), jnp.float32)]
    # 9 full-width (L, H*D) blocks double-buffered brush against the
    # default 16 MiB scoped-VMEM budget; raise it (v5e has 128 MiB)
    cp = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
    if scalars:
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(B,),
                in_specs=in_specs, out_specs=out_specs),
            compiler_params=cp,
            out_shape=out_shape)(*scalars, q2, k2, v2)
    else:
        out, lse = pl.pallas_call(
            kernel, grid=(B,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            compiler_params=cp)(q2, k2, v2)
    return out, lse


def _pallas_bwd_whole2d(q2, k2, v2, out2, lse2, do2, B, H, causal, scale,
                        valid_length=None, dropout=0.0, seed=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BL, HD = q2.shape
    L, D = BL // B, HD // H
    has_vl = valid_length is not None
    has_do = dropout > 0.0 and seed is not None
    scalars = []
    if has_vl:
        scalars.append(valid_length.astype(jnp.int32))
    if has_do:
        scalars.append(seed.astype(jnp.int32))

    def kernel(*refs):
        i = 0
        vl_ref = sd_ref = None
        if has_vl:
            vl_ref = refs[i]
            i += 1
        if has_do:
            sd_ref = refs[i]
            i += 1
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dq_ref, dk_ref, dv_ref) = refs[i:]
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            dog = do_ref[:, sl]
            s = jax.lax.dot_general(
                q_ref[:, sl], k_ref[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            if causal:
                qpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
                s = jnp.where(qpos >= kpos, s, -1e30)
            if has_vl:
                kpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
                s = jnp.where(kpos < vl_ref[pl.program_id(0)], s, -1e30)
            p = jnp.exp2(s - lse_ref[:, h:h + 1] * _LOG2E)
            if has_do:
                mt = _kernel_dropout_mult(
                    dropout, sd_ref, pl.program_id(0) * H + h, (L, L))
                pm = p * mt
            else:
                mt = None
                pm = p
            pb = pm.astype(q_ref.dtype)
            delta = jnp.sum(dog.astype(jnp.float32)
                            * o_ref[:, sl].astype(jnp.float32),
                            axis=-1, keepdims=True)
            dv_ref[:, sl] = jax.lax.dot_general(
                pb, dog, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT).astype(dv_ref.dtype)
            dp = jax.lax.dot_general(
                dog, v_ref[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            if has_do:
                dp = dp * mt
            ds = (p * (dp - delta) * scale).astype(q_ref.dtype)
            dq_ref[:, sl] = jax.lax.dot_general(
                ds, k_ref[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT).astype(dq_ref.dtype)
            dk_ref[:, sl] = jax.lax.dot_general(
                ds, q_ref[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT).astype(dk_ref.dtype)

    blk = lambda b, *a: (b, 0)  # noqa: E731
    full = pl.BlockSpec((L, HD), blk)
    one = pl.BlockSpec((L, H), blk)
    in_specs = [full, full, full, full, full, one]
    out_specs = [full, full, full]
    out_shape = [jax.ShapeDtypeStruct((BL, HD), q2.dtype)] * 3
    operands = [q2, k2, v2, out2, do2, lse2]
    cp = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
    if scalars:
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars), grid=(B,),
                in_specs=in_specs, out_specs=out_specs),
            compiler_params=cp,
            out_shape=out_shape)(*scalars, *operands)
    else:
        dq, dk, dv = pl.pallas_call(
            kernel, grid=(B,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            compiler_params=cp)(*operands)
    return dq, dk, dv


def flash_attention_packed(q2, k2, v2, B, H, causal=False, scale=None,
                           valid_length=None, dropout=0.0, seed=None):
    """Fused attention on PACKED 2-D layouts: q/k/v (B*L, H*D) — exactly a
    QKV projection's output slices — returning (B*L, H*D). No head/seq
    transposes enter the program. TPU + whole-L shapes only (the caller
    guards); gradients via custom_vjp with the matching packed backward.
    ``dropout``/``seed``: in-kernel attention-probability dropout (the
    reference's BERTEncoder semantics); the mask is regenerated from the
    (1,) int32 seed in the backward, never materialized."""
    return _fa_packed(q2, k2, v2, B, H, causal, scale, valid_length,
                      dropout, seed)


@functools.partial(__import__("jax").custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 8))
def _fa_packed(q2, k2, v2, B, H, causal, scale, valid_length=None,
               dropout=0.0, seed=None):
    out, _ = _fa_packed_fwd_impl(q2, k2, v2, B, H, causal, scale,
                                 valid_length, dropout, seed)
    return out


def _fa_packed_fwd_impl(q2, k2, v2, B, H, causal, scale, valid_length,
                        dropout=0.0, seed=None):
    scale = scale if scale is not None else 1.0 / ((q2.shape[1] // H) ** 0.5)
    return _pallas_fwd_whole2d(q2, k2, v2, B, H, causal, scale,
                               valid_length, dropout, seed)


def _fa_packed_fwd(q2, k2, v2, B, H, causal, scale, valid_length=None,
                   dropout=0.0, seed=None):
    out, lse = _fa_packed_fwd_impl(q2, k2, v2, B, H, causal, scale,
                                   valid_length, dropout, seed)
    return out, (q2, k2, v2, out, lse, valid_length, seed)


def _fa_packed_bwd(B, H, causal, scale, dropout, res, do):
    import jax
    import jax.numpy as jnp
    q2, k2, v2, out, lse, valid_length, seed = res
    scale_ = scale if scale is not None else 1.0 / ((q2.shape[1] // H) ** 0.5)
    dq, dk, dv = _pallas_bwd_whole2d(q2, k2, v2, out, lse, do, B, H,
                                     causal, scale_, valid_length,
                                     dropout, seed)
    dvl = None if valid_length is None else \
        jnp.zeros(valid_length.shape, dtype=jax.dtypes.float0)
    dseed = None if seed is None else \
        jnp.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dvl, dseed


_fa_packed.defvjp(_fa_packed_fwd, _fa_packed_bwd)


def _pallas_packed_check(q2, B, H, causal, has_vl, has_dropout=False):
    """Compile-probe the packed kernels, forward AND backward (through
    jax.grad), once per signature."""
    import jax
    import jax.numpy as jnp
    rate = 0.1 if has_dropout else 0.0

    def compile_fn():
        args = [jax.ShapeDtypeStruct(q2.shape, q2.dtype)] * 3
        if has_vl:
            args.append(jax.ShapeDtypeStruct((B,), jnp.int32))
        if has_dropout:
            args.append(jax.ShapeDtypeStruct((1,), jnp.int32))

        def fn(a, b, c, *rest):
            vl = rest[0] if has_vl else None
            sd = rest[-1] if has_dropout else None
            return _fa_packed(a, b, c, B, H, causal, 1.0, vl, rate, sd)

        def train(*xs):
            def loss(*ys):
                return (fn(*ys).astype(jnp.float32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(*xs)
        jax.jit(train).lower(*args).compile()

    return probe_compile(
        "attention_packed_fwd_bwd",
        (q2.shape, str(q2.dtype), B, H, bool(causal), bool(has_vl),
         bool(has_dropout)), compile_fn)


# ---------------------------------------------------------------------------
# pallas forward kernel (blockwise; L > _WHOLE_L_MAX)
# ---------------------------------------------------------------------------
def _pallas_fwd(q, k, v, causal, scale, valid_length=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D = q.shape
    bq, bk = _pick_bq(L), min(_BLOCK_K, L)
    nq = L // bq
    nk = L // bk
    qf = q.reshape(B * H, L, D)
    kf = k.reshape(B * H, L, D)
    vf = v.reshape(B * H, L, D)
    has_vl = valid_length is not None
    if has_vl:
        # one scalar per batch row, delivered via scalar prefetch (SMEM) —
        # a (1, 1) VMEM block would violate Mosaic's tile-shape rules
        vlf = valid_length.astype(jnp.int32)

    def kernel(*refs):
        if has_vl:
            vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc = refs
        else:
            q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc = refs
        iq = pl.program_id(1)
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, -1e30)
        l_sc[:] = jnp.zeros_like(l_sc)
        # keep operands in their storage dtype (bf16) for the MXU dots and
        # accumulate in fp32 (preferred_element_type): fp32 MXU passes run
        # at 1/4 rate, which with D=64 half-occupancy measured ~14 TF/s
        # for the whole kernel; bf16 dots recover ~4x
        qb = q_ref[0]  # (bq, D)

        def body(j, _):
            kb_ = k_ref[0, pl.ds(j * bk, bk), :]
            vb_ = v_ref[0, pl.ds(j * bk, bk), :]
            # contract over D via dot_general dims (no .T: transposing a
            # packed bf16 tile costs VPU sublane shuffles)
            s = jax.lax.dot_general(
                qb, kb_, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            if causal:
                qpos = iq * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = j * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, -1e30)
            if has_vl:
                kpos = j * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(kpos < vl_ref[pl.program_id(0) // H], s, -1e30)
            m_prev = m_sc[:, 0]
            m_b = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_b)
            p = jnp.exp2(s - m_new[:, None])
            alpha = jnp.exp2(m_prev - m_new)
            l_new = l_sc[:, 0] * alpha + jnp.sum(p, axis=-1)
            acc[:] = acc[:] * alpha[:, None] + jnp.dot(
                p.astype(vb_.dtype), vb_,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            m_sc[:, 0] = m_new
            l_sc[:, 0] = l_new
            return 0

        upper = nk if not causal else (iq * bq // bk + (bq // bk))
        jax.lax.fori_loop(0, upper if causal else nk, body, 0)
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0] = (acc[:] / l[:, None]).astype(o_ref.dtype)
        # lse laid out (BH, L, 1): trailing unit dim keeps the block shape
        # (1, bq, 1) legal for TPU tiling (bq % 8 == 0, last dim == array's)
        lse_ref[0] = ((m_sc[:, 0] + jnp.log2(l)) * _LN2)[:, None]

    out_shape = [
        jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        jax.ShapeDtypeStruct((B * H, L, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, D), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
    ]
    # long-context lengths stream full (1, L, D) k/v blocks per cell:
    # at L=32k that is ~4 MB each, double-buffered — far over the 16 MB
    # default scoped-VMEM limit (v5e has 128 MB physical); without this
    # the compile probe fails and 32k+ contexts silently took the scan
    # path (measured 1008 -> ~210 ms/step at B1 H16 L32k D64 once the
    # kernels actually run)
    cp = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
    if has_vl:
        # index maps receive the prefetched scalar ref as a trailing arg
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, nq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, vl: (b, i, 0)),
                pl.BlockSpec((1, L, D), lambda b, i, vl: (b, 0, 0)),
                pl.BlockSpec((1, L, D), lambda b, i, vl: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, vl: (b, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda b, i, vl: (b, i, 0)),
            ],
            scratch_shapes=scratch,
        )
        out, lse = pl.pallas_call(kernel, grid_spec=grid_spec,
                                  compiler_params=cp,
                                  out_shape=out_shape)(vlf, qf, kf, vf)
    else:
        out, lse = pl.pallas_call(
            kernel,
            grid=(B * H, nq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, L, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, L, D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=cp,
        )(qf, kf, vf)
    return out.reshape(B, H, L, D), lse.reshape(B, H, L)


def _pallas_fwd_check(q, k, v, causal, has_vl=False):
    """Compile-probe the blocked forward kernel once per shape/dtype
    signature.  The scale value is a plain multiplier and cannot affect
    whether Mosaic lowers, so the probe uses 1.0 and the signature carries
    only shapes/dtypes/causal/has_vl (a jax-array scale must not be
    hashed)."""
    import jax

    def compile_fn():
        args = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype)]
        if has_vl:
            import jax.numpy as jnp
            args.append(jax.ShapeDtypeStruct((q.shape[0],), jnp.int32))
            fn = lambda q_, k_, v_, vl_: _pallas_fwd(  # noqa: E731
                q_, k_, v_, causal, 1.0, vl_)
        else:
            fn = lambda q_, k_, v_: _pallas_fwd(  # noqa: E731
                q_, k_, v_, causal, 1.0)
        jax.jit(fn).lower(*args).compile()

    return probe_compile(
        "attention_blocked_fwd",
        (q.shape, k.shape, str(q.dtype), str(k.dtype), str(v.dtype),
         bool(causal), bool(has_vl)), compile_fn)


# ---------------------------------------------------------------------------
# pallas backward kernels (FA2: recompute P from lse; dkv kernel loops over
# q blocks per k block, dq kernel loops over k blocks per q block)
# ---------------------------------------------------------------------------
def _pallas_bwd(q, k, v, out, lse, do, causal, scale, valid_length=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D = q.shape
    bq, bk = _pick_bq(L), min(_BLOCK_K, L)
    nq, nk = L // bq, L // bk
    qf = q.reshape(B * H, L, D)
    kf = k.reshape(B * H, L, D)
    vf = v.reshape(B * H, L, D)
    dof = do.reshape(B * H, L, D)
    lsef = lse.reshape(B * H, L, 1)
    # delta = rowsum(do * o): cheap, fused by XLA — no kernel needed
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * H, L, 1)
    has_vl = valid_length is not None
    if has_vl:
        vlf = valid_length.astype(jnp.int32)

    def mask_s(s, i0, j0, rows, cols, vl_ref, bh):
        # rows/cols are tile-local extents; i0/j0 global offsets (q, k)
        if causal:
            qpos = i0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            kpos = j0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
            s = jnp.where(qpos >= kpos, s, -1e30)
        if has_vl:
            kpos = j0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
            s = jnp.where(kpos < vl_ref[bh // H], s, -1e30)
        return s

    def dkv_kernel(*refs):
        if has_vl:
            (vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
             dk_ref, dv_ref, dk_acc, dv_acc) = refs
        else:
            vl_ref = None
            (q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
             dk_ref, dv_ref, dk_acc, dv_acc) = refs
        bh = pl.program_id(0)
        jk = pl.program_id(1)
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        kb = k_ref[0].astype(jnp.float32)      # (bk, D)
        vb = v_ref[0].astype(jnp.float32)

        def body(i, _):
            qb = q_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
            dob = do_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
            lseb = lse_ref[0, pl.ds(i * bq, bq), :]     # (bq, 1) f32
            db = d_ref[0, pl.ds(i * bq, bq), :]
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            s = mask_s(s, i * bq, jk * bk, bq, bk, vl_ref, bh)
            p = jnp.exp2(s - lseb * _LOG2E)
            dv_acc[:] = dv_acc[:] + jnp.dot(
                p.T, dob, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
            ds = p * (dp - db) * scale
            dk_acc[:] = dk_acc[:] + jnp.dot(
                ds.T, qb, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            return 0

        # causal: k block jk only sees q blocks with i*bq + bq > jk*bk
        lower = (jk * bk) // bq if causal else 0
        jax.lax.fori_loop(lower, nq, body, 0)
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    def dq_kernel(*refs):
        if has_vl:
            (vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
             dq_ref, dq_acc) = refs
        else:
            vl_ref = None
            (q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
             dq_ref, dq_acc) = refs
        bh = pl.program_id(0)
        iq = pl.program_id(1)
        dq_acc[:] = jnp.zeros_like(dq_acc)
        qb = q_ref[0].astype(jnp.float32)      # (bq, D)
        dob = do_ref[0].astype(jnp.float32)
        lseb = lse_ref[0]
        db = d_ref[0]

        def body(j, _):
            kb = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.DEFAULT) * (scale * _LOG2E)
            s = mask_s(s, iq * bq, j * bk, bq, bk, vl_ref, bh)
            p = jnp.exp2(s - lseb * _LOG2E)
            dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
            ds = p * (dp - db) * scale
            dq_acc[:] = dq_acc[:] + jnp.dot(
                ds, kb, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            return 0

        upper = (iq * bq) // bk + (bq // bk) if causal else nk
        jax.lax.fori_loop(0, upper, body, 0)
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    # index maps take (*grid_ids, *scalar_refs); the trailing *a absorbs the
    # prefetched scalar ref in the vl variant and is empty otherwise.
    # dk/dv: tile over k blocks; q/do/lse/delta stream fully
    dkv_in = [
        pl.BlockSpec((1, L, D), lambda b, j, *a: (b, 0, 0)),   # q full
        pl.BlockSpec((1, bk, D), lambda b, j, *a: (b, j, 0)),  # k tile
        pl.BlockSpec((1, bk, D), lambda b, j, *a: (b, j, 0)),  # v tile
        pl.BlockSpec((1, L, D), lambda b, j, *a: (b, 0, 0)),   # do full
        pl.BlockSpec((1, L, 1), lambda b, j, *a: (b, 0, 0)),   # lse full
        pl.BlockSpec((1, L, 1), lambda b, j, *a: (b, 0, 0)),   # delta full
    ]
    dkv_out = [
        pl.BlockSpec((1, bk, D), lambda b, j, *a: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, j, *a: (b, j, 0)),
    ]
    dkv_shape = [jax.ShapeDtypeStruct((B * H, L, D), k.dtype),
                 jax.ShapeDtypeStruct((B * H, L, D), v.dtype)]
    dkv_scratch = [pltpu.VMEM((bk, D), jnp.float32),
                   pltpu.VMEM((bk, D), jnp.float32)]

    dq_in = [
        pl.BlockSpec((1, bq, D), lambda b, i, *a: (b, i, 0)),  # q tile
        pl.BlockSpec((1, L, D), lambda b, i, *a: (b, 0, 0)),   # k full
        pl.BlockSpec((1, L, D), lambda b, i, *a: (b, 0, 0)),   # v full
        pl.BlockSpec((1, bq, D), lambda b, i, *a: (b, i, 0)),  # do tile
        pl.BlockSpec((1, bq, 1), lambda b, i, *a: (b, i, 0)),  # lse tile
        pl.BlockSpec((1, bq, 1), lambda b, i, *a: (b, i, 0)),  # delta tile
    ]
    dq_out = [pl.BlockSpec((1, bq, D), lambda b, i, *a: (b, i, 0))]
    dq_shape = [jax.ShapeDtypeStruct((B * H, L, D), q.dtype)]
    dq_scratch = [pltpu.VMEM((bq, D), jnp.float32)]

    operands = [qf, kf, vf, dof, lsef, delta]
    # full-length streamed blocks need headroom over the 16 MB default
    # scoped-VMEM limit at long context (see _pallas_fwd); the (1, L, 1)
    # f32 lse/delta blocks pad their unit lane dim to 128 in VMEM, so the
    # backward needs most of v5e's 128 MB
    cp = pltpu.CompilerParams(vmem_limit_bytes=110 * 1024 * 1024)
    if has_vl:
        dkv = pl.pallas_call(
            dkv_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B * H, nk),
                in_specs=dkv_in, out_specs=dkv_out,
                scratch_shapes=dkv_scratch),
            compiler_params=cp,
            out_shape=dkv_shape)(vlf, *operands)
        dqr = pl.pallas_call(
            dq_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B * H, nq),
                in_specs=dq_in, out_specs=dq_out,
                scratch_shapes=dq_scratch),
            compiler_params=cp,
            out_shape=dq_shape)(vlf, *operands)
    else:
        dkv = pl.pallas_call(
            dkv_kernel, grid=(B * H, nk), in_specs=dkv_in,
            out_specs=dkv_out, out_shape=dkv_shape,
            scratch_shapes=dkv_scratch, compiler_params=cp)(*operands)
        dqr = pl.pallas_call(
            dq_kernel, grid=(B * H, nq), in_specs=dq_in,
            out_specs=dq_out, out_shape=dq_shape,
            scratch_shapes=dq_scratch, compiler_params=cp)(*operands)
    dk, dv = dkv
    dq = dqr[0]
    return (dq.reshape(B, H, L, D), dk.reshape(B, H, L, D),
            dv.reshape(B, H, L, D))


def _pallas_bwd_check(q, k, v, causal, has_vl):
    """Compile-probe the blocked backward kernels once per signature (see
    _pallas_fwd_check)."""
    import jax
    import jax.numpy as jnp
    B, H, L, D = q.shape

    def compile_fn():
        args = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct(q.shape, q.dtype),       # out
                jax.ShapeDtypeStruct((B, H, L), jnp.float32),  # lse
                jax.ShapeDtypeStruct(q.shape, q.dtype)]       # do
        if has_vl:
            args.append(jax.ShapeDtypeStruct((B,), jnp.int32))
            fn = lambda q_, k_, v_, o_, l_, do_, vl_: _pallas_bwd(  # noqa: E731
                q_, k_, v_, o_, l_, do_, causal, 1.0, vl_)
        else:
            fn = lambda q_, k_, v_, o_, l_, do_: _pallas_bwd(  # noqa: E731
                q_, k_, v_, o_, l_, do_, causal, 1.0)
        jax.jit(fn).lower(*args).compile()

    return probe_compile(
        "attention_blocked_bwd",
        (q.shape, k.shape, str(q.dtype), str(k.dtype), str(v.dtype),
         bool(causal), bool(has_vl)), compile_fn)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(__import__("jax").custom_vjp, nondiff_argnums=(3, 4, 6))
def flash_attention(q, k, v, causal=False, scale=None, valid_length=None,
                    dropout=0.0, seed=None):
    """Fused attention, (B, H, L, D) -> (B, H, L, D).

    ``valid_length``: optional (B,) int key-padding lengths (keys >= length
    are masked).  Output rows at padded query positions are don't-care
    (uniform attention), same as the reference's masked-softmax path.
    ``causal`` with Lq != Lk uses TOP-LEFT alignment on every path (query
    i attends keys <= i) — NOT FlashAttention's bottom-right convention
    (keys <= i + Lk - Lq); pad queries up front if you need the latter.
    ``dropout``/``seed``: attention-probability dropout (reference
    BERTEncoder semantics) — in-kernel PRNG on the Pallas paths, blockwise
    jax.random on the scan path; the mask is regenerated in the backward
    from the (1,) int32 seed and never materializes.

    Precision note: the kernel paths run their dots at
    ``Precision.DEFAULT`` (single-pass bf16 on the MXU) regardless of
    input dtype — f32 inputs get bf16-grade matmul accuracy (~3e-3) on
    accelerators, like every major flash implementation.  Use the dense
    path (scores under ``MXNET_ATTN_DENSE_MAX_ELEMS``) when exact-f32
    attention is required."""
    out, _ = _fa_fwd_impl(q, k, v, causal, scale, valid_length, dropout,
                          seed)
    return out


def _scan_key(seed):
    import jax
    return jax.random.PRNGKey(seed[0])


def _fa_fwd_impl(q, k, v, causal, scale, valid_length=None, dropout=0.0,
                 seed=None):
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    has_do = dropout > 0.0 and seed is not None
    if _use_pallas(q, k, v):
        qp, kp, vp, _, _, _, vlp, Lq0 = _pad_attn(
            q, k, v, valid_length=valid_length)
        # with dropout the forward and backward MUST pair on the same
        # mask-regeneration PRNG: the whole-L kernels use the pltpu PRNG,
        # the scan fallback uses jax.random threefry.  Gate the forward on
        # the BACKWARD probe too, so a bwd-only compile failure (bwd holds
        # ~3x the buffers) can never silently pair a kernel forward with a
        # scan backward and produce gradients under a different mask.
        whole_ok = _use_whole(qp, kp, vp) and _pallas_whole_check(
            "fwd", qp, kp, vp, causal, vlp is not None, has_do)
        if whole_ok and has_do:
            whole_ok = _pallas_whole_check(
                "bwd", qp, kp, vp, causal, vlp is not None, has_do)
        if whole_ok:
            out, lse = _pallas_fwd_whole(qp, kp, vp, causal, scale, vlp,
                                         dropout, seed)
            return out[:, :, :Lq0], lse[:, :, :Lq0]
        if not has_do and q.shape == k.shape and q.shape[2] % 128 == 0 \
                and _pallas_fwd_check(q, k, v, causal,
                                      has_vl=valid_length is not None):
            # blocked kernels (L > whole-L max) carry no dropout support;
            # dropout at those lengths takes the scan path
            return _pallas_fwd(q, k, v, causal, scale, valid_length)
    key = _scan_key(seed) if has_do else None
    return _scan_attention(q, k, v, causal, scale, valid_length,
                           dropout=dropout if has_do else 0.0, key=key)


def _fa_fwd(q, k, v, causal, scale, valid_length, dropout, seed):
    out, lse = _fa_fwd_impl(q, k, v, causal, scale, valid_length, dropout,
                            seed)
    return out, (q, k, v, out, lse, valid_length, seed)


# The hand-written dq/dkv kernels are numerically exact but measured ~5%
# SLOWER than the lax.scan backward at BERT-base shapes on v5e (196 vs
# 187 ms/step): the two-kernel split recomputes s and dp twice, while XLA
# pipelines the scan body (which shares them) well.  Kept for future tuning
# (e.g. fused dq+dkv over a shared k loop, head packing for D=64).
_PALLAS_BWD = bool(int(__import__("os").environ.get(
    "MXNET_ATTN_PALLAS_BWD", "0")))


def _fa_bwd(causal, scale, dropout, res, do):
    """FA2 backward: recompute P blockwise from lse (O(L·B_k) memory).
    lax.scan math by default (fastest measured); optional Pallas kernels
    via MXNET_ATTN_PALLAS_BWD=1."""
    import jax
    import jax.numpy as jnp
    q, k, v, out, lse, valid_length, seed = res
    has_do = dropout > 0.0 and seed is not None

    def rets(dq, dk, dv):
        dvl = None if valid_length is None else \
            jnp.zeros(valid_length.shape, dtype=jax.dtypes.float0)
        dseed = None if seed is None else \
            jnp.zeros(seed.shape, dtype=jax.dtypes.float0)
        return dq, dk, dv, dvl, dseed

    scale_ = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _use_pallas(q, k, v):
        qp, kp, vp, op, dop, lsep, vlp, Lq0 = _pad_attn(
            q, k, v, out, do, lse, valid_length)
        # mirror of the forward's dropout PRNG-pairing gate: with dropout
        # the backward may use the whole-L kernel ONLY if the forward
        # dispatched it too (same fwd probe), else the forward ran the
        # threefry scan and the kernel would regenerate a different mask
        whole_ok = _use_whole(qp, kp, vp) and _pallas_whole_check(
            "bwd", qp, kp, vp, causal, vlp is not None, has_do)
        if whole_ok and has_do:
            whole_ok = _pallas_whole_check(
                "fwd", qp, kp, vp, causal, vlp is not None, has_do)
        if whole_ok:
            dq, dk, dv = _pallas_bwd_whole(qp, kp, vp, op, lsep, dop,
                                           causal, scale_, vlp, dropout,
                                           seed)
            Lk0 = k.shape[2]
            return rets(dq[:, :, :Lq0], dk[:, :, :Lk0], dv[:, :, :Lk0])
    if not has_do and _PALLAS_BWD and _use_pallas(q, k, v) \
            and q.shape == k.shape and q.shape[2] % 128 == 0 \
            and _pallas_bwd_check(q, k, v, causal,
                                  valid_length is not None):
        dq, dk, dv = _pallas_bwd(q, k, v, out, lse, do, causal, scale_,
                                 valid_length)
        return rets(dq, dk, dv)
    dkey = _scan_key(seed) if has_do else None
    B, H0, Lq0, D = q.shape
    Hkv = k.shape[1]
    gq = H0 // Hkv
    if gq > 1:
        # GQA: fold the query-head group into the length axis (see the
        # forward scan) — dk/dv then come out kv-head-shaped directly,
        # with the group reduction done by the einsum itself
        q = q.reshape(B, Hkv, gq * Lq0, D)
        do = do.reshape(B, Hkv, gq * Lq0, D)
        out = out.reshape(B, Hkv, gq * Lq0, D)
        lse = lse.reshape(B, Hkv, gq * Lq0)
    H, Lq = Hkv, gq * Lq0
    Lk = k.shape[2]
    bk = min(_BLOCK_K, Lk)
    nk = (Lk + bk - 1) // bk
    pad = nk * bk - Lk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
    kb = jnp.moveaxis(kp.reshape(B, H, nk, bk, D), 2, 0)
    vb = jnp.moveaxis(vp.reshape(B, H, nk, bk, D), 2, 0)

    # dots run in the storage dtype with fp32 accumulation (the fwd
    # convention): fp32 MXU passes are 1/4 rate, which dominated the 32k
    # long-context backward (measured 993 -> ~400 ms/step after this)
    mm_dtype = q.dtype
    do32 = do.astype(jnp.float32)
    o32 = out.astype(jnp.float32)
    dom = do.astype(mm_dtype)
    qm = q.astype(mm_dtype)
    delta = jnp.sum(do32 * o32, axis=-1)  # (B,H,Lq)
    qpos = jnp.tile(jnp.arange(Lq0), gq)

    def body(dq_acc, blk):
        k_j, v_j, j = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qm, k_j.astype(mm_dtype),
                       preferred_element_type=jnp.float32) * scale_
        kpos = j * bk + jnp.arange(bk)
        valid = kpos < Lk
        if causal:
            mask = valid[None, :] & (qpos[:, None] >= kpos[None, :])
        else:
            mask = jnp.broadcast_to(valid[None, :], (Lq, bk))
        s = jnp.where(mask[None, None], s, -1e30)
        if valid_length is not None:
            vmask = kpos[None, :] < valid_length.astype(jnp.int32)[:, None]
            s = jnp.where(vmask[:, None, None, :], s, -1e30)
        p = jnp.exp(s - lse[..., None])
        if has_do:
            # same fold_in(key, j) stream as the forward scan
            keep = jax.random.bernoulli(jax.random.fold_in(dkey, j),
                                        1.0 - dropout, s.shape)
            mt = jnp.where(keep, 1.0 / (1.0 - dropout), 0.0)
            pm = p * mt
        else:
            mt = None
            pm = p
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", pm.astype(mm_dtype), dom,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dom, v_j.astype(mm_dtype),
                        preferred_element_type=jnp.float32)
        if has_do:
            dp = dp * mt
        ds = (p * (dp - delta[..., None]) * scale_).astype(mm_dtype)
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                     k_j.astype(mm_dtype),
                                     preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qm,
                          preferred_element_type=jnp.float32)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, H, nk * bk, D)[:, :, :Lk]
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, H, nk * bk, D)[:, :, :Lk]
    if gq > 1:
        dq = dq.reshape(B, H0, Lq0, D)
    return rets(dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# Dense attention materializes the (B, H, Lq, Lk) fp32 score tensor in HBM
# every layer, forward and backward; the flash kernel streams it through
# VMEM.  Whole-step measurement on v5e (BERT-base L=512 B=32: flash 190ms vs
# dense 236ms fwd+bwd) shows flash wins as soon as scores are tens of MB —
# earlier isolated-op timings that favored dense were an artifact of per-call
# dispatch latency.  Dense remains only for small
# problems where the pallas grid would be degenerate.  Budget counts SCORE
# ELEMENTS (B*H*Lq*Lk): default 2e7 ≈ 80 MB of fp32 scores.
_DENSE_MAX_SCORE_ELEMS = int(float(__import__("os").environ.get(
    "MXNET_ATTN_DENSE_MAX_ELEMS", "2e7")))


def _dense_attention(q, k, v, causal, scale, valid_length=None,
                     dropout=0.0, seed=None):
    """Plain XLA attention: fp32 scores/softmax (matching the flash paths),
    fused by the compiler, differentiated by jax.  ``dropout``/``seed``:
    attention-prob dropout via jax.random (the reference's dense
    softmax->Dropout->PV order)."""
    import jax
    import jax.numpy as jnp
    if k.shape[1] != q.shape[1]:  # GQA: broadcast kv heads
        r = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, r, axis=1)
        v = jnp.repeat(v, r, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    Lq, Lk = q.shape[2], k.shape[2]
    if causal:
        # same convention as the scan/pallas paths: query i attends keys <= i
        mask = jnp.arange(Lq)[:, None] >= jnp.arange(Lk)[None, :]
        s = jnp.where(mask, s, -1e30)
    if valid_length is not None:
        vmask = jnp.arange(Lk)[None, :] < \
            valid_length.astype(jnp.int32)[:, None]
        s = jnp.where(vmask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if dropout > 0.0 and seed is not None:
        keep = jax.random.bernoulli(_scan_key(seed), 1.0 - dropout, p.shape)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout)), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def use_packed_attention(B, L, H, D, causal=False, has_vl=False,
                         dtype="bfloat16", has_dropout=False):
    """True when the packed-2D attention path applies and compiles: TPU,
    whole-L shapes. Models call this to skip the (B,L,H,D)->(B,H,L,D)
    transposes entirely."""
    import jax
    import jax.numpy as jnp
    if not kernel_dispatch_allowed():
        return False
    from ..parallel import ring_attention_config
    if ring_attention_config() is not None:
        # ring-promoted step: fall through to flash_attention_nd so the
        # ppermute ring path (sequence sharded over the seq axis) applies
        return False
    if not (L <= _WHOLE_L_MAX and L % 128 == 0 and D % 8 == 0):
        return False
    # small-problem policy: below the dense score budget XLA's fused
    # dense attention beats a B-cell pallas grid — UNLESS attention
    # dropout is active: the dense path pays a threefry mask over
    # (B, H, L, L) while the kernels draw bits in-register (measured on
    # transformer_base: dense+dropout 233k tok/s vs kernels 328k)
    if B * H * L * L <= _DENSE_MAX_SCORE_ELEMS and not has_dropout:
        return False
    q2 = jax.ShapeDtypeStruct((B * L, H * D), jnp.dtype(dtype))
    return _pallas_packed_check(q2, B, H, causal, has_vl, has_dropout)


def _attn_seed(dropout):
    """(1,) int32 step seed from the framework RNG when attention-prob
    dropout is active in training, else None."""
    from .. import autograd
    from .. import random as _random
    if dropout <= 0.0 or not autograd.is_training():
        return None
    return _seed_arr(_random.next_key())


def flash_attention_packed_nd(q2, k2, v2, B, H, causal=False, scale=None,
                              valid_length=None, dropout=0.0):
    """NDArray-facing packed attention: q/k/v (B*L, H*D) -> (B*L, H*D).

    The packed layout is exactly the QKV projection's output slices, so no
    head/seq transpose ever materializes (measured: the (B,L,H,D) <->
    (B,H,L,D) copies were ~12 ms/step on the BERT-base workload).
    ``dropout``: attention-probability dropout, applied in-kernel when
    training (reference BERTEncoder semantics)."""
    from ..ndarray.ndarray import apply_op, unwrap
    sc = unwrap(scale) if scale is not None else None
    seed = _attn_seed(dropout)
    rate = dropout if seed is not None else 0.0
    if valid_length is not None:
        if seed is not None:
            return apply_op(
                lambda a, b, c, vl, sd: _fa_packed(
                    a, b, c, B, H, causal, sc, vl, rate, sd),
                q2, k2, v2, valid_length, seed,
                op_name="flash_attention_packed")
        return apply_op(
            lambda a, b, c, vl: _fa_packed(a, b, c, B, H, causal, sc, vl),
            q2, k2, v2, valid_length, op_name="flash_attention_packed")
    if seed is not None:
        return apply_op(
            lambda a, b, c, sd: _fa_packed(a, b, c, B, H, causal, sc, None,
                                           rate, sd),
            q2, k2, v2, seed, op_name="flash_attention_packed")
    return apply_op(lambda a, b, c: _fa_packed(a, b, c, B, H, causal, sc),
                    q2, k2, v2, op_name="flash_attention_packed")


def flash_attention_nd(q, k, v, causal=False, scale=None, valid_length=None,
                       dropout=0.0):
    """NDArray-facing fused attention (inputs (B, H, L, D)).

    Memory-dispatched: dense XLA attention while B*H*Lq*Lk stays within
    ``MXNET_ATTN_DENSE_MAX_ELEMS``, the O(L)-memory flash kernel beyond.
    ``valid_length``: optional (B,) key-padding lengths (reference
    length-mask semantics) — supported on every path.  ``dropout``:
    attention-probability dropout when training, on every path."""
    from ..ndarray.ndarray import apply_op, unwrap
    sc = unwrap(scale) if scale is not None \
        else 1.0 / (unwrap(q).shape[-1] ** 0.5)
    B, H, Lq, _ = unwrap(q).shape
    Lk = unwrap(k).shape[2]
    seed = _attn_seed(dropout)
    rate = dropout if seed is not None else 0.0
    D = unwrap(q).shape[3]
    from ..parallel import ring_attention_config
    ring = ring_attention_config()
    if ring is not None:
        mesh, seq_axis = ring
        n_seq = mesh.shape[seq_axis]
        # ring path: full-sequence self-attention with the sequence
        # sharded over the seq axis, K/V rotating via ppermute
        # (SPMDTrainer(ring_attention=True)).  Dropout and
        # valid_length have no ring kernel — those calls (and decode
        # or cross-attention shapes) fall back to the dense/flash
        # single-device paths below.
        if (n_seq > 1 and Lq == Lk and Lq % n_seq == 0
                and seed is None and valid_length is None):
            from ..parallel.ring_attention import ring_attention as _ring
            from jax.sharding import PartitionSpec as _P
            spec = _P(None, seq_axis, None, None)

            def ring_impl(q_, k_, v_):
                import jax
                import jax.numpy as jnp
                # (B, H, L, D) -> the ring kernel's (B, L, H, D)
                qt, kt, vt = (jnp.transpose(a, (0, 2, 1, 3))
                              for a in (q_, k_, v_))
                out = jax.shard_map(
                    lambda a, b, c: _ring(a, b, c, seq_axis,
                                          causal=causal, scale=sc),
                    mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)(qt, kt, vt)
                return jnp.transpose(out, (0, 2, 1, 3))

            return apply_op(ring_impl, q, k, v, op_name="ring_attention")
    # dropout-aware policy: with an active in-kernel dropout seed the
    # pallas path wins even below the dense score budget (the dense path
    # pays a threefry mask over the full score tensor) — but only when
    # the whole-L kernel shape constraints guarantee in-register bits
    # (otherwise the fallback would pay threefry anyway)
    kernel_dropout_ok = (
        seed is not None
        and Lq % 128 == 0 and Lk % 128 == 0
        and Lq <= _WHOLE_L_MAX and Lk <= _WHOLE_L_MAX and D % 8 == 0)
    if _FORCE_DENSE or (B * H * Lq * Lk <= _DENSE_MAX_SCORE_ELEMS
                        and not kernel_dropout_ok):
        impl, name = _dense_attention, "dense_attention"
    else:
        impl, name = flash_attention, "flash_attention"
    if valid_length is not None:
        if seed is not None:
            return apply_op(
                lambda q_, k_, v_, vl_, sd: impl(q_, k_, v_, causal, sc,
                                                 vl_, rate, sd),
                q, k, v, valid_length, seed, op_name=name)
        return apply_op(
            lambda q_, k_, v_, vl_: impl(q_, k_, v_, causal, sc, vl_),
            q, k, v, valid_length, op_name=name)
    if seed is not None:
        return apply_op(
            lambda q_, k_, v_, sd: impl(q_, k_, v_, causal, sc, None,
                                        rate, sd),
            q, k, v, seed, op_name=name)
    return apply_op(lambda q_, k_, v_: impl(q_, k_, v_, causal, sc),
                    q, k, v, op_name=name)
