"""BERT (GluonNLP-shaped: ``scripts/bert`` / gluonnlp.model.BERTModel —
the reference stack's NLP headline workload, SURVEY.md §0/§6).

TPU-first differences from the GluonNLP implementation:
- attention is fused flash attention (``mxnet_tpu.ops.flash_attention``)
  instead of the interleaved-matmul O(L²) contrib ops;
- the whole encoder hybridizes to one XLA program;
- TP/SP sharding rules for the mesh live in :func:`bert_sharding_rules`.
"""
from __future__ import annotations

import math

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon import nn
from ..gluon.parameter import Parameter
from .. import initializer as init
from ..telemetry import part

__all__ = ["BERTModel", "BERTEncoder", "TransformerEncoderLayer",
           "MultiHeadAttention", "PositionwiseFFN", "bert_base", "bert_large",
           "bert_sharding_rules", "BERTPretrainingLoss"]


def length_mask(F, L, valid_length):
    """(B,) lengths -> (B, L) 1/0 mask (reference gluon-nlp mask shape)."""
    steps = F.arange(0, L)
    return (steps.reshape(1, L) <
            valid_length.reshape(-1, 1)).astype("float32")


class MultiHeadAttention(HybridBlock):
    """Self-attention with fused QKV projection + flash attention core.

    Attention-probability dropout (reference: GluonNLP BERTEncoder applies
    Dropout to the softmax output before the PV product) is applied on
    EVERY path: in-kernel PRNG on the fused Pallas paths (the mask is
    regenerated from a per-step seed in the backward and never
    materializes), jax.random on the dense path."""

    def __init__(self, units, num_heads, dropout=0.0, use_flash=True,
                 causal=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError("units must divide num_heads")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self._use_flash = use_flash
        self._attn_drop = dropout
        self.qkv = nn.Dense(3 * units, flatten=False, in_units=units)
        self.out_proj = nn.Dense(units, flatten=False, in_units=units)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x, mask=None, valid_length=None):
        # x: (B, L, C)
        from .. import ndarray as F
        from ..ops import flash_attention_nd
        from ..ops.flash_attention import (flash_attention_packed_nd,
                                          use_packed_attention)
        B, L, C = x.shape
        H = self._heads
        D = C // H
        with part("project"):
            qkv = self.qkv(x)                  # (B, L, 3C)
        from .. import autograd as _ag
        drop = self._attn_drop if _ag.is_training() else 0.0
        if self._use_flash and mask is None and use_packed_attention(
                B, L, H, D, causal=self._causal,
                has_vl=valid_length is not None,
                dtype=str(qkv.dtype), has_dropout=drop > 0):
            # packed path: q/k/v stay in the projection's (B*L, H*D)
            # layout — no head/seq transposes in the whole program
            with part("attend"):
                qkv2 = qkv.reshape(B * L, 3 * C)
                out2 = flash_attention_packed_nd(
                    qkv2[:, :C], qkv2[:, C:2 * C], qkv2[:, 2 * C:], B, H,
                    causal=self._causal, valid_length=valid_length,
                    dropout=drop)
            with part("project"):
                return self.out_proj(out2.reshape(B, L, C))
        with part("attend"):
            qkv = qkv.reshape(B, L, 3, H, D)
            q = qkv[:, :, 0].transpose((0, 2, 1, 3))   # (B, H, L, D)
            k = qkv[:, :, 1].transpose((0, 2, 1, 3))
            v = qkv[:, :, 2].transpose((0, 2, 1, 3))
            if self._use_flash and mask is None:
                # length masks ride the fused kernel (O(L) memory) instead
                # of a materialized (B, L, L) additive mask
                out = flash_attention_nd(q, k, v, causal=self._causal,
                                         valid_length=valid_length,
                                         dropout=drop)
            else:
                if mask is None and valid_length is not None:
                    mask = length_mask(F, L, valid_length)
                scores = F.batch_dot(q.reshape(B * H, L, D),
                                     k.reshape(B * H, L, D),
                                     transpose_b=True) / math.sqrt(D)
                if mask is not None:
                    # mask: (B, L) 1=valid
                    m = mask.reshape(B, 1, 1, L)
                    scores = scores.reshape(B, H, L, L) + (1 - m) * -1e30
                    scores = scores.reshape(B * H, L, L)
                att = F.softmax(scores, axis=-1)
                att = self.dropout(att)
                out = F.batch_dot(att, v.reshape(B * H, L, D))
                out = out.reshape(B, H, L, D)
            out = out.transpose((0, 2, 1, 3)).reshape(B, L, C)
        with part("project"):
            return self.out_proj(out)

    # -- incremental decode (docs/SERVING.md "Generative serving") ---------
    def prefill(self, x, valid_length=None):
        """Prompt pass of the KV-cached decode path.

        Runs causal self-attention over the whole prompt and returns
        ``(out (B, L, C), k (B, H, L, D), v (B, H, L, D))`` — the K/V the
        caller scatters into its cache slots.  Math is the dense-score
        formulation (fp32 softmax) so :meth:`decode_step` continues the
        SAME numerics: prefill+decode vs a full re-forward agree to float
        tolerance, not bit identity (the full forward may ride the fused
        flash kernels)."""
        import jax
        import jax.numpy as jnp
        from ..ndarray.ndarray import NDArray, unwrap
        B, L, C = x.shape
        H = self._heads
        D = C // H
        with part("project"):
            qkv = unwrap(self.qkv(x)).reshape(B, L, 3, H, D)
            q = jnp.transpose(qkv[:, :, 0], (0, 2, 1, 3))   # (B, H, L, D)
            k = jnp.transpose(qkv[:, :, 1], (0, 2, 1, 3))
            v = jnp.transpose(qkv[:, :, 2], (0, 2, 1, 3))
        with part("attend"):
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
            mask = jnp.tril(jnp.ones((L, L), bool))[None, None]
            if valid_length is not None:
                vl = unwrap(valid_length).astype(jnp.int32)
                mask = mask & (jnp.arange(L)[None, None, None, :]
                               < vl[:, None, None, None])
            scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
            att = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
            out = jnp.transpose(out, (0, 2, 1, 3)).reshape(B, L, C)
        with part("project"):
            return self.out_proj(NDArray(out)), NDArray(k), NDArray(v)

    def decode_step(self, x, k_cache, v_cache, position, active=None):
        """One token per sequence against a ring-buffer KV cache.

        ``x``: (B, 1, C) current-token activations; ``k_cache`` /
        ``v_cache``: (B, H, M, D) ring buffers; ``position``: (B,) int32
        — the sequence index of THIS token (== tokens already cached).
        The new K/V land at ``position % M`` and attention covers the
        ``min(position + 1, M)`` resident entries — past wraparound that
        is a sliding window over the last M tokens (softmax is
        order-invariant, so ring order never matters).  ``active``:
        optional (B,) 0/1 write gate — inactive rows (freed slots riding
        a fixed-shape decode batch) attend but never write, so a freed
        slot cannot scribble on a neighbour's future prompt.

        Returns ``(out (B, 1, C), k_cache', v_cache')``."""
        import jax
        import jax.numpy as jnp
        from ..ndarray.ndarray import NDArray, unwrap
        B, _, C = x.shape
        H = self._heads
        D = C // H
        with part("project"):
            qkv = unwrap(self.qkv(x)).reshape(B, 3, H, D)
            q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # (B, H, D)
        kc = unwrap(k_cache)
        vc = unwrap(v_cache)
        pos = unwrap(position).astype(jnp.int32)
        M = kc.shape[2]
        with part("ring_write"):
            write = jax.nn.one_hot(pos % M, M, dtype=kc.dtype)     # (B, M)
            if active is not None:
                write = write * unwrap(active).astype(kc.dtype)[:, None]
            w = write[:, None, :, None]
            kc = kc * (1 - w) + k_new[:, :, None, :].astype(kc.dtype) * w
            vc = vc * (1 - w) + v_new[:, :, None, :].astype(vc.dtype) * w
        with part("attend"):
            n_valid = jnp.minimum(pos + 1, M)                      # (B,)
            mask = jnp.arange(M)[None, :] < n_valid[:, None]       # (B, M)
            scores = jnp.einsum("bhd,bhmd->bhm", q, kc) / math.sqrt(D)
            scores = jnp.where(mask[:, None, :], scores.astype(jnp.float32),
                               -1e30)
            att = jax.nn.softmax(scores, axis=-1).astype(vc.dtype)
            out = jnp.einsum("bhm,bhmd->bhd", att, vc).reshape(B, 1, C)
        with part("project"):
            return self.out_proj(NDArray(out)), NDArray(kc), NDArray(vc)

    hybrid_forward = None


class PositionwiseFFN(HybridBlock):
    """Dense -> activation -> Dense -> Dropout (GluonNLP shape).

    On TPU the erf-GELU path dispatches to the fused Pallas FFN kernel
    (ops/ffn_fused.py): both matmuls + GELU + output dropout in one kernel,
    backward recomputes nothing and keeps the hidden-state gradients in
    VMEM.  Set ``MXNET_FUSED_FFN=0`` to force the layer path."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 **kwargs):
        super().__init__(**kwargs)
        self.ffn_1 = nn.Dense(hidden_size, flatten=False, in_units=units)
        self.ffn_2 = nn.Dense(units, flatten=False, in_units=hidden_size)
        self._act_kind = activation
        self._rate = dropout
        self.act = nn.Activation(activation) if activation != "gelu" \
            else nn.GELU()
        self.dropout = nn.Dropout(dropout)

    def forward(self, x):
        import os
        if self._act_kind in ("gelu", "relu") and x.ndim == 3 \
                and os.environ.get("MXNET_FUSED_FFN", "1") == "1" \
                and str(x.dtype) in ("bfloat16", "float32"):
            from ..ops.ffn_fused import ffn_gelu_nd, use_fused_ffn
            w1, b1 = self.ffn_1.weight, self.ffn_1.bias
            w2, b2 = self.ffn_2.weight, self.ffn_2.bias
            B, L, C = x.shape
            from .. import autograd as _ag
            drop = self._rate if _ag.is_training() else 0.0
            # weight dtype must match the activation dtype: the compile
            # probe builds x AND weights in str(x.dtype), so a mixed
            # fp32-params/bf16-activations config would pass the probe yet
            # fail inside the kernel at the first real step
            from ..base import dtype_name
            if b1 is not None and b2 is not None \
                    and w1.shape and w1.shape[-1] == C \
                    and dtype_name(w1.dtype) == str(x.dtype) \
                    and dtype_name(w2.dtype) == str(x.dtype) \
                    and use_fused_ffn(B, L, C, w1.shape[0], str(x.dtype),
                                      act=self._act_kind, dropout=drop):
                return ffn_gelu_nd(x, w1.data(), b1.data(),
                                   w2.data(), b2.data(),
                                   dropout=self._rate, act=self._act_kind)
        return self.dropout(self.ffn_2(self.act(self.ffn_1(x))))

    hybrid_forward = None


def apply_residual_ln(ln, x, inner, rate, dropout_layer):
    """``ln(x + dropout(inner))`` — the post-LN transformer glue, fused
    into one Pallas pass per direction on TPU (ops/residual_ln.py);
    falls back to the layer composition anywhere else.
    ``MXNET_FUSED_RESLN=0`` forces the layer path."""
    import os
    if os.environ.get("MXNET_FUSED_RESLN", "1") == "1" \
            and x.ndim == 3 and str(x.dtype) in ("bfloat16", "float32"):
        from ..ops.residual_ln import residual_ln_nd, use_residual_ln
        from .. import autograd as _ag
        B, L, C = x.shape
        drop = rate if _ag.is_training() else 0.0
        # probe-vs-runtime dtype guard: the probe compiles with gamma/beta
        # in their REAL dtype (AMP keeps LN params fp32 while activations
        # are bf16 — the kernel handles the mix, so it must stay
        # dispatched there; r5 briefly hard-gated on dtype equality and
        # lost the 8% BERT res-LN win)
        from ..base import dtype_name
        if ln.gamma.shape and ln.gamma.shape[0] == C \
                and use_residual_ln(B, L, C, str(x.dtype), dropout=drop,
                                    param_dtype=dtype_name(ln.gamma.dtype)):
            return residual_ln_nd(x, inner, ln.gamma.data(),
                                  ln.beta.data(), dropout=rate,
                                  eps=ln._eps)
    # rate == 0 callers (the FFN glue: the FFN already applied its own
    # output dropout) must NOT run the layer dropout again
    return ln(x + (dropout_layer(inner) if rate > 0 else inner))


class TransformerEncoderLayer(HybridBlock):
    """Post-LN transformer layer (BERT convention).

    On TPU the two ``ln(x + dropout(inner))`` glue chains dispatch to the
    fused residual+dropout+LN Pallas op (ops/residual_ln.py) — one HBM
    pass per direction instead of XLA's separate mask/add/stats/apply
    passes.  ``MXNET_FUSED_RESLN=0`` forces the layer path."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 use_flash=True, causal=False, **kwargs):
        super().__init__(**kwargs)
        self.attention = MultiHeadAttention(units, num_heads, dropout,
                                            use_flash=use_flash,
                                            causal=causal)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout)
        self.ln1 = nn.LayerNorm(in_channels=units, epsilon=1e-12)
        self.ln2 = nn.LayerNorm(in_channels=units, epsilon=1e-12)
        self._rate = dropout
        self.dropout = nn.Dropout(dropout)

    def _res_ln(self, ln, x, inner, rate):
        return apply_residual_ln(ln, x, inner, rate, self.dropout)

    # A device trace files each half of the layer, its residual add and
    # its post-norm with it, under its part: ``mx.attention`` (the
    # attention block names ``project`` / ``attend`` / ``ring_write``
    # inside it) and ``mx.ffn``.
    def _ffn_part(self, x):
        # the FFN applies its own output dropout (in-kernel on the fused
        # path), so the second glue runs with rate 0
        with part("ffn"):
            return self._res_ln(self.ln2, x, self.ffn(x), 0.0)

    def forward(self, x, mask=None, valid_length=None):
        with part("attention"):
            x = self._res_ln(self.ln1, x,
                             self.attention(x, mask, valid_length),
                             self._rate)
        return self._ffn_part(x)

    # -- incremental decode ------------------------------------------------
    def prefill(self, x, valid_length=None):
        """Prompt pass: returns ``(out, k, v)`` — the attention K/V of
        this layer for the caller's cache (docs/SERVING.md)."""
        with part("attention"):
            att, k, v = self.attention.prefill(x, valid_length)
            x = self._res_ln(self.ln1, x, att, self._rate)
        return self._ffn_part(x), k, v

    def decode_step(self, x, k_cache, v_cache, position, active=None):
        """One cached decode hop; returns ``(out, k_cache', v_cache')``."""
        with part("attention"):
            att, kc, vc = self.attention.decode_step(
                x, k_cache, v_cache, position, active=active)
            x = self._res_ln(self.ln1, x, att, self._rate)
        return self._ffn_part(x), kc, vc

    hybrid_forward = None


class BERTEncoder(HybridBlock):
    """Transformer encoder stack.

    NOTE: although this block OWNS ``position_weight``, it does NOT add
    position embeddings or apply the embedding LayerNorm — ``BERTModel``
    does both in HF order (embed + position -> LN -> dropout) before
    calling the encoder.  Standalone users must add positions themselves
    (e.g. ``x + enc.position_weight.data()[:L]``)."""

    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, max_length=512, dropout=0.1, use_flash=True,
                 remat=False, causal=False, **kwargs):
        super().__init__(**kwargs)
        self._max_length = max_length
        self._units = units
        self.position_weight = Parameter(
            "position_weight", shape=(max_length, units), init=init.Normal(0.02))
        self.dropout = nn.Dropout(dropout)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            layer = TransformerEncoderLayer(
                units, hidden_size, num_heads, dropout, use_flash=use_flash,
                causal=causal)
            if remat:
                # per-layer gradient checkpointing: with flash attention this
                # is what makes long-context large-batch pretraining fit
                layer.remat()
            self.layers.add(layer)

    def forward(self, x, mask=None, valid_length=None):
        # position add + LN happen in BERTModel (HF/gluon-nlp embedding
        # order); the encoder owns dropout + the layer stack
        with part("embed"):
            x = self.dropout(x)
        for layer in self.layers._children.values():
            x = layer(x, mask, valid_length)
        return x

    # -- incremental decode ------------------------------------------------
    def prefill(self, x, valid_length=None):
        """Prompt pass over the stack: ``(out, [(k, v), ...])`` with one
        (B, H, L, D) K/V pair per layer (a ``causal=True`` stack — the
        GPT-style decoder-only configuration)."""
        kvs = []
        for layer in self.layers._children.values():
            x, k, v = layer.prefill(x, valid_length)
            kvs.append((k, v))
        return x, kvs

    def decode_step(self, x, caches, position, active=None):
        """One cached decode hop over the stack.  ``caches``: per-layer
        ``(k_cache, v_cache)`` ring buffers; returns ``(out, caches')``."""
        new = []
        for layer, (kc, vc) in zip(self.layers._children.values(), caches):
            x, kc, vc = layer.decode_step(x, kc, vc, position,
                                          active=active)
            new.append((kc, vc))
        return x, new

    hybrid_forward = None


class BERTModel(HybridBlock):
    """Embeddings + encoder + pooler + MLM/NSP heads (GluonNLP BERTModel)."""

    def __init__(self, vocab_size=30522, token_type_vocab_size=2,
                 num_layers=12, units=768, hidden_size=3072, num_heads=12,
                 max_length=512, dropout=0.1, use_pooler=True,
                 use_decoder=True, use_classifier=True, use_flash=True,
                 remat=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units,
                                       weight_initializer=init.Normal(0.02))
        self.token_type_embed = nn.Embedding(
            token_type_vocab_size, units, weight_initializer=init.Normal(0.02))
        self.embed_ln = nn.LayerNorm(in_channels=units, epsilon=1e-12)
        self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                   max_length, dropout, use_flash=use_flash,
                                   remat=remat)
        self.pooler = nn.Dense(units, activation="tanh", flatten=False,
                               in_units=units) if use_pooler else None
        if use_decoder:
            self.decoder_transform = nn.Dense(units, flatten=False,
                                              in_units=units)
            self.decoder_act = nn.GELU()
            self.decoder_ln = nn.LayerNorm(in_channels=units, epsilon=1e-12)
            self.decoder_bias = Parameter("decoder_bias", shape=(vocab_size,),
                                          init=init.Zero())
        else:
            self.decoder_transform = None
        self.classifier = nn.Dense(2, flatten=False, in_units=units) \
            if use_classifier else None

    def forward(self, inputs, token_types=None, valid_length=None,
                masked_positions=None):
        from .. import ndarray as F
        with part("embed"):
            seq = self.word_embed(inputs)
            if token_types is not None:
                seq = seq + self.token_type_embed(token_types)
            # BERT order (HF + gluon-nlp): word + token_type + position,
            # THEN the embedding LayerNorm — required for pretrained-weight
            # compatibility (tools/convert_weights.py)
            L = seq.shape[1]
            seq = seq + self.encoder.position_weight.data()[:L] \
                .reshape(1, L, self._units)
            seq = self.embed_ln(seq)
        # length masking rides the fused attention kernels directly (no
        # materialized (B, L) -> (B, L, L) additive mask; reference builds
        # one in gluon-nlp BERTModel._encode_sequence)
        out = self.encoder(seq, None, valid_length)
        with part("head"):
            return self._heads(F, out, masked_positions)

    def _heads(self, F, out, masked_positions):
        """The pooler, the NSP classifier and the weight-tied MLM decoder
        on the encoder's output: ``mx.head`` in a device trace."""
        results = [out]
        if self.pooler is not None:
            pooled = self.pooler(out[:, 0])
            results.append(pooled)
            if self.classifier is not None:
                results.append(self.classifier(pooled))
        if self.decoder_transform is not None and masked_positions is not None:
            # gather masked positions: (B, M)
            B, L, C = out.shape
            M = masked_positions.shape[1]
            pos = masked_positions.astype("int32")
            gathered = F.take(out.reshape(B * L, C),
                              (F.arange(0, B).reshape(-1, 1) * L + pos)
                              .reshape(-1), axis=0)
            h = self.decoder_ln(self.decoder_act(
                self.decoder_transform(gathered)))
            # weight-tied MLM head: h @ word_embed.T + bias (MXU matmul).
            # LayerNorm emits fp32; cast h to the embedding dtype so the
            # (M, vocab) logits stay bf16 (an fp32 head matmul runs at the
            # 1/4 MXU rate and doubles the largest write of the step —
            # the fused CE does its own fp32 math on the fly)
            wemb = self.word_embed.weight.data()
            logits = F.FullyConnected(
                h.astype(wemb.dtype), wemb, self.decoder_bias.data(),
                num_hidden=0, flatten=False)
            results.append(logits.reshape(B, M, -1))
        return tuple(results) if len(results) > 1 else results[0]

    hybrid_forward = None


class BERTPretrainingLoss(HybridBlock):
    """MLM + NSP joint loss (GluonNLP BERTForPretraining loss)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        from ..gluon.loss import SoftmaxCrossEntropyLoss
        # MLM uses the fused nd.softmax_cross_entropy (see forward)
        self.nsp_loss = SoftmaxCrossEntropyLoss()

    def forward(self, mlm_logits, nsp_logits, mlm_labels, mlm_weights,
                nsp_labels):
        from .. import ndarray as F
        B, M, V = mlm_logits.shape
        with part("loss"):
            # fused CE: fp32 math internally, no (B*M, V) log-softmax ever
            # materialized — pass the logits in their storage dtype (bf16)
            per_tok = F.softmax_ce_loss(mlm_logits.reshape(B * M, V),
                                        mlm_labels.reshape(-1),
                                        mlm_weights.reshape(-1))
            denom = F.sum(mlm_weights) + 1e-6
            mlm = F.sum(per_tok) / denom
            nsp = F.mean(self.nsp_loss(nsp_logits, nsp_labels))
            return mlm + nsp

    hybrid_forward = None


def bert_base(vocab_size=30522, max_length=512, dropout=0.1, **kwargs):
    return BERTModel(vocab_size=vocab_size, num_layers=12, units=768,
                     hidden_size=3072, num_heads=12, max_length=max_length,
                     dropout=dropout, **kwargs)


def bert_large(vocab_size=30522, max_length=512, dropout=0.1, **kwargs):
    return BERTModel(vocab_size=vocab_size, num_layers=24, units=1024,
                     hidden_size=4096, num_heads=16, max_length=max_length,
                     dropout=dropout, **kwargs)


def bert_sharding_rules(tp_axis="model"):
    """Megatron-style TP rules for :func:`mxnet_tpu.parallel.shard_params`:
    QKV/FFN-in column-parallel, out-proj/FFN-out row-parallel, embeddings
    vocab-sharded."""
    return [
        (r"qkv\.weight$", (tp_axis, None)),
        (r"qkv\.bias$", (tp_axis,)),
        (r"ffn_1\.weight$", (tp_axis, None)),
        (r"ffn_1\.bias$", (tp_axis,)),
        (r"out_proj\.weight$", (None, tp_axis)),
        (r"ffn_2\.weight$", (None, tp_axis)),
        (r"word_embed\.weight$", (tp_axis, None)),
    ]
