"""Decoder-only causal language model — the generative-serving workload.

``TransformerLM`` reuses the BERT encoder family with ``causal=True``
(GPT shape: learned positions + causal transformer stack + tied-width
vocab projection).  It exposes the three entry points the generation
runtime (``mxnet_tpu.serving.generate``) compiles:

* :meth:`forward` — full causal re-forward over a whole sequence.  This
  is the **parity referee**: KV-cached decode must reproduce its logits
  to float tolerance (``tests/test_generate.py``).
* :meth:`prefill` — one pass over the prompt returning next-token logits
  plus the per-layer K/V to scatter into cache slots.
* :meth:`decode_step` — one token per sequence against per-layer
  ``(B, H, M, D)`` ring-buffer caches.
"""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..gluon import nn
from .. import initializer as init
from ..telemetry import part
from .bert import BERTEncoder

__all__ = ["TransformerLM", "tiny_lm"]


class TransformerLM(HybridBlock):
    """Causal transformer LM over a ``causal=True`` :class:`BERTEncoder`.

    ``max_length`` bounds the learned position table: generation beyond
    it clamps to the last position row (the KV ring buffer's sliding
    window is the real context bound — docs/SERVING.md)."""

    def __init__(self, vocab_size=256, num_layers=2, units=64,
                 hidden_size=128, num_heads=4, max_length=256, dropout=0.0,
                 use_flash=True, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._vocab = vocab_size
        self._max_length = max_length
        self.embed = nn.Embedding(vocab_size, units,
                                  weight_initializer=init.Normal(0.02))
        self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                   num_heads, max_length, dropout,
                                   use_flash=use_flash, causal=True)
        self.proj = nn.Dense(vocab_size, flatten=False, in_units=units)

    @property
    def num_layers(self):
        return len(self.encoder.layers._children)

    @property
    def num_heads(self):
        first = next(iter(self.encoder.layers._children.values()))
        return first.attention._heads

    @property
    def units(self):
        return self._units

    def cache_spec(self, max_len):
        """What the generation engine keeps for this model: for each layer
        the ``(kind, trailing shape, dtype)`` of its rings, a key and a
        value ring of (H, M, D) float32 (docs/SERVING.md)."""
        H = self.num_heads
        shape = (H, int(max_len), self._units // H)
        return [[("key", shape, "float32"), ("value", shape, "float32")]
                for _ in range(self.num_layers)]

    def _embed(self, tokens):
        """Token + learned-position embedding for a left-aligned batch."""
        with part("embed"):
            x = self.embed(tokens)
            L = x.shape[1]
            return x + self.encoder.position_weight.data()[:L] \
                .reshape(1, L, self._units)

    def _head(self, x):
        with part("head"):
            return self.proj(x)

    def forward(self, tokens, valid_length=None):
        """Full causal forward: (B, L) ids -> (B, L, vocab) logits."""
        x = self._embed(tokens)
        return self._head(self.encoder(x, None, valid_length))

    hybrid_forward = None

    # -- incremental decode ------------------------------------------------
    def prefill(self, tokens, valid_length=None):
        """Prompt pass: (B, L) ids -> ``(logits (B, L, vocab), kvs)``
        with one (B, H, L, D) K/V pair per layer for the caller's cache."""
        x = self._embed(tokens)
        out, kvs = self.encoder.prefill(x, valid_length)
        return self._head(out), kvs

    def decode_step(self, tokens, caches, position, active=None):
        """One token per sequence: (B,) ids at (B,) positions against the
        per-layer ring caches.  Returns ``(logits (B, vocab), caches')``."""
        import jax.numpy as jnp
        from ..ndarray.ndarray import NDArray, unwrap
        with part("embed"):
            tok = unwrap(tokens).reshape(-1)
            B = tok.shape[0]
            pos = unwrap(position).astype(jnp.int32)
            x = unwrap(self.embed(NDArray(tok.reshape(B, 1))))
            # positions past the learned table clamp to its last row — the
            # ring buffer (not this table) is the true context bound
            pw = unwrap(self.encoder.position_weight.data())
            penc = jnp.take(pw, jnp.clip(pos, 0, self._max_length - 1),
                            axis=0)[:, None, :]
            x = NDArray(x + penc.astype(x.dtype))
        out, caches = self.encoder.decode_step(x, caches, position,
                                               active=active)
        with part("head"):
            return NDArray(unwrap(self.proj(out))[:, 0]), caches


def tiny_lm(vocab_size=128, **kwargs):
    """Small CPU-friendly config for tests and benchmarks."""
    cfg = dict(num_layers=2, units=64, hidden_size=128, num_heads=4,
               max_length=256, dropout=0.0)
    cfg.update(kwargs)
    return TransformerLM(vocab_size=vocab_size, **cfg)
