"""The post-LN transformer layer of Vaswani et al. 2017 as BERT (Devlin et
al. 2018) and GPT-1 (Radford et al. 2018) use it, in plain float32
``jax.numpy``: no kernels, no cache, no batching tricks.  Callers run it
under ``jax.default_matmul_precision("highest")``; on a TPU a float32
product is otherwise rounded to bfloat16 passes.

Weights come in a dict under the names the program's blocks give them
(``<prefix>attention.qkv.weight`` ...), each ``(out, in)`` as MXNet's
``Dense`` stores them, the fused QKV rows ordered q, k, v."""
import math

import jax
import jax.numpy as jnp


def dense(x, p, name):
    return x @ p[name + ".weight"].T + p[name + ".bias"]


def layer_norm(x, p, name, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p[name + ".gamma"] \
        + p[name + ".beta"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def self_attention(x, p, prefix, heads, causal):
    b, l, c = x.shape
    d = c // heads
    qkv = dense(x, p, prefix + "qkv").reshape(b, l, 3, heads, d)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d)     # (b, h, l, l)
    if causal:
        keep = jnp.tril(jnp.ones((l, l), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v               # (b, h, l, d)
    out = out.transpose(0, 2, 1, 3).reshape(b, l, c)
    return dense(out, p, prefix + "out_proj")


def encoder_layer(x, p, prefix, heads, causal, eps):
    """x -> LN(x + attention(x)) -> LN(. + FFN(.)), no dropout."""
    x = layer_norm(x + self_attention(x, p, prefix + "attention.", heads,
                                      causal), p, prefix + "ln1", eps)
    h = dense(gelu(dense(x, p, prefix + "ffn.ffn_1")), p,
              prefix + "ffn.ffn_2")
    return layer_norm(x + h, p, prefix + "ln2", eps)


_layer = jax.jit(encoder_layer, static_argnums=(2, 3, 4, 5))
project = jax.jit(dense, static_argnums=2)


def encoder(x, p, layers, heads, causal, eps):
    for i in range(layers):
        # one compiled layer, called with each layer's own weights
        sub = {k.split(".", 3)[3]: v for k, v in p.items()
               if k.startswith(f"encoder.layers.{i}.")}
        x = _layer(x, sub, "", heads, causal, eps)
    return x
