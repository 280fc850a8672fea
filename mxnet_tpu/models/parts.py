"""What the decoder families written as pure functions of a dict of raw
weights share (``deepseek.py``, ``lfm2.py``): the norm, the product, the
rotation, the weights of one layer, and the initializer of a served model
built from a seed.  Weights are stored [in, out]; norms and rotations are
float32 inside whatever the activations are."""
from __future__ import annotations

import functools

import numpy as onp

from .. import initializer as init
from .. import random as _random

__all__ = ["rms_norm", "matmul", "rope", "sub_weights", "FanInNormal"]


def rms_norm(x, g, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 / jnp.sqrt(ms + eps) * g.astype(jnp.float32)).astype(x.dtype)


def matmul(x, w):
    """x @ w, accumulated in float32, in x's type."""
    import jax.numpy as jnp
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def rope(x, cos, sin, interleaved):
    """Rotate the last axis of ``x`` by the angles behind ``cos`` / ``sin``
    ([..., dim / 2], broadcast against x): pairs are (2i, 2i + 1) if
    ``interleaved`` else (i, i + dim / 2)."""
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    if interleaved:
        a, b = x32[..., 0::2], x32[..., 1::2]
    else:
        a, b = jnp.split(x32, 2, axis=-1)
    ra, rb = a * cos - b * sin, a * sin + b * cos
    if interleaved:
        out = jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    else:
        out = jnp.concatenate([ra, rb], axis=-1)
    return out.astype(x.dtype)


def sub_weights(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


@functools.lru_cache(maxsize=None)
def _normal_maker(shape, dtype, sigma):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda key: (jax.random.normal(key, shape, jnp.float32)
                                * sigma).astype(dtype))


class FanInNormal(init.Initializer):
    """Normal of standard deviation ``sigma``, or ``fan_in ** -0.5`` of a
    matrix stored [..., in, out], so that every product keeps its input's
    scale.  Made in one jitted program a shape: no float32 copy of a
    bfloat16 stack of experts."""

    def __init__(self, sigma=None):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, name, shape, dtype):
        sigma = self.sigma or shape[-2] ** -0.5
        return _normal_maker(tuple(shape), str(onp.dtype(dtype)),
                             float(sigma))(_random.next_key())
