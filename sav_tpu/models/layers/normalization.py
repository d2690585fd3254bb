"""LayerScale (CaiT; /root/reference/models/layers/normalizations/layerscale.py:5-23) and RMSNorm (the decoder family)."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any


class LayerScaleBlock(nn.Module):
    """Per-channel learned scale on a residual branch, initialized to ``eps``."""

    eps: float = 1e-4
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        dim = inputs.shape[-1]
        scale = self.param("scale", nn.initializers.constant(self.eps), (dim,))
        return inputs * scale.astype(inputs.dtype)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``: no mean subtraction, no bias.
    Statistics in float32 whatever the input's dtype; the result is cast to
    ``dtype``. With ``offset`` the stored weight is the scale's distance from
    1 (leaf ``offset``, zero at the start): ``x / rms(x) * (1 + w)``, the form
    of the checkpoints whose weight decay pulls a norm's scale toward 1."""

    eps: float = 1e-6
    offset: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        if self.offset:
            scale = 1.0 + self.param("offset", nn.initializers.zeros, (inputs.shape[-1],))
        else:
            scale = self.param("scale", nn.initializers.ones, (inputs.shape[-1],))
        x = inputs.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)
