"""The double-gated short convolution: a token mixer that is neither
attention nor a recurrence, as the convolution-attention hybrid decoders
interleave it with grouped-query softmax attention::

    [B | C | x~] = x W_in                   # D -> 3 D, no bias
    u = B * x~
    c_t = sum_{j < W} w_j u_{t - (W - 1) + j}     # depthwise, causal, width W (3)
    y = (C * c) W_out                       # D -> D; no activation anywhere

The block is cubic in its input and has no norm inside: ``stats`` reports the
largest RMS of any sequence's ``C * c``. Position ``t`` reads positions ``t - W + 1 .. t`` only; a
decode step would carry the last ``W - 1`` rows of ``u`` (``serve/`` holds no
such state yet).

Scopes, for the readers of a trace: the input projection under ``to_qkv``
(leaf ``in_proj``), the two gates and the convolution under ``sconv/core``
(forward, recomputed and backward: :func:`~sav_tpu.models.layers.causal_conv.
gated_causal_conv_of_thirds`), the output projection under ``to_out`` (leaf
``out_proj``). The module's name holds no ``SelfAttentionBlock``: the
attention readers pass it by.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.models.layers.causal_conv import KERNEL_INIT, gated_causal_conv_of_thirds
from sav_tpu.models.layers.feedforward import _bias_free_dense

Dtype = Any


class _Proj(nn.Module):
    """One bias-free matmul (leaf ``leaf``) under a scope of its own."""

    features: int
    leaf: str
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return _bias_free_dense(self.quant, self.dtype)(self.features, name=self.leaf)(x)


class _ConvKernel(nn.Module):
    """The ``[W, C]`` depthwise kernel (no bias)."""

    width: int

    @nn.compact
    def __call__(self, channels: int) -> jax.Array:
        return self.param("kernel", KERNEL_INIT, (self.width, channels))


class ShortConvBlock(nn.Module):
    """See the module docstring. Returns ``(y, stats)``; ``stats`` holds the
    largest RMS of any sequence's ``C * c`` (``out_rms_max``), a float32
    scalar without a gradient. Tags for a caller's remat policy: ``sconv_in`` (the input
    projection's result), ``sconv_core`` (``C * c``), ``attn_out`` (the
    block's result, the name every token mixer gives its own)."""

    conv_width: int = 3
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array):
        dim = inputs.shape[-1]
        gates = _Proj(3 * dim, "in_proj", self.quant, self.dtype, name="to_qkv")(inputs)
        gates = checkpoint_name(gates, "sconv_in")  # [B | C | x~], read where it lies
        kernel = _ConvKernel(self.conv_width, name="conv")(dim)
        with jax.named_scope("sconv/core"):
            mixed = checkpoint_name(gated_causal_conv_of_thirds(gates, kernel), "sconv_core")
            square = jnp.square(jax.lax.stop_gradient(mixed).astype(jnp.float32))
            rms = jnp.sqrt(jnp.max(jnp.mean(square, axis=(1, 2))))
        out = _Proj(dim, "out_proj", self.quant, self.dtype, name="to_out")(mixed)
        return checkpoint_name(out, "attn_out"), {"out_rms_max": rms}
