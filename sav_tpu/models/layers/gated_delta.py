"""The gated delta-rule block: linear attention with a recurrent state
(arXiv:2412.06464), as the hybrid decoders interleave it with softmax
attention.

For ``H_k`` key heads of ``d_k`` feeding ``H`` value heads of ``d_v``
(``H_k`` divides ``H``)::

    [q | k | v | z] = x W_qkvz     # laid out BY KEY HEAD: for each of the H_k,
                                   # [q d_k | k d_k | v (H/H_k) d_v | z (H/H_k) d_v]
    [b | a] = x W_ba               # by key head too: [b H/H_k | a H/H_k]
    [q | k | v] = silu(conv([q | k | v]))   # causal, depthwise, width 4, no bias
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)        # float32
    o = gated_delta_rule_from_raw(q, k, v, g, beta)  # sav_tpu/ops/gated_delta.py, whose rule_operands computes
    #   q, k = l2norm(q), l2norm(k);  q = q d_k^-0.5
    y = W_o (RMSNorm_{d_v}(o) w silu(z))            # per value head; w is plain

Scopes, for the readers of a trace: the two input projections under
``to_qkv`` (``qkvz``, ``ba``), the output merge ``to_out``; the work between
them under ``gdn/conv`` (the convolution and its SiLU), ``gdn/rule`` (the
normalisation of q and k, the gates and the rule) and ``gdn/gate_norm``. The
module's name holds no ``SelfAttentionBlock``: the attention readers pass it
by.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.models.layers.causal_conv import (  # noqa: F401
    KERNEL_INIT, causal_conv_silu, causal_depthwise_conv, conv_silu_by_key_head, key_head_parts,
)
from sav_tpu.models.layers.feedforward import _bias_free_dense
from sav_tpu.ops.gated_delta import CHUNK, gated_delta_rule_from_raw, l2_normalise  # noqa: F401
from sav_tpu.ops.quant import QuantDenseGeneral

Dtype = Any


def split_by_key_head(qkvz: jax.Array, ba: jax.Array, key_heads: int, key_ch: int, heads: int, value_ch: int):
    """The fused projections' outputs ``[..., H_k (2 d_k + 2 r d_v)]`` and
    ``[..., H_k 2 r]`` (``r = H / H_k``) -> ``q, k [..., H_k, d_k]``, ``v, z
    [..., H, d_v]``, ``b, a [..., H]``: value heads ``j r .. (j + 1) r - 1``
    are key head ``j``'s. The layout written out; the block reads q, k and v
    through the convolution (:func:`~sav_tpu.models.layers.causal_conv.
    conv_silu_by_key_head`), which splits by the same rule."""
    lead = qkvz.shape[:-1]
    q, k, v, z = key_head_parts(qkvz, key_heads, key_ch, heads // key_heads * value_ch)
    q, k = (t.reshape(lead + (key_heads, key_ch)) for t in (q, k))
    v, z = (t.reshape(lead + (heads, value_ch)) for t in (v, z))
    return (q, k, v, z) + split_gates(ba, key_heads, heads)


def split_gates(ba: jax.Array, key_heads: int, heads: int) -> tuple:
    """``[..., H_k 2 r]``, a key head's ``[b r | a r]`` -> ``b, a [..., H]``."""
    lead = ba.shape[:-1]
    b, a = jnp.split(ba.reshape(lead + (key_heads, 2 * heads // key_heads)), 2, axis=-1)
    return b.reshape(lead + (heads,)), a.reshape(lead + (heads,))


class _InputProj(nn.Module):
    key_heads: int
    key_ch: int
    heads: int
    value_ch: int
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, x: jax.Array):
        dense = _bias_free_dense(self.quant, self.dtype)
        group = self.heads // self.key_heads
        qkvz = dense(self.key_heads * 2 * (self.key_ch + group * self.value_ch), name="qkvz")(x)
        return qkvz, dense(2 * self.heads, name="ba")(x)


class _CausalConv(nn.Module):
    """:func:`conv_silu_by_key_head` with its ``[W, C]`` kernel (no bias) over
    ``C``, the q, k and v among ``qkvz``'s channels: ``q, k, v, z``, flat."""

    width: int
    key_heads: int
    key_ch: int
    value_ch: int  # a key head's: r d_v

    @nn.compact
    def __call__(self, qkvz: jax.Array):
        channels = self.key_heads * (2 * self.key_ch + self.value_ch)
        kernel = self.param("kernel", KERNEL_INIT, (self.width, channels))
        return conv_silu_by_key_head(qkvz, kernel, self.key_heads, self.key_ch, self.value_ch)


class _GatedNorm(nn.Module):
    """``RMSNorm(o) w act(z)`` over a value head's lanes, float32 inside
    (``act``: SiLU here, a sigmoid in the vector-decay block)."""

    eps: float
    dtype: Dtype
    activation: Any = nn.silu

    @nn.compact
    def __call__(self, o: jax.Array, z: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (o.shape[-1],))

        @jax.checkpoint  # float32 inside; the backward pass starts from o and z as they came
        def gated(o, z, scale):
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps)
            return (o * scale * self.activation(z.astype(jnp.float32))).astype(self.dtype)

        return gated(o, z, scale)


def _decay_rates(key, shape):
    """``A_log`` at the start: ``log A``, ``A`` uniform in (0, 16] (the
    published modelling code's)."""
    return jnp.log(jax.random.uniform(key, shape, minval=1e-4, maxval=16.0))


class GatedDeltaNetBlock(nn.Module):
    """See the module docstring. Returns ``(y, stats)``; ``stats`` holds the
    smallest ``exp(g_t)`` of the call (``decay_min``) and the largest RMS of
    any head's final state (``state_rms_max``), float32 scalars without a
    gradient."""

    key_heads: int
    heads: int  # value heads
    key_ch: int
    value_ch: int
    conv_width: int = 4
    norm_eps: float = 1e-6
    chunk: int = CHUNK
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array):
        batch, seq, _ = inputs.shape
        qkvz, ba = _InputProj(
            self.key_heads, self.key_ch, self.heads, self.value_ch, self.quant, self.dtype, name="to_qkv"
        )(inputs)
        b, a = split_gates(ba, self.key_heads, self.heads)
        a_log = self.param("A_log", _decay_rates, (self.heads,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (self.heads,))

        with jax.named_scope("gdn/conv"):
            conv = _CausalConv(
                self.conv_width, self.key_heads, self.key_ch, self.heads // self.key_heads * self.value_ch, name="conv"
            )
            *qkv, z = conv(qkvz)
            q, k, v = (checkpoint_name(t, "gdn_conv") for t in qkv)
            q, k = (t.reshape(batch, seq, self.key_heads, self.key_ch) for t in (q, k))
            v, z = (t.reshape(batch, seq, self.heads, self.value_ch) for t in (v, z))
        with jax.named_scope("gdn/rule"):
            beta = jax.nn.sigmoid(b.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
            out, state, least = gated_delta_rule_from_raw(q, k, v, g, beta, self.chunk)
            out = checkpoint_name(out, "gdn_out")
            stats = jax.lax.stop_gradient({
                "decay_min": jnp.exp(least),
                "state_rms_max": jnp.sqrt(jnp.max(jnp.mean(jnp.square(state), axis=(-2, -1)))),
            })
        with jax.named_scope("gdn/gate_norm"):
            out = _GatedNorm(self.norm_eps, self.dtype, name="gate_norm")(out, z)
        dense = functools.partial(QuantDenseGeneral, mode=self.quant) if self.quant else nn.DenseGeneral
        out = dense(
            features=inputs.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype, name="to_out"
        )(out)
        return checkpoint_name(out, "attn_out"), stats
