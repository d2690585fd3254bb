"""Feed-forward blocks: transformer MLP, the gated (SwiGLU) MLP and CeiT's
locally-enhanced FF.

Reference: FFBlock (/root/reference/models/layers/feedforwards/ff.py:8-34),
LeFFBlock (/root/reference/models/layers/feedforwards/leff.py:9-63).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.models.layers.depthwise import DepthwiseConv2D
from sav_tpu.ops.quant import QuantDense

Dtype = Any


class FFBlock(nn.Module):
    """Dense(expand) → act → dropout → Dense(in_ch) → dropout."""

    expand_ratio: Optional[float] = 4.0
    hidden_ch: Optional[int] = None
    dropout_rate: float = 0.0
    activation_fn: Callable = nn.gelu
    use_bias: bool = True
    # int8 quantized dots ("int8" QAT / "int8_serve") — both FFN
    # matmuls route through sav_tpu/ops/quant.py; None = plain nn.Dense.
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, is_training: bool) -> jax.Array:
        in_ch = inputs.shape[-1]
        hidden = self.hidden_ch or int(in_ch * self.expand_ratio)
        dense = (
            functools.partial(QuantDense, mode=self.quant)
            if self.quant else nn.Dense
        )
        x = dense(hidden, use_bias=self.use_bias, dtype=self.dtype, name="fc1")(inputs)
        x = self.activation_fn(x)
        x = nn.Dropout(rate=self.dropout_rate)(x, deterministic=not is_training)
        x = dense(in_ch, use_bias=self.use_bias, dtype=self.dtype, name="fc2")(x)
        x = nn.Dropout(rate=self.dropout_rate)(x, deterministic=not is_training)
        return x


def _bias_free_dense(quant: Optional[str], dtype):
    return functools.partial(
        functools.partial(QuantDense, mode=quant) if quant else nn.Dense,
        use_bias=False,
        dtype=dtype,
    )


def clamped_gate_up(gate: jax.Array, up: jax.Array, limit: float, activation_fn: Callable = nn.silu) -> jax.Array:
    """``act(gate) * up``, and where ``limit`` > 0 ``act(min(gate, limit)) *
    clip(up, -limit, limit)``: the clamped SwiGLU of the public configs'
    ``*_swiglu_limit_list`` (the gate held from above, the linear branch from
    both sides). ``limit`` 0 is the plain product, the same program as before
    the clamp existed."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return activation_fn(gate) * up


class _GateUp(nn.Module):
    """The gated MLP's input matmuls as two children of one scope (``fc1``):
    ``act(x W_gate) * (x W_up)`` (:func:`clamped_gate_up` at ``limit``)."""

    hidden_ch: int
    activation_fn: Callable
    quant: Optional[str]
    dtype: Dtype
    limit: float = 0.0

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        dense = _bias_free_dense(self.quant, self.dtype)
        gate = checkpoint_name(dense(self.hidden_ch, name="gate")(inputs), "ffn_gate")
        up = checkpoint_name(dense(self.hidden_ch, name="up")(inputs), "ffn_up")
        return clamped_gate_up(gate, up, self.limit, self.activation_fn)


class GatedFFBlock(nn.Module):
    """``W_down(act(W_gate x) * W_up x)``, no bias (SwiGLU with ``silu``).
    Scopes as :class:`FFBlock`'s: the input matmuls under ``fc1``, the down
    projection ``fc2``. The three matmuls' outputs carry ``checkpoint_name``
    tags (``ffn_gate``, ``ffn_up``, ``ffn_out``) for a caller's remat policy;
    without one they are the identity. ``limit`` > 0 clamps the two branches
    (:func:`clamped_gate_up`)."""

    hidden_ch: int
    activation_fn: Callable = nn.silu
    quant: Optional[str] = None  # as FFBlock.quant
    dtype: Dtype = jnp.float32
    limit: float = 0.0

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        x = _GateUp(
            self.hidden_ch, self.activation_fn, self.quant, self.dtype, self.limit, name="fc1"
        )(inputs)
        out = _bias_free_dense(self.quant, self.dtype)(inputs.shape[-1], name="fc2")(x)
        return checkpoint_name(out, "ffn_out")


class LeFFBlock(nn.Module):
    """CeiT locally-enhanced feed-forward.

    Splits the CLS token off, expands patch tokens, re-grids them to √L×√L,
    applies a depthwise conv (default 5×5), projects back, and re-concats the
    CLS token. BatchNorm after each stage as in the reference (leff.py:39-59).
    """

    expand_ratio: Optional[float] = 4.0
    hidden_ch: Optional[int] = None
    kernel_size: tuple[int, int] = (5, 5)
    activation_fn: Callable = nn.gelu
    # int8 quantized expand/project dots; the depthwise conv and the
    # BatchNorms stay in ``dtype`` (conv is not a projection/FFN dot).
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, is_training: bool) -> jax.Array:
        in_ch = inputs.shape[-1]
        hidden = self.hidden_ch or int(in_ch * self.expand_ratio)
        dense = (
            functools.partial(QuantDense, mode=self.quant)
            if self.quant else nn.Dense
        )
        cls_tok, tokens = inputs[:, :1], inputs[:, 1:]
        b, l, _ = tokens.shape
        side = int(round(l**0.5))
        if side * side != l:
            raise ValueError(f"LeFF requires a square token grid, got {l} tokens")

        norm = lambda name: nn.BatchNorm(
            use_running_average=not is_training, momentum=0.9, dtype=self.dtype, name=name
        )
        x = dense(hidden, dtype=self.dtype, name="expand")(tokens)
        x = self.activation_fn(norm("bn1")(x))
        x = x.reshape(b, side, side, hidden)
        # Shifted-FMA depthwise (param-compatible with the nn.Conv grouped
        # form; see layers/depthwise.py for why not feature_group_count).
        x = DepthwiseConv2D(
            features=hidden,
            kernel_size=self.kernel_size,
            dtype=self.dtype,
            name="dwconv",
        )(x)
        x = self.activation_fn(norm("bn2")(x))
        x = x.reshape(b, l, hidden)
        x = dense(in_ch, dtype=self.dtype, name="project")(x)
        x = self.activation_fn(norm("bn3")(x))
        return jnp.concatenate([cls_tok, x], axis=1)
