"""The causal depthwise convolution over the sequence, in one home: the gated
delta-rule block reads it fused with a SiLU (:func:`causal_conv_silu`), the
short-convolution block between two gates (:func:`gated_causal_conv`).

``y_t = sum_i kernel[i] x_{t - (W - 1) + i}`` a channel: position ``t`` reads
``t - W + 1 .. t`` and zeros before the sequence starts. No bias. Both fused
forms write their backward pass out: what JAX transposes from the forward is
a padded float32 copy a tap (see :func:`causal_conv_silu`).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

# A ``[W, C]`` kernel at ``W ** -0.5`` a tap.
KERNEL_INIT = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0, out_axis=1)


def causal_depthwise_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``y_t = sum_i kernel[i] x_{t - (W - 1) + i}`` a channel, on ``x [B, S,
    C]`` with ``kernel [W, C]``: position ``t`` reads ``t - W + 1 .. t`` and
    zeros before the sequence starts. Summed in float32 from taps that are
    slices of ``x`` padded once, in its own dtype."""
    width, seq = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    taps = (padded[:, i:i + seq].astype(jnp.float32) * kernel[i].astype(jnp.float32) for i in range(width))
    return functools.reduce(jnp.add, taps)


def _input_gradient(dy: jax.Array, kernel: jax.Array) -> jax.Array:
    """``dx_s = sum_i kernel[i] dy_{s + W - 1 - i}`` from float32 ``dy``
    padded once at its end."""
    width, seq = kernel.shape[0], dy.shape[1]
    ahead = jnp.pad(dy, ((0, 0), (0, width - 1), (0, 0)))
    return functools.reduce(
        jnp.add,
        (ahead[:, width - 1 - i:width - 1 - i + seq] * kernel[i].astype(jnp.float32) for i in range(width)),
    )


def _kernel_gradient(dy: jax.Array, x: jax.Array, width: int) -> jax.Array:
    """``dkernel[i] = sum dy_t x_{t - W + 1 + i}`` from float32 ``dy``."""
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jnp.stack([jnp.sum(dy * padded[:, i:i + seq].astype(jnp.float32), axis=(0, 1)) for i in range(width)])


@jax.custom_vjp
def causal_conv_silu(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``silu(causal_depthwise_conv(x, kernel))`` in ``x``'s dtype, with the
    backward pass written out: it computes the float32 sums again from ``x``
    (SiLU's derivative reads them), ``dx_s = sum_i kernel[i] dy_{s + W - 1 -
    i}`` from ``dy`` padded once at its end, and ``dkernel[i] = sum dy_t x_{t
    - W + 1 + i}``, each one fusion over the operands. What JAX transposes
    from the forward is a padded float32 copy a tap: compiled for a v5e at
    ``[4, 4096, 8192]`` the pass and its gradient move 1.6 GB and hold 0.54 GB
    beside their operands this way, 7.3 GB and 1.34 GB that way."""
    return nn.silu(causal_depthwise_conv(x, kernel)).astype(x.dtype)


def _causal_conv_silu_fwd(x, kernel):
    return causal_conv_silu(x, kernel), (x, kernel)


def _causal_conv_silu_bwd(residuals, g):
    x, kernel = residuals
    y = causal_depthwise_conv(x, kernel)
    gate = jax.nn.sigmoid(y)
    dy = g.astype(jnp.float32) * gate * (1.0 + y * (1.0 - gate))  # d silu(y) / dy
    dx = _input_gradient(dy, kernel)
    dkernel = _kernel_gradient(dy, x, kernel.shape[0])
    return dx.astype(x.dtype), dkernel.astype(kernel.dtype)


causal_conv_silu.defvjp(_causal_conv_silu_fwd, _causal_conv_silu_bwd)


@jax.custom_vjp
def gated_causal_conv(b: jax.Array, c: jax.Array, x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``c * causal_depthwise_conv(b * x, kernel)`` on ``[B, S, C]`` operands,
    in their dtype: the product ``b * x`` is formed in that dtype, the
    convolution's sums and the outer gate in float32. No activation. The
    backward pass is written out as :func:`causal_conv_silu`'s: it forms ``b *
    x`` and the sums again and keeps nothing but the four operands."""
    return (c.astype(jnp.float32) * causal_depthwise_conv(b * x, kernel)).astype(x.dtype)


def _gated_causal_conv_fwd(b, c, x, kernel):
    return gated_causal_conv(b, c, x, kernel), (b, c, x, kernel)


def _gated_causal_conv_bwd(residuals, g):
    b, c, x, kernel = residuals
    u = b * x
    g = g.astype(jnp.float32)
    dc = g * causal_depthwise_conv(u, kernel)
    dconv = g * c.astype(jnp.float32)
    du = _input_gradient(dconv, kernel)
    db, dx = du * x.astype(jnp.float32), du * b.astype(jnp.float32)
    dkernel = _kernel_gradient(dconv, u, kernel.shape[0])
    return db.astype(b.dtype), dc.astype(c.dtype), dx.astype(x.dtype), dkernel.astype(kernel.dtype)


gated_causal_conv.defvjp(_gated_causal_conv_fwd, _gated_causal_conv_bwd)
