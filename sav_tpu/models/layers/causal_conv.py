"""The causal depthwise convolution over the sequence, in one home: the gated
delta-rule block reads it fused with a SiLU (:func:`causal_conv_silu`), the
short-convolution block between two gates (:func:`gated_causal_conv`).

``y_t = sum_i kernel[i] x_{t - (W - 1) + i}`` a channel: position ``t`` reads
``t - W + 1 .. t`` and zeros before the sequence starts. No bias. Both fused
forms write their backward pass out: what JAX transposes from the forward is
a padded float32 copy a tap (see :func:`causal_conv_silu`).

Each fused form is one of two programs, which ``ops/causal_conv.py::conv_form``
picks from the backend and the shapes (the choice is a record of the dispatch
log, ``ops/attention.py::snapshot_dispatch_log``): ``kernel``, a Pallas call a
direction that holds a block of rows in VMEM and touches HBM once an operand
(a TPU, channels of whole lane tiles, rows of whole 16-row tiles), or ``xla``,
the functions written out below (everything else, and what the tests hold the
kernels to).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from sav_tpu.ops import _backend
from sav_tpu.ops import attention as _attention
from sav_tpu.ops import causal_conv as _kernels

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


def _form(fused: str, operand: jax.Array, kernel: jax.Array) -> dict:
    """:func:`~sav_tpu.ops.causal_conv.conv_form`'s choice for taps over ``[B,
    S, C]`` of ``operand``'s rows and dtype, noted in the dispatch log."""
    (batch, seq, _), (width, channels) = operand.shape, kernel.shape
    form = _kernels.conv_form(seq, channels, width, operand.dtype)
    _attention.log_conv_form(fused, (batch, seq, channels), width, jnp.dtype(operand.dtype).name, form)
    return form


def _tiles(form: dict) -> dict:
    return {"block_s": form["block_s"], "block_c": form["block_c"], "interpret": _backend.default_interpret()}


def causal_conv_silu(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``silu(causal_depthwise_conv(x, kernel))`` in ``x``'s dtype, with the
    backward pass written out, as the kernels of ``ops/causal_conv.py`` or as
    :func:`_conv_silu_xla`: :func:`~sav_tpu.ops.causal_conv.conv_form` says
    which."""
    form = _form("silu", x, kernel)
    if form["conv"] == "kernel":
        return _conv_silu_in_vmem(x, kernel, **_tiles(form))
    return _conv_silu_xla(x, kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_silu_in_vmem(x, kernel, block_s: int, block_c: int, interpret: bool):
    return _kernels.conv_silu_forward(x, kernel, block_s, block_c, interpret)


def _conv_silu_in_vmem_fwd(x, kernel, block_s, block_c, interpret):
    return _conv_silu_in_vmem(x, kernel, block_s, block_c, interpret), (x, kernel)


def _conv_silu_in_vmem_bwd(block_s, block_c, interpret, residuals, g):
    return _kernels.conv_silu_backward(*residuals, g, block_s, block_c, interpret)


_conv_silu_in_vmem.defvjp(_conv_silu_in_vmem_fwd, _conv_silu_in_vmem_bwd)


def conv_silu_by_key_head(qkvz: jax.Array, kernel: jax.Array, key_heads: int, key_ch: int, value_ch: int):
    """:func:`causal_conv_silu` over the q, k and v of the delta-rule block's
    input projection, ``qkvz [B, S, H_k (2 d_k + 2 r d_v)]`` laid out by key
    head (``[q d_k | k d_k | v r d_v | z r d_v]`` each; ``value_ch = r d_v``),
    with ``kernel [W, (all q | all k | all v)]`` -> ``q, k [B, S, H_k d_k]``,
    ``v, z [B, S, H_k r d_v]``, z as it came. Where the kernels run and a key
    head's parts are whole lane tiles they read the projection where it lies
    and write its gradient whole (``reads: in_place`` in the dispatch log's
    record): no join of q, k and v before the taps, no split after. Otherwise
    the parts are split off, joined and handed to :func:`causal_conv_silu`."""
    channels = key_heads * (2 * key_ch + value_ch)
    form = _kernels.conv_form(qkvz.shape[1], channels, kernel.shape[0], qkvz.dtype, key_head=(key_ch, value_ch))
    if form.get("reads") == "in_place":
        _attention.log_conv_form(
            "silu", qkvz.shape[:-1] + (channels,), kernel.shape[0], jnp.dtype(qkvz.dtype).name, form)
        return _conv_silu_of_key_heads(
            qkvz, kernel, key_heads, key_ch, value_ch, form["block_s"], _backend.default_interpret())
    return conv_silu_joined(causal_conv_silu, qkvz, kernel, key_heads, key_ch, value_ch)


def conv_silu_joined(conv, qkvz: jax.Array, kernel: jax.Array, key_heads: int, key_ch: int, value_ch: int):
    """:func:`conv_silu_by_key_head` with q, k and v split off ``qkvz``,
    joined for ``conv`` (a silu form over ``[B, S, C]``) and split again."""
    q, k, v, z = key_head_parts(qkvz, key_heads, key_ch, value_ch)
    mixed = conv(jnp.concatenate([q, k, v], axis=-1), kernel)
    return (*jnp.split(mixed, [key_heads * key_ch, 2 * key_heads * key_ch], axis=-1), z)


def key_head_parts(qkvz: jax.Array, key_heads: int, key_ch: int, value_ch: int) -> list:
    """``[..., H_k (q d_k | k d_k | v r d_v | z r d_v)] -> q, k [..., H_k d_k],
    v, z [..., H_k r d_v]`` (``value_ch = r d_v``)."""
    by_head = qkvz.reshape(qkvz.shape[:-1] + (key_heads, 2 * key_ch + 2 * value_ch))
    parts = jnp.split(by_head, [key_ch, 2 * key_ch, 2 * key_ch + value_ch], axis=-1)
    return [t.reshape(qkvz.shape[:-1] + (-1,)) for t in parts]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _conv_silu_of_key_heads(qkvz, kernel, key_heads: int, key_ch: int, value_ch: int, block_s: int, interpret: bool):
    q, k, v = _kernels.key_head_conv_silu_forward(qkvz, kernel, key_heads, key_ch, value_ch, block_s, interpret)
    # z as whole lane tiles cut out and joined: a reshape by key head would have XLA lay all of qkvz out anew.
    stride = qkvz.shape[-1] // key_heads
    z = [qkvz[..., (head + 1) * stride - value_ch:(head + 1) * stride] for head in range(key_heads)]
    return q, k, v, jnp.concatenate(z, axis=-1)


def _conv_silu_of_key_heads_fwd(qkvz, kernel, *sizes):
    return _conv_silu_of_key_heads(qkvz, kernel, *sizes), (qkvz, kernel)


def _conv_silu_of_key_heads_bwd(key_heads, key_ch, value_ch, block_s, interpret, residuals, g):
    return _kernels.key_head_conv_silu_backward(*residuals, *g, key_heads, key_ch, value_ch, block_s, interpret)


_conv_silu_of_key_heads.defvjp(_conv_silu_of_key_heads_fwd, _conv_silu_of_key_heads_bwd)


@jax.custom_vjp
def _conv_silu_xla(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """:func:`causal_conv_silu` as XLA's program. The backward
    computes the float32 sums again from ``x``
    (SiLU's derivative reads them), ``dx_s = sum_i kernel[i] dy_{s + W - 1 -
    i}`` from ``dy`` padded once at its end, and ``dkernel[i] = sum dy_t x_{t
    - W + 1 + i}``, each one fusion over the operands. What JAX transposes
    from the forward is a padded float32 copy a tap: compiled for a v5e at
    ``[4, 4096, 8192]`` the pass and its gradient move 1.6 GB and hold 0.54 GB
    beside their operands this way, 7.3 GB and 1.34 GB that way."""
    return nn.silu(causal_depthwise_conv(x, kernel)).astype(x.dtype)


def _conv_silu_xla_fwd(x, kernel):
    return _conv_silu_xla(x, kernel), (x, kernel)


def _conv_silu_xla_bwd(residuals, g):
    x, kernel = residuals
    y = causal_depthwise_conv(x, kernel)
    gate = jax.nn.sigmoid(y)
    dy = g.astype(jnp.float32) * gate * (1.0 + y * (1.0 - gate))  # d silu(y) / dy
    dx = _input_gradient(dy, kernel)
    dkernel = _kernel_gradient(dy, x, kernel.shape[0])
    return dx.astype(x.dtype), dkernel.astype(kernel.dtype)


_conv_silu_xla.defvjp(_conv_silu_xla_fwd, _conv_silu_xla_bwd)


def gated_causal_conv(b: jax.Array, c: jax.Array, x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``c * causal_depthwise_conv(b * x, kernel)`` on ``[B, S, C]`` operands,
    in their dtype: :func:`gated_causal_conv_of_thirds` of the three joined (a
    join XLA's program sees through)."""
    return gated_causal_conv_of_thirds(jnp.concatenate([b, c, x], axis=-1), kernel)


def gated_causal_conv_of_thirds(gates: jax.Array, kernel: jax.Array) -> jax.Array:
    """``c * causal_depthwise_conv(b * x, kernel)`` on ``gates = [b | c | x]``,
    ``[B, S, 3 C]`` as the short-convolution block's input projection leaves
    it, in its dtype: the product ``b * x`` is formed in that dtype, the
    convolution's sums and the outer gate in float32. No activation. The
    backward pass is written out as :func:`causal_conv_silu`'s: it forms ``b *
    x`` and the sums again and keeps nothing but ``gates`` and the kernel; the
    gradient is one ``[B, S, 3 C]`` array again. As the kernels of
    ``ops/causal_conv.py``, which read the thirds where they lie, or as
    :func:`_gated_conv_xla`."""
    if gates.shape[-1] != 3 * kernel.shape[1]:
        raise ValueError(f"gated convolution: gates {gates.shape} beside a kernel {kernel.shape}")
    form = _form("gated", gates, kernel)
    if form["conv"] == "kernel":
        return _gated_conv_in_vmem(gates, kernel, **_tiles(form))
    return _gated_conv_xla(gates, kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gated_conv_in_vmem(gates, kernel, block_s: int, block_c: int, interpret: bool):
    return _kernels.gated_conv_forward(gates, kernel, block_s, block_c, interpret)


def _gated_conv_in_vmem_fwd(gates, kernel, block_s, block_c, interpret):
    return _gated_conv_in_vmem(gates, kernel, block_s, block_c, interpret), (gates, kernel)


def _gated_conv_in_vmem_bwd(block_s, block_c, interpret, residuals, g):
    return _kernels.gated_conv_backward(*residuals, g, block_s, block_c, interpret)


_gated_conv_in_vmem.defvjp(_gated_conv_in_vmem_fwd, _gated_conv_in_vmem_bwd)


@jax.custom_vjp
def _gated_conv_xla(gates: jax.Array, kernel: jax.Array) -> jax.Array:
    """:func:`gated_causal_conv_of_thirds` as XLA's program."""
    b, c, x = jnp.split(gates, 3, axis=-1)
    return (c.astype(jnp.float32) * causal_depthwise_conv(b * x, kernel)).astype(x.dtype)


def _gated_conv_xla_fwd(gates, kernel):
    return _gated_conv_xla(gates, kernel), (gates, kernel)


def _gated_conv_xla_bwd(residuals, g):
    gates, kernel = residuals
    b, c, x = jnp.split(gates, 3, axis=-1)
    u = b * x
    g = g.astype(jnp.float32)
    dc = g * causal_depthwise_conv(u, kernel)
    dconv = g * c.astype(jnp.float32)
    du = _input_gradient(dconv, kernel)
    db, dx = du * x.astype(jnp.float32), du * b.astype(jnp.float32)
    dkernel = _kernel_gradient(dconv, u, kernel.shape[0])
    dgates = jnp.concatenate([db.astype(gates.dtype), dc.astype(gates.dtype), dx.astype(gates.dtype)], axis=-1)
    return dgates, dkernel.astype(kernel.dtype)


_gated_conv_xla.defvjp(_gated_conv_xla_fwd, _gated_conv_xla_bwd)
