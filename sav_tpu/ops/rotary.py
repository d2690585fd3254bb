"""Fixed sinusoidal and rotary position embeddings.

Working rebuild of the reference's broken rotary path
(/root/reference/models/layers/position_embed.py:8-45 — undefined ``self.dim``,
malformed ``10e4 ** intervals / dim`` frequency formula; SURVEY.md §2.9 #12).
Frequencies here follow the standard RoPE formulation
``inv_freq_i = base ** (-2i / dim)`` (``base`` 10,000 unless given).

Two pairings of the lanes: the vision zoo's :func:`rotate_every_two` pairs
lane ``2i`` with ``2i + 1``; the decoder family's :func:`rotate_half` pairs
lane ``i`` with ``i + dim/2`` (the layout of the public language-model
checkpoints), with tables from :func:`half_split_tables`. Latent attention
rotates adjacent pairs of a slice of the head at its own base, in float32,
on a key that may have no head axis: :func:`apply_rotary_interleaved`, with
YaRN's blended frequencies (arXiv:2309.00071) where the public config's
``rope_scaling`` group is given.
"""

from __future__ import annotations

import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context ``factor`` times the
    original: ``0.1 mscale ln(factor) + 1`` (1 at ``factor`` <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_scale(scaling: Optional[Mapping]) -> float:
    """What the softmax scale of a head is multiplied by under
    ``scaling``: ``yarn_mscale(factor, mscale_all_dim) ** 2``."""
    return yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2 if scaling else 1.0


def yarn_inv_freq(dim: int, base, scaling: Mapping) -> jax.Array:
    """``[dim/2]`` float32 inverse frequencies under YaRN: pair ``i`` turns
    ``original_max_position_embeddings base ** (-2i / dim) / 2 pi`` times over
    the original context. Pairs that turn ``beta_fast`` times or more keep the
    base's frequency, those that turn ``beta_slow`` times or fewer take it
    over ``factor``, and between the two pair indices (floor of the first,
    ceiling of the second) the two are blended linearly in the index."""
    kind = scaling.get("type") or scaling.get("rope_type", "yarn")
    if kind != "yarn":
        raise ValueError(f"rotary scaling {kind!r} is not implemented: yarn alone")
    original = scaling["original_max_position_embeddings"]

    def pair_index(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_index(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_index(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    plain = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def _angles(seq_len: int, dim: int, base, scaling: Optional[Mapping] = None) -> jax.Array:
    """``[seq_len, dim/2]`` float32: position times ``base ** (-2i / dim)``
    (times :func:`yarn_inv_freq` under ``scaling``)."""
    if dim % 2 != 0:
        raise ValueError(f"rotary dim must be even, got {dim}")
    if scaling:
        inv_freq = yarn_inv_freq(dim, base, scaling)
    else:
        inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    return jnp.einsum("i,j->ij", t, inv_freq)


def fixed_positional_embedding(seq_len: int, dim: int, dtype=jnp.float32):
    """Sinusoidal (sin, cos) tables of shape ``[seq_len, dim]`` each.

    Each frequency is repeated twice along the feature axis so the tables
    align with :func:`rotate_every_two` pairing.
    """
    freqs = jnp.repeat(_angles(seq_len, dim, 10000), 2, axis=-1)  # [L, dim]
    return jnp.sin(freqs).astype(dtype), jnp.cos(freqs).astype(dtype)


def rotate_every_two(x: jax.Array) -> jax.Array:
    """``(x0, x1, x2, x3, ...) -> (-x1, x0, -x3, x2, ...)`` along the last axis."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    out = jnp.stack([-x2, x1], axis=-1)
    return out.reshape(x.shape)


def apply_rotary_pos_emb(x: jax.Array, sincos) -> jax.Array:
    """Apply RoPE to ``x: [..., seq_len, dim]`` (or ``[..., seq_len, heads, dim]``).

    ``sincos``: pair of ``[seq_len, dim]`` tables from
    :func:`fixed_positional_embedding`.
    """
    sin, cos = sincos
    if x.ndim == 4:  # [B, L, H, D] — broadcast over heads
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    sin = sin.astype(x.dtype)
    cos = cos.astype(x.dtype)
    return x * cos + rotate_every_two(x) * sin


def half_split_tables(seq_len: int, dim: int, base: float = 10000.0, scaling: Optional[Mapping] = None):
    """Float32 ``(sin, cos)`` tables ``[seq_len, dim]`` for :func:`rotate_half`
    pairing: frequency ``i`` sits at lanes ``i`` and ``i + dim/2``. Under
    ``scaling`` (a public config's YaRN group) the frequencies are
    :func:`yarn_inv_freq`'s and both tables are multiplied by the group's
    ``attention_factor`` (``0.1 ln(factor) + 1`` where it gives none): the
    convention of the configs that spell the key so, the temperature on the
    tables and not on the softmax scale (:func:`yarn_softmax_scale` is the
    other convention's)."""
    freqs = _angles(seq_len, dim, base, scaling)
    freqs = jnp.concatenate([freqs, freqs], axis=-1)  # [L, dim]
    sin, cos = jnp.sin(freqs), jnp.cos(freqs)
    if scaling:
        factor = scaling.get("attention_factor") or yarn_mscale(scaling["factor"], 1.0)
        sin, cos = sin * factor, cos * factor
    return sin, cos


def rotate_half(x: jax.Array) -> jax.Array:
    """``(a, b) -> (-b, a)`` for the two halves of the last axis."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def apply_rotary_half(x: jax.Array, sincos) -> jax.Array:
    """RoPE with the rotate-halves pairing on ``x: [B, seq_len, heads, dim]``.

    The rotation runs in float32 and is cast back: at position 4,095 a bf16
    cosine is off by 2**-9 of a turn's amplitude, which the float32
    reference would see on every logit.
    """
    sin, cos = sincos
    sin, cos = sin[None, :, None, :], cos[None, :, None, :]
    x32 = x.astype(jnp.float32)
    return (x32 * cos + rotate_half(x32) * sin).astype(x.dtype)


def apply_rotary_interleaved(x: jax.Array, base: float, scaling: Optional[Mapping] = None) -> jax.Array:
    """RoPE with the adjacent-pairs pairing ``(2i, 2i + 1)`` at ``base`` on the
    whole last axis of ``x: [B, seq_len, dim]`` or ``[B, seq_len, heads, dim]``
    (pass the slice of the head that rotates). Float32 inside, cast back, for
    :func:`apply_rotary_half`'s reason. ``scaling`` is the public config's
    ``rope_scaling`` group (YaRN): blended frequencies, and cos and sin times
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    freqs = jnp.repeat(_angles(x.shape[1], x.shape[-1], base, scaling), 2, axis=-1)  # [L, dim]
    sin, cos = jnp.sin(freqs), jnp.cos(freqs)
    if scaling:
        amplitude = yarn_mscale(scaling["factor"], scaling["mscale"]) / yarn_mscale(
            scaling["factor"], scaling["mscale_all_dim"]
        )
        if amplitude != 1.0:
            sin, cos = sin * amplitude, cos * amplitude
    if x.ndim == 4:
        sin, cos = sin[:, None, :], cos[:, None, :]
    x32 = x.astype(jnp.float32)
    return (x32 * cos + rotate_every_two(x32) * sin).astype(x.dtype)
