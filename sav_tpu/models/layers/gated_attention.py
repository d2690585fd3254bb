"""Grouped-query softmax attention, gated or not, over the whole prefix or a
window of it: the hybrid decoders' softmax layer.

``H`` query heads of ``D`` read ``H_kv`` key/value heads (``H_kv`` divides
``H``; query head ``h`` reads key/value head ``h // (H / H_kv)``)::

    [query | gate] = x W_q          # gate "lane": by head [query D | gate D]
    gate = x W_g                    # gate "head": W_g [D_model, H], one a head
    k = x W_k;  v = x W_v           # H_kv heads
    query, k = RMSNorm_D(query), RMSNorm_D(k)       # per head, one weight each
    the first ``rotary_ch`` lanes of query and k rotate (lane i with i +
    rotary_ch / 2) at ``rope_theta``, under ``rope_scaling`` (a YaRN group)
    at its blended frequencies with both tables times its
    ``attention_factor``; the others pass
    out = softmax(query k^T D^-0.5 + mask) v    # j <= i; with ``window`` also j > i - window
    y = W_o (out sigmoid(gate))     # a head's gate multiplies its D lanes

Its sizes tell the family's form. ``gate`` is a granularity: ``"lane"`` (a
gate a lane and token, the other half of ``W_q``; ``True`` reads so),
``"head"`` (a gate a head and token from a projection of its own, leaf
``to_qkv/gate``, the head-wise form of arXiv:2505.06708) or none (``None``,
``False``: ``y = W_o out``); ``rotary_ch`` equal to ``D`` turns the whole
head; ``norm_offset`` says whether the two norms store their weight as the
offset from 1 or plainly; ``window`` makes it a sliding-window layer. No bias
anywhere. Scopes: the module's own name holds ``SelfAttentionBlock``; the
input projections (the head-wise gate's too), the two norms and the rotary
lie under ``to_qkv``, the output merge is ``to_out``, so the readers of a
trace see it as any other attention block; the core runs under
``attn/window`` or ``attn/full`` by its kind, so a trace tells the two kinds
of layer of one decoder apart.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.models.layers.feedforward import _bias_free_dense
from sav_tpu.models.layers.normalization import RMSNorm
from sav_tpu.ops.attention import dot_product_attention
from sav_tpu.ops.quant import QuantDenseGeneral
from sav_tpu.ops.rotary import apply_rotary_half, half_split_tables

Dtype = Any


def gate_granularity(gate) -> Optional[str]:
    """``gate`` as one of ``None``, ``"lane"``, ``"head"`` (a bool is the
    older spelling of none or a gate a lane)."""
    if isinstance(gate, bool):
        return "lane" if gate else None
    if gate not in (None, "lane", "head"):
        raise ValueError(f"attention gate {gate!r}: None, 'lane' or 'head'")
    return gate


def rotate_leading_lanes(x: jax.Array, rotary_ch: int, theta: float, scaling: Optional[Mapping] = None) -> jax.Array:
    """Rotary on the first ``rotary_ch`` lanes of ``x [B, S, H, D]`` with the
    rotate-halves pairing inside them (``scaling``: YaRN's tables,
    ``ops/rotary.py::half_split_tables``); the other lanes pass."""
    tables = half_split_tables(x.shape[1], rotary_ch, theta, scaling)
    if rotary_ch == x.shape[-1]:
        return apply_rotary_half(x, tables)
    return jnp.concatenate([apply_rotary_half(x[..., :rotary_ch], tables), x[..., rotary_ch:]], axis=-1)


class _GatedQKVProj(nn.Module):
    """``x -> (query [B, S, H, D], k [B, S, H_kv, D], v the same, gate [B, S,
    H, D], [B, S, H, 1] or None)``, normed and rotated."""

    num_heads: int
    kv_heads: int
    head_ch: int
    rotary_ch: int
    gate: Optional[str]
    norm_offset: bool
    rope_theta: float
    rope_scaling: Optional[Any]
    norm_eps: float
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, x: jax.Array):
        b, s, _ = x.shape
        h, kv, d = self.num_heads, self.kv_heads, self.head_ch
        dense = _bias_free_dense(self.quant, self.dtype)

        def norm(name):
            return RMSNorm(eps=self.norm_eps, offset=self.norm_offset, dtype=self.dtype, name=name)

        if self.gate == "lane":
            query, gate = jnp.split(dense(h * 2 * d, name="q")(x).reshape(b, s, h, 2 * d), 2, axis=-1)
        else:
            query, gate = dense(h * d, name="q")(x).reshape(b, s, h, d), None
        if self.gate == "head":
            gate = dense(h, name="gate")(x)[..., None]
        key = dense(kv * d, name="k")(x).reshape(b, s, kv, d)
        value = dense(kv * d, name="v")(x).reshape(b, s, kv, d)
        rotary = (self.rotary_ch, self.rope_theta, self.rope_scaling)
        query = rotate_leading_lanes(norm("q_norm")(query), *rotary)
        key = rotate_leading_lanes(norm("k_norm")(key), *rotary)
        return query, key, value, gate


class GatedSelfAttentionBlock(nn.Module):
    """Causal grouped-query self-attention, over the whole prefix or the last
    ``window`` positions of it, with a sigmoid gate on its output where
    ``gate`` names a granularity; see the module docstring. Returns ``(y,
    stats)``; ``stats`` holds the mean of the gate, a float32 scalar without a
    gradient, under ``gate_mean`` and, for a head-wise gate, also under the
    layer's kind (``gate_mean_window`` | ``gate_mean_full``); nothing without
    a gate."""

    num_heads: int
    kv_heads: int
    head_ch: int
    rotary_ch: int
    gate: Any = "lane"  # None | "lane" | "head" (False, True: the first two)
    norm_offset: bool = True
    rope_theta: float = 10000.0
    rope_scaling: Optional[Mapping] = None  # a YaRN group, with its attention_factor on the tables
    window: Optional[int] = None  # a sliding-window layer's positions
    norm_eps: float = 1e-6
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array):
        granularity = gate_granularity(self.gate)
        query, key, value, gate = _GatedQKVProj(
            num_heads=self.num_heads,
            kv_heads=self.kv_heads,
            head_ch=self.head_ch,
            rotary_ch=self.rotary_ch,
            gate=granularity,
            norm_offset=self.norm_offset,
            rope_theta=self.rope_theta,
            rope_scaling=dict(self.rope_scaling) if self.rope_scaling else None,
            norm_eps=self.norm_eps,
            quant=self.quant,
            dtype=self.dtype,
            name="to_qkv",
        )(inputs)
        query, key, value, gate = (
            t if t is None else checkpoint_name(t, "attn_qkv") for t in (query, key, value, gate)
        )
        kind = "full" if self.window is None else "window"
        with jax.named_scope(f"attn/{kind}"):
            out = dot_product_attention(
                query,
                key,
                value,
                scale=self.head_ch ** -0.5,
                backend=self.backend,
                logits_dtype=self.logits_dtype or self.dtype,
                causal=True,
                window=self.window,
            )
        stats = {}
        if granularity:
            opened = jax.nn.sigmoid(gate.astype(jnp.float32))
            out = (out.astype(jnp.float32) * opened).astype(self.dtype)
            stats["gate_mean"] = jax.lax.stop_gradient(jnp.mean(opened))
            if granularity == "head":
                stats[f"gate_mean_{kind}"] = stats["gate_mean"]
        dense = functools.partial(QuantDenseGeneral, mode=self.quant) if self.quant else nn.DenseGeneral
        out = dense(
            features=inputs.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype, name="to_out"
        )(out)
        return checkpoint_name(out, "attn_out"), stats
