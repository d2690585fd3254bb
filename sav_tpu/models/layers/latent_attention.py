"""Multi-head latent attention (MLA), the training form.

arXiv:2405.04434 section 2.1 / arXiv:2412.19437 section 2.1.1: queries, and
keys with values, each go through a low-rank path with an RMSNorm inside;
a head's query and key are a part without position (``nope_ch``) beside a
rotary part (``rope_ch``) whose key side is ONE head shared by all heads;
the value head (``v_ch``) is narrower than the query/key head::

    c_q = RMSNorm(x W_qa);   q = c_q W_qb                 # H heads of nope + rope
    [c_kv | k_rope] = x W_kva;   c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb                              # H heads of nope + v
    q = [q_nope | rot(q_rope)];   k = [k_nope | rot(k_rope) for every head]
    out = softmax(q k^T / sqrt(nope + rope), causal) v;   y = concat(out) W_o

``rope_scaling`` (the public config's group, YaRN) blends the rotary
frequencies and multiplies the softmax scale (``sav_tpu/ops/rotary.py``).

Three variants, each told by an argument and each the program above where the
argument has its default:

* ``q_rank=None``, the direct query (the public configs' ``q_lora_rank``
  null): ``q = x W_q``, one ``[D, H (nope + rope)]`` matrix (leaf ``q``); no
  ``q_a``, no norm inside the query's path.
* ``qk_norm`` (``use_qk_norm``): before the rotation, each head's whole query
  passes ``RMSNorm_{nope + rope}`` with ONE weight shared by the heads (leaf
  ``q_head_norm``) and the rotary key ``RMSNorm_{rope}`` (``k_rope_norm``).
  No norm runs across a head's ``[k_nope | k_rope]``: it would make the one
  rotary key differ by head, which the latent cache excludes.
* ``gate`` (``gated_attention_proj_granularity_type`` ``head_wise``): ``y = W_o
  concat_h(sigmoid(x W_gate)_h out_h)``, ``W_gate [D, H]`` (leaf ``gate``), one
  number a head and token, in float32.

Training materialises ``k`` and ``v`` a head; the absorbed form (``W_kvb``
folded into the query and the output) is decoding's and is not here.

Scopes: the module's own name holds ``SelfAttentionBlock``; the four input
projections and the two inner norms lie under ``to_qkv``, the output merge
is ``to_out``, so the readers of a trace see it as any other attention block.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.models.layers.feedforward import _bias_free_dense
from sav_tpu.models.layers.normalization import RMSNorm
from sav_tpu.ops.attention import dot_product_attention
from sav_tpu.ops.quant import QuantDenseGeneral
from sav_tpu.ops.rotary import apply_rotary_interleaved, yarn_softmax_scale

Dtype = Any


class _LatentQKVProj(nn.Module):
    """``x -> (q [B, S, H, nope + rope], k the same, v [B, S, H, v_ch], the
    output gate's ``[B, S, H]`` or None)``, rotary applied. The two
    up-projections are plain ``[rank, H x width]`` matrices (a head is a
    contiguous slice of the output)."""

    num_heads: int
    q_rank: Optional[int]
    kv_rank: int
    nope_ch: int
    rope_ch: int
    v_ch: int
    rope_theta: float
    rope_scaling: Optional[Any]
    norm_eps: float
    quant: Optional[str]
    dtype: Dtype
    qk_norm: bool = False
    gate: bool = False

    @nn.compact
    def __call__(self, x: jax.Array):
        b, s, _ = x.shape
        h, nope, rope = self.num_heads, self.nope_ch, self.rope_ch
        dense = _bias_free_dense(self.quant, self.dtype)

        def norm(name):
            return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name=name)

        c_q = None if self.q_rank is None else norm("q_norm")(dense(self.q_rank, name="q_a")(x))
        kv = dense(self.kv_rank + rope, name="kv_a")(x)
        c_kv, k_rope = norm("kv_norm")(kv[..., : self.kv_rank]), kv[..., self.kv_rank:]
        # Tagged for a caller's remat policy: with the latents kept, the
        # backward pass recomputes the up-projections from them and not the
        # down-projections from the layer's input.
        c_q, c_kv, k_rope = (None if t is None else checkpoint_name(t, "mla_latent") for t in (c_q, c_kv, k_rope))

        source, name = (x, "q") if c_q is None else (c_q, "q_b")
        q = dense(h * (nope + rope), name=name)(source).reshape(b, s, h, nope + rope)
        kv = dense(h * (nope + self.v_ch), name="kv_b")(c_kv).reshape(b, s, h, nope + self.v_ch)
        if self.qk_norm:
            q, k_rope = norm("q_head_norm")(q), norm("k_rope_norm")(k_rope)
        q_rope = apply_rotary_interleaved(q[..., nope:], self.rope_theta, self.rope_scaling)
        k_rope = apply_rotary_interleaved(k_rope, self.rope_theta, self.rope_scaling)  # [B, S, rope]: one head
        query = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        key = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rope))], axis=-1
        )
        opened = dense(h, name="gate")(x) if self.gate else None
        return query, key, kv[..., nope:], opened


class LatentSelfAttentionBlock(nn.Module):
    """Causal multi-head latent self-attention; see the module docstring."""

    num_heads: int
    q_rank: Optional[int]  # None: the direct query
    kv_rank: int
    nope_ch: int
    rope_ch: int
    v_ch: int
    rope_theta: float = 10000.0
    rope_scaling: Optional[Any] = None  # the public config's group (YaRN)
    norm_eps: float = 1e-6
    qk_norm: bool = False  # per-head query norm and rotary-key norm before the rotation
    gate: bool = False  # a sigmoid gate a head on the core's output
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        query, key, value, opened = _LatentQKVProj(
            num_heads=self.num_heads,
            q_rank=self.q_rank,
            kv_rank=self.kv_rank,
            nope_ch=self.nope_ch,
            rope_ch=self.rope_ch,
            v_ch=self.v_ch,
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            norm_eps=self.norm_eps,
            quant=self.quant,
            dtype=self.dtype,
            qk_norm=self.qk_norm,
            gate=self.gate,
            name="to_qkv",
        )(inputs)
        query, key, value = (checkpoint_name(t, "attn_qkv") for t in (query, key, value))
        out = dot_product_attention(
            query,
            key,
            value,
            scale=(self.nope_ch + self.rope_ch) ** -0.5 * yarn_softmax_scale(self.rope_scaling),
            backend=self.backend,
            logits_dtype=self.logits_dtype or self.dtype,
            causal=True,
        )
        if opened is not None:
            opened = jax.nn.sigmoid(checkpoint_name(opened, "attn_qkv").astype(jnp.float32))
            out = (out.astype(jnp.float32) * opened[..., None]).astype(self.dtype)
        dense = functools.partial(QuantDenseGeneral, mode=self.quant) if self.quant else nn.DenseGeneral
        out = dense(
            features=inputs.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype, name="to_out"
        )(out)
        return checkpoint_name(out, "attn_out")
