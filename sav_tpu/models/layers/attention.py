"""Canonical multi-head attention blocks.

Capability parity with the reference's attention family
(/root/reference/models/layers/attentions/attention.py:10-74,
talking_heads.py:5-14), redesigned around the backend-dispatched functional
cores in :mod:`sav_tpu.ops.attention` so every block can run on the
single-pass fused short-sequence kernel (``backend='fused'``), the
blockwise flash kernel (``backend='pallas'``) or the XLA reference path
(``backend='xla'``) — ``'auto'`` resolves per shape from the measured
attn_tune cache. Talking-heads mixing couples heads, so it gets its own
fused kernel that keeps all heads of a batch element in one grid cell
(:mod:`sav_tpu.ops.talking_heads` — CaiT's self-attention trunk); the XLA
path remains the numerics reference and the long-sequence/dropout fallback.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.ops.attention import dot_product_attention
from sav_tpu.ops.quant import (
    QuantDenseGeneral,
    int8_serve_dot,
    int8_ste_dot,
    quant_rng_data,
)
from sav_tpu.ops.rotary import (
    apply_rotary_half,
    apply_rotary_pos_emb,
    fixed_positional_embedding,
    half_split_tables,
)

Dtype = Any


class TalkingHeadsBlock(nn.Module):
    """Learned head-mixing transform (orthogonal init), applied to attention
    logits or probabilities. Reference: talking_heads.py:5-14.

    Calling with ``None`` returns the raw ``[H, H]`` kernel instead of
    applying it — the fused talking-heads kernel consumes the matrix
    directly while keeping the identical ``{pre,post}_softmax/kernel``
    checkpoint layout."""

    num_heads: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Optional[jax.Array]) -> jax.Array:
        kernel = self.param(
            "kernel", nn.initializers.orthogonal(), (self.num_heads, self.num_heads)
        )
        if x is None:
            return kernel
        return jnp.einsum("hi,...hqk->...iqk", kernel.astype(x.dtype), x)


def talking_heads_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    num_heads: int,
    scale: float,
    attn_dropout_rate: float,
    is_training: bool,
    dtype: Dtype,
) -> jax.Array:
    """Attention core with pre/post-softmax head mixing (XLA path).

    Must be called from within a parent module's ``@nn.compact`` ``__call__``
    — it instantiates the two ``TalkingHeadsBlock`` submodules (named
    ``pre_softmax`` / ``post_softmax``) on the caller's scope. Shared by
    ``AttentionBlock`` and ``CvTAttentionBlock``.
    """
    logits = jnp.einsum(
        "...qhd,...khd->...hqk",
        query * jnp.asarray(scale, query.dtype),
        key,
        preferred_element_type=jnp.float32,
    )
    logits = TalkingHeadsBlock(num_heads=num_heads, dtype=dtype, name="pre_softmax")(
        logits
    )
    probs = jax.nn.softmax(logits, axis=-1)
    probs = TalkingHeadsBlock(num_heads=num_heads, dtype=dtype, name="post_softmax")(
        probs
    )
    probs = nn.Dropout(rate=attn_dropout_rate)(probs, deterministic=not is_training)
    return jnp.einsum("...hqk,...khd->...qhd", probs.astype(value.dtype), value)


class _FusedQKVProj(nn.Module):
    """Stacked QKV projection computed as three slice-of-param matmuls.

    Parameter tree is byte-identical to
    ``nn.DenseGeneral(features=(3, heads, head_ch), name=...)`` — kernel
    ``[in, 3, H, D]``, bias ``[3, H, D]`` — so checkpoints interchange with
    the declarative layout. The compute differs deliberately: a single
    einsum to ``[B, L, 3, H, D]`` followed by *middle-axis activation
    slices* makes XLA relayout every slice (~1.3 ms/layer at DeiT-S shapes,
    profiled in PERF.md §5); slicing the small *parameter* on its
    unsharded 3-axis instead and running one einsum per projection keeps
    every activation in its natural ``[B, L, H, D]`` layout. The param
    slices are also what Megatron-style tensor parallelism wants: the
    ``to_qkv`` sharding rule places the H axis, which each per-projection
    einsum preserves (no flatten of a sharded dim).
    """

    num_heads: int
    head_ch: int
    use_bias: bool = False
    # int8 quant arm (sav_tpu/ops/quant.py): "int8" routes each slice
    # einsum through the STE dot; "int8_serve" declares the stacked
    # kernel as int8 + a per-slice-channel scale. The per-slice compute
    # structure (and the TP-friendly param slicing) is unchanged.
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array):
        in_ch = x.shape[-1]
        h, d = self.num_heads, self.head_ch
        hd = h * d

        def kernel_init(rng, shape, param_dtype):
            # Match DenseGeneral: lecun_normal over the flattened
            # (fan_in, prod(features)) matrix, reshaped to the tree shape.
            flat = nn.initializers.lecun_normal()(rng, (in_ch, 3 * hd), param_dtype)
            return flat.reshape(shape)

        if self.quant == "int8_serve":
            kernel = self.param(
                "kernel", nn.initializers.zeros_init(), (in_ch, 3, h, d), jnp.int8
            )
            scale = self.param(
                "scale", nn.initializers.ones_init(), (3, h, d), jnp.float32
            )
        else:
            kernel = self.param("kernel", kernel_init, (in_ch, 3, h, d), jnp.float32)
            kernel = kernel.astype(self.dtype)
        xc = x.astype(self.dtype)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (3, h, d), jnp.float32
            ).astype(self.dtype)

        if self.quant == "int8_serve":
            def proj(t):
                y = int8_serve_dot(xc, kernel[:, t], scale[t], 1).astype(self.dtype)
                return y + bias[t] if self.use_bias else y
        elif self.quant:
            qkey = quant_rng_data(self)

            def proj(t):
                y = int8_ste_dot(xc, kernel[:, t], jax.random.fold_in(qkey, t), 1)
                return y + bias[t] if self.use_bias else y
        else:
            def proj(t):
                y = jnp.einsum("...i,ihd->...hd", xc, kernel[:, t])
                return y + bias[t] if self.use_bias else y

        return proj(0), proj(1), proj(2)


class AttentionBlock(nn.Module):
    """Multi-head (cross-)attention with optional talking heads.

    Reference: attention.py:10-67. Q/K/V are ``nn.DenseGeneral`` projections
    to ``(num_heads, head_ch)``; logits scale is ``head_ch ** -0.5``; output
    merge is a ``DenseGeneral`` over ``(heads, head_ch)``.
    """

    num_heads: int
    head_ch: Optional[int] = None
    out_ch: Optional[int] = None
    talking_heads: bool = False
    attn_dropout_rate: float = 0.0
    out_dropout_rate: float = 0.0
    use_bias: bool = False
    # Stacked QKV parameter for self-attention (one [in, 3, H, D] kernel —
    # see _FusedQKVProj for how it is computed). Changes the param tree
    # (to_qkv instead of to_q/to_k/to_v) — set False for the reference's
    # three-projection layout if a checkpoint/repro needs it, and for any
    # cross-attention use (Q and K/V come from different inputs). The
    # checkpoint layout depends on this flag alone, never on call arguments.
    fused_qkv: bool = True
    # RoPE on Q/K after projection (the working rebuild of the reference's
    # broken, never-wired rotary path — SURVEY.md §2.9 #12).
    use_rotary: bool = False
    # The decoder family's rotary: lanes paired by halves (i with i + D/2)
    # at this base, rotated in float32. None keeps the zoo's every-two
    # pairing at base 10,000.
    rotary_half_base: Optional[float] = None
    # Decoder self-attention: position i sees j <= i (the dense path masks by
    # an iota comparison, the flash kernel skips blocks above the diagonal).
    causal: bool = False
    # Attention-core backend: None/'auto' = measured three-way dispatch
    # (sav_tpu.ops.attention.resolve_attention_backend — fused-short /
    # xla / flash by shape band + the attn_tune cache), or force 'xla' |
    # 'fused' | 'pallas'.
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None  # None = inherit dtype (softmax math)
    # Sequence parallelism: route the attention core through
    # sav_tpu.parallel.seq_parallel over ``seq_mesh``'s 'seq' axis
    # ('ring' | 'ulysses'; None = single-device core). Config-reachable via
    # TrainConfig.sequence_parallel / train.py --sp N. Self-attention only,
    # deterministic only (no attention dropout), exact numerics incl. the
    # CLS-odd sequence lengths of the model zoo (pad-and-mask).
    seq_parallel: Optional[str] = None
    seq_mesh: Optional[Any] = None
    # int8 quantized projection dots ("int8" QAT / "int8_serve" — see
    # sav_tpu/ops/quant.py): Q/K/V and the output merge route through
    # the quantized dot; the attention core (QK/AV) stays in ``dtype``
    # by design (PERF §5: those dots are not matmul-roofline-bound).
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, inputs_q: jax.Array, inputs_kv: jax.Array, is_training: bool
    ) -> jax.Array:
        in_ch = inputs_q.shape[-1]
        head_ch = self.head_ch or in_ch // self.num_heads
        out_ch = self.out_ch or in_ch
        scale = head_ch**-0.5

        dense = functools.partial(
            QuantDenseGeneral if self.quant else nn.DenseGeneral,
            axis=-1,
            use_bias=self.use_bias,
            dtype=self.dtype,
            **({"mode": self.quant} if self.quant else {}),
        )
        if self.fused_qkv:
            # Self-attention: one stacked [in, 3, H, D] parameter, computed
            # as per-projection einsums over its slices (_FusedQKVProj —
            # avoids the activation-slice relayouts, keeps TP sharding).
            # Same init distribution per column as three separate
            # DenseGenerals (fan_in is identical).
            if inputs_q is not inputs_kv:
                raise ValueError(
                    "fused_qkv=True projects Q, K and V from one input and is "
                    "only valid for self-attention; pass fused_qkv=False for "
                    "cross-attention (distinct inputs_q / inputs_kv)."
                )
            query, key, value = _FusedQKVProj(
                num_heads=self.num_heads,
                head_ch=head_ch,
                use_bias=self.use_bias,
                quant=self.quant,
                dtype=self.dtype,
                name="to_qkv",
            )(inputs_q)
        else:
            proj = functools.partial(
                dense, features=(self.num_heads, head_ch)
            )
            query = proj(name="to_q")(inputs_q)
            key = proj(name="to_k")(inputs_kv)
            value = proj(name="to_v")(inputs_kv)

        if self.causal and (
            self.seq_parallel or self.talking_heads or inputs_q is not inputs_kv
        ):
            raise ValueError(
                "causal attention is plain self-attention on one device: no "
                "talking heads, no sequence parallelism, no cross-attention"
            )
        if self.use_rotary and self.rotary_half_base is not None:
            sincos = half_split_tables(query.shape[1], head_ch, self.rotary_half_base)
            query = apply_rotary_half(query, sincos)
            key = apply_rotary_half(key, sincos)
        elif self.use_rotary:
            sincos = fixed_positional_embedding(query.shape[1], head_ch)
            query = apply_rotary_pos_emb(query, sincos)
            if key.shape[1] != query.shape[1]:
                sincos = fixed_positional_embedding(key.shape[1], head_ch)
            key = apply_rotary_pos_emb(key, sincos)

        # Tags for a caller's remat policy (the identity without one): Q, K
        # and V as the core receives them, and the merged output below.
        query, key, value = (checkpoint_name(x, "attn_qkv") for x in (query, key, value))

        has_attn_dropout = self.attn_dropout_rate > 0.0 and is_training
        if self.seq_parallel:
            if self.talking_heads and self.seq_parallel != "ring":
                raise ValueError(
                    "talking-heads sequence parallelism is ring-only "
                    "(Ulysses shards heads across devices; the head mix "
                    "would cross them) — use seq_parallel='ring'"
                )
            if has_attn_dropout:
                raise ValueError(
                    "sequence-parallel attention is deterministic-only; "
                    "set attn_dropout_rate=0 (the reference recipes use "
                    "stochastic depth + output dropout, not attention "
                    "dropout)"
                )
            if inputs_q is not inputs_kv:
                raise ValueError(
                    "sequence parallelism supports self-attention blocks "
                    "only (q and kv shards must cover the same sequence)"
                )
            if self.seq_mesh is None:
                raise ValueError(
                    "seq_parallel set but no seq_mesh given; pass the "
                    "training Mesh (with a 'seq' axis) to the block"
                )
            if self.backend in ("pallas", "fused"):
                raise ValueError(
                    "seq_parallel runs the dense XLA core per shard; "
                    f"backend={self.backend!r} is not routed under SP (the "
                    "bare ring_attention/ulysses_attention ops expose flash "
                    "mode for divisible lengths) — unset one of the two"
                )
            # logits_dtype does not apply here: online-softmax statistics
            # (running max / denominator) are f32 by construction — see
            # TrainConfig.sequence_parallel.
            from sav_tpu.parallel.seq_parallel import (
                sequence_parallel_attention,
            )

            th = None
            if self.talking_heads:
                # Head mixing rides the ring via head-pair accumulators
                # (parallel.ring_attention._ring_talking_heads_shard_fn);
                # same {pre,post}_softmax/kernel checkpoint layout as the
                # dense and fused paths.
                th = (
                    TalkingHeadsBlock(
                        num_heads=self.num_heads, dtype=self.dtype,
                        name="pre_softmax",
                    )(None),
                    TalkingHeadsBlock(
                        num_heads=self.num_heads, dtype=self.dtype,
                        name="post_softmax",
                    )(None),
                )
            out = sequence_parallel_attention(
                query,
                key,
                value,
                mesh=self.seq_mesh,
                method=self.seq_parallel,
                scale=scale,
                talking_heads=th,
            )
        elif self.talking_heads:
            from sav_tpu.ops.talking_heads import fused_eligible

            backend = self.backend or "auto"
            fused_ok = (
                not has_attn_dropout
                and query.ndim == 4
                and fused_eligible(self.num_heads, key.shape[1], head_ch)
            )
            if backend in ("pallas", "fused"):
                # Head mixing couples heads, so both kernel backends mean
                # the same thing here: the dedicated talking-heads kernel
                # (itself single-KV-block fused).
                if has_attn_dropout:
                    raise ValueError(
                        "pallas talking-heads attention is deterministic-only "
                        "(attention dropout runs on the XLA path)"
                    )
                use_fused = True  # kv-length guard raises inside the kernel
            else:
                # Measured crossover on v5e (tools/th_micro.py, CaiT-XXS
                # trunk shape B=256 L=197 H=4 D=48): fused wins fwd+bwd
                # (5.67 vs 7.13 ms) but loses forward-only (4.40 vs
                # 3.07 ms) — so 'auto' rides the kernel for training and
                # dense XLA for inference.
                use_fused = (
                    backend == "auto"
                    and fused_ok
                    and is_training
                    and jax.default_backend() == "tpu"
                )
            if use_fused:
                from sav_tpu.ops.talking_heads import (
                    flash_talking_heads_attention,
                )

                w_pre = TalkingHeadsBlock(
                    num_heads=self.num_heads, dtype=self.dtype, name="pre_softmax"
                )(None)
                w_post = TalkingHeadsBlock(
                    num_heads=self.num_heads, dtype=self.dtype, name="post_softmax"
                )(None)
                out = flash_talking_heads_attention(
                    query, key, value, w_pre, w_post, scale=scale
                )
            else:
                out = talking_heads_attention(
                    query,
                    key,
                    value,
                    num_heads=self.num_heads,
                    scale=scale,
                    attn_dropout_rate=self.attn_dropout_rate,
                    is_training=is_training,
                    dtype=self.dtype,
                )
        else:
            dropout_rng = self.make_rng("dropout") if has_attn_dropout else None
            # Resolved HERE (None = this block's compute dtype — the
            # reference's semantics: its logits einsum runs in the model
            # dtype, attention.py:41-48) so no jitted path ever reads the
            # deprecated process-wide default in sav_tpu.ops.attention.
            out = dot_product_attention(
                query,
                key,
                value,
                scale=scale,
                dropout_rate=self.attn_dropout_rate,
                dropout_rng=dropout_rng,
                deterministic=not is_training,
                backend=self.backend,
                logits_dtype=self.logits_dtype or self.dtype,
                causal=self.causal,
            )

        out = dense(
            features=out_ch,
            axis=(-2, -1),
            name="to_out",
        )(out)
        out = checkpoint_name(out, "attn_out")
        out = nn.Dropout(rate=self.out_dropout_rate)(out, deterministic=not is_training)
        return out


class SelfAttentionBlock(AttentionBlock):
    """Self-attention specialization (attention.py:70-74)."""

    @nn.compact
    def __call__(self, inputs: jax.Array, is_training: bool) -> jax.Array:  # type: ignore[override]
        return super().__call__(inputs, inputs, is_training)
