"""JoyAI-LLM-Flash: a pre-norm decoder with latent attention, routed and
shared experts and a multi-token-prediction module.

Sizes of ``https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/
config.json``; the layer equations are those of the family its config
names (arXiv:2412.19437 sections 2.1, 2.2 and 4.2)::

    h = E[tokens]
    for each layer:  h = h + Attn(RMSNorm(h));  h = h + FFN(RMSNorm(h))
    logits = RMSNorm_f(h) W_head                                # untied

``Attn`` is :class:`~sav_tpu.models.layers.LatentSelfAttentionBlock`. ``FFN``
is SwiGLU at ``mlp_ch`` in the first ``first_dense`` layers and
:class:`~sav_tpu.models.layers.SparseMoEBlock` after them: sigmoid scores,
the top ``top_k`` of score plus a selection bias, a shared expert, no
dropped token. The selection bias is state, not a parameter: one row a
routed layer in the ``batch_stats`` collection (``select_bias``), stepped
after every training step by ``bias_update_rate * sign(mean(c) - c_e)`` on
the step's routing counts ``c`` (the auxiliary-loss-free balancing of the
paper); the sequence-wise balance loss is sown into ``losses`` at relative
scale 1 (``TrainConfig.aux_loss_weight`` is its ``alpha``).

Multi-token prediction (one module, ``mtp``): at position ``i`` it reads the
main stack's output before the final norm and the embedding of token
``i + 1``, ``h'_i = W_eh [RMSNorm(h_i) ; RMSNorm(E[t_{i+1}])]``, runs one
expert layer of its own, its own final norm and the model's head, and is
scored on token ``i + 2``. With ``targets`` (token ``i + 1`` at position
``i``) the model derives both; the last position has no such target and
its term is exactly zero.

``experts_held = (offset, count)`` gives every routed layer one chip's share
of an expert-parallel deployment (see ``SparseMoEBlock``); nothing here
stands in for the chips that hold the rest.

Scopes, for the readers of a trace: layers ``layer_<i>``; in a layer the
attention block is ``LatentSelfAttentionBlock_0`` (``to_qkv``, ``to_out``),
the dense MLP ``GatedFFBlock_0`` (``fc1``, ``fc2``), the expert layer
``moe`` (``route``, ``dispatch``, ``experts/fc1|fc2``, ``combine``,
``shared/fc1|fc2``); the module ``mtp`` (its head and loss under
``mtp/lm_head``); the head ``lm_head``.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sav_tpu.models.layers import (
    GatedFFBlock,
    LatentSelfAttentionBlock,
    RMSNorm,
    SparseMoEBlock,
)
from sav_tpu.models.layers.moe import rows_over_bound
from sav_tpu.models.ouro import LMHead

Dtype = Any

# What the backward pass of a rematerialised layer application finds kept
# (tags in the layers and in the flash kernel's forward rule); everything
# else is computed again: norms, rotary, SiLU and the gates' products, the
# down projections, and the routed experts' gather of rows with the grouped
# matmuls that read them (268 MB of rows a routed layer when this was chosen:
# keeping them would have passed 15.0 GB; 34 MB since the buffers are
# bounded, which reopens the choice: ROADMAP S5d). Chosen on a v5e at the
# published widths, 2 x 4,096 tokens and
# 16 of 256 experts held: 406.4 ms a step in 13.55 GB, against 419.0 ms in
# 12.78 GB with the first three alone and 471.1 ms in 11.54 GB with nothing
# kept (PERF.md section 6, PR 30).
KEPT_UNDER_REMAT = (
    "attn_qkv", "flash_out", "flash_lse", "attn_out", "mla_latent",
    "ffn_gate", "ffn_up", "moe_route", "moe_order",
)


class LatentDecoderBlock(nn.Module):
    """One pre-norm layer; ``num_experts`` 0 makes its FFN the dense SwiGLU.
    Returns ``(h, counts, balance)``, the last two ``None`` for a dense layer."""

    num_heads: int
    q_rank: int
    kv_rank: int
    nope_ch: int
    rope_ch: int
    v_ch: int
    mlp_ch: int
    num_experts: int
    top_k: int
    routed_scale: float
    experts_held: Optional[Any]
    rope_theta: float
    norm_eps: float
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, select_bias: Optional[jax.Array]):
        def norm(name):
            return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name=name)

        a = LatentSelfAttentionBlock(
            num_heads=self.num_heads,
            q_rank=self.q_rank,
            kv_rank=self.kv_rank,
            nope_ch=self.nope_ch,
            rope_ch=self.rope_ch,
            v_ch=self.v_ch,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            backend=self.backend,
            logits_dtype=self.logits_dtype,
            quant=self.quant,
            dtype=self.dtype,
        )(norm("attn_norm")(inputs))
        x = inputs + a
        y = norm("ffn_norm")(x)
        if not self.num_experts:
            return x + GatedFFBlock(hidden_ch=self.mlp_ch, quant=self.quant, dtype=self.dtype)(y), None, None
        m, counts, balance = SparseMoEBlock(
            num_experts=self.num_experts,
            top_k=self.top_k,
            hidden_ch=self.mlp_ch,
            routed_scale=self.routed_scale,
            experts_held=self.experts_held,
            quant=self.quant,
            dtype=self.dtype,
            name="moe",
        )(y, select_bias)
        return x + m, counts, balance


class JoyAILM(nn.Module):
    """tokens ``[B, S]`` int32 ->

    - without ``targets``: ``{"logits": [B, S, V]}`` float32 (the main head);
    - with ``targets`` ``[B, S]`` (the next token at every position):
      ``{"ce": [B, S], "ce_mtp": [B, S]}`` (the last ``ce_mtp`` is 0),
      ``"moe_counts" [B, R, E]`` (each sequence's routings by routed layer,
      the module's last, and expert), ``"moe_held" [B]`` (those of them on
      the experts held), ``"moe_rows_over_bound" [B, R]`` (each routed
      layer's rows on the experts held over the rows its buffers hold, the
      same in every row: above 1 it took the overflow pass) and
      ``"moe_bias_abs_max" [B]``.
    """

    num_classes: int  # the vocabulary held here
    embed_dim: int
    num_layers: int
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_ch: int
    rope_ch: int
    v_ch: int
    mlp_ch: int
    expert_ch: int
    num_experts: int
    top_k: int
    routed_scale: float
    first_dense: int = 1
    bias_update_rate: float = 1e-3
    experts_held: Optional[Any] = None  # (offset, count) of num_experts; None = all
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    loss_block_tokens: int = 2048
    # Rematerialise each layer application in the backward pass, but for
    # KEPT_UNDER_REMAT; False keeps everything.
    remat: bool = False
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    # int8 arm: the layers' projections, MLPs and experts; embedding, router,
    # eh_proj and head stay in ``dtype``.
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, tokens: jax.Array, is_training: bool, targets: Optional[jax.Array] = None
    ) -> dict:
        block_cls = LatentDecoderBlock
        if self.remat:
            kept = jax.checkpoint_policies.save_only_these_names(*KEPT_UNDER_REMAT)
            block_cls = nn.remat(LatentDecoderBlock, policy=kept)

        def block(name: str, routed: bool):
            return block_cls(
                num_heads=self.num_heads,
                q_rank=self.q_rank,
                kv_rank=self.kv_rank,
                nope_ch=self.nope_ch,
                rope_ch=self.rope_ch,
                v_ch=self.v_ch,
                mlp_ch=self.expert_ch if routed else self.mlp_ch,
                num_experts=self.num_experts if routed else 0,
                top_k=self.top_k,
                routed_scale=self.routed_scale,
                experts_held=tuple(self.experts_held) if self.experts_held else None,
                rope_theta=self.rope_theta,
                norm_eps=self.norm_eps,
                backend=self.backend,
                logits_dtype=self.logits_dtype,
                quant=self.quant,
                dtype=self.dtype,
                name=name,
            )

        routed_layers = self.num_layers - self.first_dense
        select_bias = self.variable(
            "batch_stats", "select_bias", jnp.zeros, (routed_layers + 1, self.num_experts), jnp.float32
        )
        embed = nn.Embed(self.num_classes, self.embed_dim, dtype=self.dtype, name="embed")
        head = LMHead(self.num_classes, self.loss_block_tokens, dtype=self.dtype, name="lm_head")

        if targets is None and self.is_initializing():
            targets = tokens  # init's trace makes every parameter, the MTP module's too
        h = embed(tokens)
        counts, balances = [], []
        for i in range(self.num_layers):
            routed = i >= self.first_dense
            bias = select_bias.value[i - self.first_dense] if routed else None
            h, c, b = block(f"layer_{i}", routed)(h, bias)
            if routed:
                counts.append(c)
                balances.append(b)
        main = head(RMSNorm(eps=self.norm_eps, dtype=self.dtype, name="final_norm")(h), targets)
        if targets is None:
            return {"logits": main}

        # ``targets`` is token i + 1 at position i: the module's input there,
        # and shifted once more its target; the last position has no
        # next-but-one token and no term.
        mtp = _MTP(self.norm_eps, self.dtype, lambda: block("layer", True), name="mtp")
        h_mtp, c, b = mtp(h, embed(targets), select_bias.value[-1])
        counts.append(c)
        balances.append(b)
        with jax.named_scope("mtp"):
            mtp_targets = jnp.concatenate([targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1)
            has_target = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
            ce_mtp = jnp.where(has_target[None, :], head(h_mtp, mtp_targets), 0.0)

        self.sow("losses", "moe_balance_loss", sum(balances))
        counts = jnp.stack(counts, axis=1)  # [B, R, E]
        if is_training and self.is_mutable_collection("batch_stats"):
            load = jnp.sum(counts, axis=0)  # [R, E]: the step's routings
            step = jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
            select_bias.value = select_bias.value + self.bias_update_rate * step
        bias_max = jnp.max(jnp.abs(select_bias.value))
        offset, held = self.experts_held or (0, self.num_experts)
        on_held = counts[..., offset:offset + held]
        over_bound = rows_over_bound(counts, tokens.size * self.top_k, self.experts_held)
        return {
            "ce": main,
            "ce_mtp": ce_mtp,
            "moe_counts": counts,
            "moe_held": jnp.sum(on_held, axis=(1, 2)),
            "moe_rows_over_bound": jnp.broadcast_to(over_bound, counts.shape[:2]),
            "moe_bias_abs_max": jnp.broadcast_to(bias_max, tokens.shape[:1]),
        }


class _MTP(nn.Module):
    """The multi-token-prediction module's own weights: ``W_eh [RMSNorm(h) ;
    RMSNorm(E[next token])]``, one expert layer and a final norm."""

    norm_eps: float
    dtype: Dtype
    make_layer: Any  # () -> the module's expert layer, built in this scope

    @nn.compact
    def __call__(self, h, next_embedding, select_bias):
        def norm(name):
            return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name=name)

        both = jnp.concatenate([norm("h_norm")(h), norm("e_norm")(next_embedding)], axis=-1)
        x = nn.Dense(h.shape[-1], use_bias=False, dtype=self.dtype, name="eh_proj")(both)
        x, counts, balance = self.make_layer()(x, select_bias)
        return norm("final_norm")(x), counts, balance
