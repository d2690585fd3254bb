"""The decoder of the expert families: a pre-norm stack with latent
attention, routed and shared experts and multi-token-prediction modules,
whose residual path is plain or a set of hyper-connected streams.

Two registry entries build it. ``joyai_llm_flash``: sizes of
``https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json``,
the layer equations of the family its config names (arXiv:2412.19437 sections
2.1, 2.2 and 4.2)::

    h = E[tokens]
    for each layer:  h = h + Attn(RMSNorm(h));  h = h + FFN(RMSNorm(h))
    logits = RMSNorm_f(h) W_head                                # untied

``xing4_0_29b_a4b``: sizes of ``https://huggingface.co/XingChen-AGI/
Xing4.0-29B-A4B/blob/main/config.json``, the same sublayers at other widths
with YaRN on the rotary part (``rope_scaling``) and, for ``hc_mult`` > 1, each
``h + F(RMSNorm(h))`` replaced by manifold-constrained hyper-connections over
``hc_mult`` residual streams (:class:`~sav_tpu.models.layers.hyper_connection.
HyperConnection`; arXiv:2512.24880): the embedding is copied to every stream,
the final norm reads their sum, and the module's layer does the same with
``eh_proj``'s result. At ``hc_mult`` 1 the state is one array and a sublayer
is ``h + F(RMSNorm(h))``, the same program as before the streams existed.

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

Multi-token prediction (``mtp_modules`` 1: one module, ``mtp``; 0: none,
and no ``ce_mtp`` in the outputs): at position ``i`` it reads the
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
``shared/fc1|fc2``); a hyper-connection's maps under ``hc_attn`` and
``hc_ffn`` (``hc/pre``, ``hc/sinkhorn``) and its merge under the layer
(``hc/post``); the module ``mtp`` (its head and loss under ``mtp/lm_head``);
the head ``lm_head``.
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
from sav_tpu.models.layers.hyper_connection import HyperConnection, fan_in, fan_out
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
    "ffn_gate", "ffn_up", "moe_route", "moe_order", "hc_maps",
)

# The hyper-connected family's choice among the same names, on a v5e at its
# published widths, 2 x 4,096 tokens and 8 of 64 experts held, where the state
# alone is 12.15 GB: without ``attn_qkv`` (268 MB a layer; the backward pass
# runs the two up-projections and the rotary again from ``mla_latent``) 453 ms
# a step in 14.47 GB, against 440 ms in 15.53 GB with every name and 493 ms in
# 14.49 GB with none: 12.6 ms a GB, the cheapest of the names (PERF.md section
# 6, PR 32). ``hc_maps`` (24 float32 a token and sublayer) spares the backward
# pass the maps' projection and the twenty Sinkhorn iterations.
KEPT_UNDER_REMAT_BESIDE_STREAMS = tuple(name for name in KEPT_UNDER_REMAT if name != "attn_qkv")


class LatentDecoderBlock(nn.Module):
    """One pre-norm layer; ``num_experts`` 0 makes its FFN the dense SwiGLU.
    ``hc`` holds :class:`HyperConnection`'s sizes (``streams`` 1: the state is
    one array and a sublayer is ``h + F(RMSNorm(h))``). Returns ``(state,
    counts, balance, hc_stats)``: the two in the middle ``None`` for a dense
    layer, the last ``None`` at one stream, else the larger of its two
    sublayers' ``stats``."""

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
    rope_scaling: Optional[Any] = None
    hc: Optional[Any] = None  # HyperConnection's sizes as a dict; None = one stream
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs, select_bias: Optional[jax.Array]):
        def norm(name):
            return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name=name)

        def residual(name):
            return HyperConnection(**(self.hc or {"streams": 1}), norm_eps=self.norm_eps, dtype=self.dtype, name=name)

        u, merge = residual("hc_attn")(inputs)
        a = LatentSelfAttentionBlock(
            num_heads=self.num_heads,
            q_rank=self.q_rank,
            kv_rank=self.kv_rank,
            nope_ch=self.nope_ch,
            rope_ch=self.rope_ch,
            v_ch=self.v_ch,
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            norm_eps=self.norm_eps,
            backend=self.backend,
            logits_dtype=self.logits_dtype,
            quant=self.quant,
            dtype=self.dtype,
        )(norm("attn_norm")(u))
        x, attn_stats = merge(a)
        u, merge = residual("hc_ffn")(x)
        y = norm("ffn_norm")(u)
        if not self.num_experts:
            m, counts, balance = GatedFFBlock(hidden_ch=self.mlp_ch, quant=self.quant, dtype=self.dtype)(y), None, None
        else:
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
        x, ffn_stats = merge(m)
        stats = attn_stats and jax.tree.map(jnp.maximum, attn_stats, ffn_stats)
        return x, counts, balance, stats


class JoyAILM(nn.Module):
    """tokens ``[B, S]`` int32 ->

    - without ``targets``: ``{"logits": [B, S, V]}`` float32 (the main head);
    - with ``targets`` ``[B, S]`` (the next token at every position):
      ``{"ce": [B, S]}``, with a module ``"ce_mtp": [B, S]`` (the last is 0),
      ``"moe_counts" [B, R, E]`` (each sequence's routings by routed layer,
      the module's last, and expert), ``"moe_held" [B]`` (those of them on
      the experts held), ``"moe_rows_over_bound" [B, R]`` (each routed
      layer's rows on the experts held over the rows its buffers hold, the
      same in every row: above 1 it took the overflow pass),
      ``"moe_bias_abs_max" [B]`` and, at ``hc_mult`` > 1,
      ``"hc_doubly_stochastic_err" [B]`` and ``"hc_stream_gain" [B]`` (the
      largest of any sublayer's :class:`HyperConnection` ``stats``, the same
      in every row).
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
    mtp_modules: int = 1  # the public configs' num_nextn_predict_layers: 0 or 1
    bias_update_rate: float = 1e-3
    experts_held: Optional[Any] = None  # (offset, count) of num_experts; None = all
    rope_theta: float = 1e4
    rope_scaling: Optional[Any] = None  # the public config's group (YaRN); None = plain rotary
    norm_eps: float = 1e-6
    # Residual streams (the public config's hc_mult) and, past one, the
    # Sinkhorn projection's iterations, its eps and the clamp of its logits.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    loss_block_tokens: int = 2048
    # Rematerialise each layer application in the backward pass, but for the
    # names of ``kept_under_remat``; False keeps everything.
    remat: bool = False
    kept_under_remat: tuple = KEPT_UNDER_REMAT
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    # int8 arm: the layers' projections, MLPs and experts; embedding, router,
    # hyper-connection maps, eh_proj and head stay in ``dtype``.
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, tokens: jax.Array, is_training: bool, targets: Optional[jax.Array] = None
    ) -> dict:
        if self.mtp_modules not in (0, 1):
            raise ValueError(f"mtp_modules {self.mtp_modules}: none or one module, no chain of them")
        block_cls = LatentDecoderBlock
        if self.remat:
            kept = jax.checkpoint_policies.save_only_these_names(*self.kept_under_remat)
            block_cls = nn.remat(LatentDecoderBlock, policy=kept)
        hc = None
        if self.hc_mult > 1:
            hc = {"streams": self.hc_mult, "sinkhorn_iters": self.hc_sinkhorn_iters,
                  "sinkhorn_eps": self.hc_eps, "res_clamp": tuple(self.hc_res_clamp)}

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
                rope_scaling=self.rope_scaling,
                hc=hc,
                backend=self.backend,
                logits_dtype=self.logits_dtype,
                quant=self.quant,
                dtype=self.dtype,
                name=name,
            )

        routed_layers = self.num_layers - self.first_dense
        select_bias = self.variable(
            "batch_stats", "select_bias", jnp.zeros,
            (routed_layers + self.mtp_modules, self.num_experts), jnp.float32,
        )
        embed = nn.Embed(self.num_classes, self.embed_dim, dtype=self.dtype, name="embed")
        head = LMHead(self.num_classes, self.loss_block_tokens, dtype=self.dtype, name="lm_head")

        if targets is None and self.is_initializing():
            targets = tokens  # init's trace makes every parameter, the MTP module's too
        h = fan_out(embed(tokens), self.hc_mult)
        counts, balances, hc_stats = [], [], []
        for i in range(self.num_layers):
            routed = i >= self.first_dense
            bias = select_bias.value[i - self.first_dense] if routed else None
            h, c, b, stats = block(f"layer_{i}", routed)(h, bias)
            hc_stats.append(stats)
            if routed:
                counts.append(c)
                balances.append(b)
        h = fan_in(h)
        main = head(RMSNorm(eps=self.norm_eps, dtype=self.dtype, name="final_norm")(h), targets)
        if targets is None:
            return {"logits": main}
        out = {"ce": main}

        if self.mtp_modules:
            # ``targets`` is token i + 1 at position i: the module's input there,
            # and shifted once more its target; the last position has no
            # next-but-one token and no term.
            mtp = _MTP(self.norm_eps, self.hc_mult, self.dtype, lambda: block("layer", True), name="mtp")
            h_mtp, c, b, stats = mtp(h, embed(targets), select_bias.value[-1])
            counts.append(c)
            balances.append(b)
            hc_stats.append(stats)
            with jax.named_scope("mtp"):
                mtp_targets = jnp.concatenate([targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1)
                has_target = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
                out["ce_mtp"] = jnp.where(has_target[None, :], head(h_mtp, mtp_targets), 0.0)

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
        out.update({
            "moe_counts": counts,
            "moe_held": jnp.sum(on_held, axis=(1, 2)),
            "moe_rows_over_bound": jnp.broadcast_to(over_bound, counts.shape[:2]),
            "moe_bias_abs_max": jnp.broadcast_to(bias_max, tokens.shape[:1]),
        })
        if hc:
            err, gain = (jnp.max(jnp.stack(column)) for column in zip(*hc_stats))
            out["hc_doubly_stochastic_err"] = jnp.broadcast_to(err, tokens.shape[:1])
            out["hc_stream_gain"] = jnp.broadcast_to(gain, tokens.shape[:1])
        return out


class _MTP(nn.Module):
    """The multi-token-prediction module's own weights: ``W_eh [RMSNorm(h) ;
    RMSNorm(E[next token])]``, one expert layer (its state ``W_eh``'s result
    copied to every stream, its result their sum) and a final norm."""

    norm_eps: float
    streams: int
    dtype: Dtype
    make_layer: Any  # () -> the module's expert layer, built in this scope

    @nn.compact
    def __call__(self, h, next_embedding, select_bias):
        def norm(name):
            return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name=name)

        both = jnp.concatenate([norm("h_norm")(h), norm("e_norm")(next_embedding)], axis=-1)
        x = nn.Dense(h.shape[-1], use_bias=False, dtype=self.dtype, name="eh_proj")(both)
        x, counts, balance, stats = self.make_layer()(fan_out(x, self.streams), select_bias)
        return norm("final_norm")(fan_in(x)), counts, balance, stats
