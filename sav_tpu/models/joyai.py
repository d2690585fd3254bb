"""The decoder of the expert families: a pre-norm stack of token mixers
(latent attention; gated delta-rule blocks with a gated grouped-query
attention block every few layers; double-gated short convolutions with a
grouped-query attention block between them; vector-decay delta-rule
blocks with a latent attention block every sixth layer; or sliding-window
grouped-query attention with a full layer of fewer heads every fourth), routed experts with
a shared expert or without, and multi-token-prediction modules, whose
residual path is plain or a set of hyper-connected streams, whose head is its
own matrix or the embedding's table.

Six registry entries build it. ``joyai_llm_flash``: sizes of
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

``qwen3_next_80b_a3b``: sizes of ``https://huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct/blob/main/config.json``. Its token mixers come
from a per-layer list of kinds (``mixers``, one a layer, of which a depth cut
runs the first ``num_layers``; this entry gives the config's
``full_attention_interval`` and :func:`hybrid_mixers` builds the list from
it): ``gated_delta`` is
:class:`~sav_tpu.models.layers.gated_delta.GatedDeltaNetBlock`, a recurrence
over the sequence, ``gated_attention`` is :class:`~sav_tpu.models.layers.
gated_attention.GatedSelfAttentionBlock`; its norms store their weight as the
offset from 1 (``norm_offset``), its router scores by a softmax over all
experts without a selection bias (``scoring``, ``bias_update_rate`` 0), its
shared expert sits behind a sigmoid gate (``shared_gate``), every layer is an
expert layer and there is no MTP module.

``lfm2_24b_a2b``: sizes of ``https://huggingface.co/LiquidAI/LFM2-24B-A2B/
blob/main/config.json``. ``mixers`` is the config's own ``layer_types``, which
no interval gives: ``conv`` is :class:`~sav_tpu.models.layers.short_conv.
ShortConvBlock` (two gates around a causal depthwise convolution of width 3),
``full_attention`` the grouped-query block at this family's sizes (no output
gate, rotary on the whole head, plain norm weights). Two leading dense
layers, sigmoid-routed experts with a selection bias and NO shared expert
(``shared_expert`` False: the layer's result is the routed sum alone), the
selected scores divided by their sum plus 1e-6 (``router_weight_eps``), no
MTP module, and a tied head (``tie_head``: ``logits = RMSNorm_f(h) E^T``;
the tree has no ``lm_head`` and the table's gradient is the sum of both
uses).

``ling_3.0_flash``: the language model of ``https://huggingface.co/inclusionAI/
Ling-3.0-flash-VL/blob/main/config.json`` (no vision tower: the public config
gives it no key). ``mixers`` by the config's ``layer_group_size`` 6: ``kda``
(:class:`~sav_tpu.models.layers.kda.KDABlock`, the delta rule with a decay a
key lane, arXiv:2510.26692) in five layers of six and ``latent`` in the sixth,
there with the direct query (``q_rank`` None), the norms of ``use_qk_norm``
(``latent_qk_norm``) and a head-wise sigmoid gate on the core's output
(``latent_gate``). Two leading dense layers; 512 sigmoid-routed experts with a
selection bias, the top 8 picked inside 4 of 8 groups (``n_group``,
``topk_group``), a shared expert; the SwiGLU of the last layers' experts
clamped (``expert_limits``, ``shared_limits``: a number a published layer, 0
in the first 34); no MTP module; an untied head.

``laguna_s_2.1``: sizes of ``https://huggingface.co/poolside/Laguna-S-2.1/
blob/main/config.json``. ``mixers`` is the config's own ``layer_types``: two
kinds of softmax layer WITH DIFFERENT HEAD COUNTS, so ``to_qkv`` and
``to_out`` differ in shape by the layer's kind. ``sliding_attention`` (36
layers): 72 query heads of 128 on 8 key/value heads, each position attending
to itself and the 511 before it (``window``), rotary on the whole head at
base 10,000; ``full_attention`` (12 layers, every fourth from layer 0): 48
query heads on 8, causal, rotary on the leading 64 lanes at base 500,000
under YaRN with the group's ``attention_factor`` on the tables. Both norm q
and k a head with a plain weight and gate the core's output a head
(``gate`` ``"head"``). One leading dense layer; 256 softmax-routed experts,
top-10 normalised and scaled by 2.5 (``routed_scale`` on a softmax router),
no selection bias, a shared expert behind a sigmoid gate; no MTP module; an
untied head.

The kinds of mixer a ``mixers`` list may name: ``latent`` (the block at this
class's own latent sizes) and the keys of :data:`MIXER_BLOCKS`:
``gated_delta``, ``gated_attention`` / ``full_attention`` (one block, the
sizes of ``gated_attention``), ``sliding_attention`` (the same block at the
sizes of ``sliding_attention``, which give it a ``window``), ``conv``,
``kda``.

``Attn`` is :class:`~sav_tpu.models.layers.LatentSelfAttentionBlock` unless
``mixers`` says otherwise. ``FFN``
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
token mixer is ``LatentSelfAttentionBlock_0``, ``GatedSelfAttentionBlock_0``,
``GatedDeltaNetBlock_0``, ``ShortConvBlock_0`` or ``KDABlock_0`` (``to_qkv``,
``to_out`` in each; the second's core under ``attn/full`` or, with a window,
``attn/window``; the third also ``gdn/conv``, ``gdn/rule``,
``gdn/gate_norm``, the fourth ``sconv/core``, the last ``kda/conv``,
``kda/rule``, ``kda/gate_norm``),
the dense MLP ``GatedFFBlock_0`` (``fc1``, ``fc2``), the expert layer
``moe`` (``route``, with the group-limited selection under ``route/groups``,
``dispatch``, ``experts/fc1|fc2``, ``combine``, ``shared/fc1|fc2`` where it
has a shared expert); a hyper-connection's maps under ``hc_attn`` and
``hc_ffn`` (``hc/pre``, ``hc/sinkhorn``) and its merge under the layer
(``hc/post``); the module ``mtp`` (its head and loss under ``mtp/lm_head``);
the head ``lm_head`` (tied or not).
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
from sav_tpu.models.layers.gated_attention import GatedSelfAttentionBlock
from sav_tpu.models.layers.gated_delta import GatedDeltaNetBlock
from sav_tpu.models.layers.hyper_connection import HyperConnection, fan_in, fan_out
from sav_tpu.models.layers.kda import KDABlock
from sav_tpu.models.layers.short_conv import ShortConvBlock
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

# The hybrid family's choice: the names above (``mla_latent`` and ``hc_maps``
# tag nothing here) and three of the delta-rule block's own, a layer at 4 x
# 4,096 tokens: ``gdn_solved`` (the rule's ``T beta`` and masked ``Q K^T`` a
# chunk, 134 MB: with them the layer's recomputation runs the scan alone and
# not the triangular inverses a second time), ``gdn_conv`` (q, k, v after the
# convolution, 268 MB: spares the input projections and the convolution) and
# ``gdn_out`` (the rule's output, 134 MB). Chosen on a v5e at the published
# widths, 4 x 4,096 tokens and 32 of 512 experts held (sequences/s,
# ``memory_program_bytes``; my chip run, PR 37, call 3): ``gdn_out`` alone
# 6.195 in 15.00 GB, with ``gdn_solved`` 6.689 in 14.88 GB, with ``gdn_conv``
# too **6.755 in 15.19 GB**; the projections' results as well (402 MB a layer)
# compile to 15.60 GB for a described v5e, over the cell's 15.5, and carry no
# tag.
KEPT_UNDER_REMAT_BESIDE_RECURRENCE = KEPT_UNDER_REMAT + ("gdn_solved", "gdn_conv", "gdn_out")

# The convolution-attention hybrid's choice: the names above (``mla_latent``
# and ``hc_maps`` tag nothing here) and the short-convolution block's two, a
# layer at 4 x 8,192 tokens: ``sconv_in`` (the input projection's ``[B | C |
# x~]``, 403 MB: spares the backward pass the norm and the block's largest
# matmul, 2048 x 6144) and ``sconv_core`` (``C * c``, 134 MB: spares it the
# core's forward, a pass over four arrays). Chosen on a v5e at the published
# widths, 4 x 8,192 tokens and 8 of 64 experts held (seconds a step, the
# compiled step's bytes; my chip run, PR 39, calls 1 and 2): **both 0.5314 in
# 14.59 GB**, ``sconv_in`` alone 0.5326 in 14.78 GB (more, not less: XLA
# schedules the recomputed core where the step's memory peaks), ``sconv_core``
# alone 0.5498 in 14.71 GB, neither 0.5502 in 14.35 GB: the projection's
# result is worth 18 ms a step, the core's 1.
KEPT_UNDER_REMAT_BESIDE_CONVOLUTION = KEPT_UNDER_REMAT + ("sconv_in", "sconv_core")

# The vector-decay hybrid's choice: the names above (``hc_maps`` tags nothing
# here) and two of the KDA block's four, a layer at 2 x 4,096 tokens:
# ``gdn_solved`` (the rule's ``T beta`` and masked pair terms a chunk, 67 MB:
# with them the layer's recomputation runs the scan alone) and ``kda_out``
# (the rule's output, 67 MB); left to be computed again: ``kda_conv`` (q, k, v
# after the convolutions, 201 MB) and ``kda_gates`` (the two gate projections'
# results, 134 MB). Chosen on a v5e at the published widths, 2 x 4,096 tokens
# and 8 of 512 experts held, where the state alone is 12.27 GB (seconds a
# step, the compiled step's bytes; my chip run, PR 43, call 1): neither
# 0.6182 in 14.98 GB, ``gdn_solved`` alone 0.5770 in 14.90, **both 0.5712 in
# 14.71 GB**, with ``kda_conv`` 0.5671 in 15.19, with ``kda_gates`` 0.5565 in
# 15.19 (the four together compile to 15.67 GB). The last two are 0.7% and
# 2.6% faster and stand 0.3 GB under the cell's 15.5 GB before the harness's
# own buffers are counted: a chip with more room passes them.
KEPT_UNDER_REMAT_BESIDE_VECTOR_DECAY = KEPT_UNDER_REMAT + ("gdn_solved", "kda_out")


# The window/full hybrid's choice: the names above (``mla_latent`` and
# ``hc_maps`` tag nothing here; the block tags no name of its own), a layer at
# 1 x 4,096 tokens and 72 or 48 query heads of 128, where the state alone is
# 12.98 GB. By choice, the step's bytes compiled for a described v5e and, where
# a machine was had, sequences/s and ``memory_program_bytes`` on the chip (my
# chip run, PR 46, call A): **every name 4.727 in 13.30 GB (13.54 on the
# chip)**; without ``attn_qkv`` 13.82 GB and with ``flash_out``,
# ``flash_lse``, ``moe_route`` and ``moe_order`` alone 13.59 GB (more, not
# less: XLA schedules what is computed again where the step's memory peaks,
# as PR 39 met); nothing kept 13.24 GB (five more Mosaic calls: the forward
# kernels run again); no rematerialisation at all 14.82 GB, near the cell's
# 15.5 once the harness's own buffers are counted: not taken.
# On the chip (calls A and C, 5 s windows): without ``attn_qkv`` 4.563 in 14.06
# GB, the four names 4.312 in 13.84, nothing kept 4.058 in 13.50.
# Every name kept is within 0.06 GB of the least any choice compiles to and
# 2 GB under the cell's limit, so nothing is given up for room; what the kept
# ``attn_qkv`` does not spare is the q projection, which the backward pass
# computes again for the norm's sake (PERF.md section 7).
KEPT_UNDER_REMAT_BESIDE_WINDOWS = KEPT_UNDER_REMAT


# How a step's per-layer ``stats`` become one number: by key.
STAT_REDUCTIONS = {
    "hc_doubly_stochastic_err": jnp.max, "hc_stream_gain": jnp.max,
    "gdn_decay_min": jnp.min, "gdn_state_rms_max": jnp.max, "attn_gate_mean": jnp.mean,
    "attn_gate_mean_window": jnp.mean, "attn_gate_mean_full": jnp.mean,
    "sconv_out_rms_max": jnp.max,
    "kda_decay_min": jnp.min, "kda_state_rms_max": jnp.max, "moe_groups_held": jnp.mean,
}

# A layer's token mixer by the kind a ``mixers`` list gives it: the block,
# the prefix its ``stats`` take, and the field of :class:`JoyAILM` that holds
# its sizes. ``full_attention`` is the public configs' name for a softmax
# layer; both names build the grouped-query block. ``sliding_attention`` is
# their name for one over a window, the same block at sizes of its own (its
# head count may differ from the full layer's).
MIXER_BLOCKS = {
    "gated_delta": (GatedDeltaNetBlock, "gdn_", "gated_delta"),
    "gated_attention": (GatedSelfAttentionBlock, "attn_", "gated_attention"),
    "full_attention": (GatedSelfAttentionBlock, "attn_", "gated_attention"),
    "sliding_attention": (GatedSelfAttentionBlock, "attn_", "sliding_attention"),
    "conv": (ShortConvBlock, "sconv_", "short_conv"),
    "kda": (KDABlock, "kda_", "kda"),
}


def hybrid_mixers(num_layers: int, interval: int, full: str = "gated_attention", linear: str = "gated_delta") -> tuple:
    """The token mixer of each layer of a hybrid decoder: layer ``i`` is
    ``full`` where ``(i + 1) % interval == 0`` and ``linear`` otherwise (the
    public configs' rule, under their ``full_attention_interval`` or
    ``layer_group_size``)."""
    return tuple(full if (i + 1) % interval == 0 else linear for i in range(num_layers))


class LatentDecoderBlock(nn.Module):
    """One pre-norm layer; ``num_experts`` 0 makes its FFN the dense SwiGLU.
    ``mixer`` is the token mixer's kind (``latent``: latent attention at the
    sizes of the fields below; a key of :data:`MIXER_BLOCKS`: that block at
    ``mixer_sizes``). ``hc`` holds :class:`HyperConnection`'s sizes
    (``streams`` 1: the state is one array and a sublayer is ``h +
    F(RMSNorm(h))``). Returns ``(state, counts, balance, stats)``: the two in
    the middle ``None`` for a dense layer; ``stats`` a dict of float32 scalars
    under the keys of :data:`STAT_REDUCTIONS` (the hyper-connections' two, the
    larger of the two sublayers'; the delta-rule blocks' two each; the gated
    attention's one, and a head-wise gate's by its layer's kind; the short
    convolution's one; a group-limited expert layer's one), ``None`` or empty
    where the layer has none."""

    mlp_ch: int
    num_experts: int
    top_k: int
    routed_scale: float
    experts_held: Optional[Any]
    norm_eps: float
    num_heads: int = 0
    q_rank: Optional[int] = 0  # None: latent attention's direct query
    kv_rank: int = 0
    nope_ch: int = 0
    rope_ch: int = 0
    v_ch: int = 0
    rope_theta: float = 1e4
    rope_scaling: Optional[Any] = None
    latent_qk_norm: bool = False
    latent_gate: bool = False
    mixer: str = "latent"
    mixer_sizes: Optional[Any] = None  # the gated blocks' sizes as a dict
    norm_offset: bool = False  # the norms' weights are offsets from 1
    scoring: str = "sigmoid"
    shared_expert: bool = True
    shared_gate: bool = False
    router_weight_eps: float = 0.0
    n_group: int = 1
    topk_group: int = 1
    expert_limit: float = 0.0  # this layer's SwiGLU clamps; 0 = none
    shared_limit: float = 0.0
    hc: Optional[Any] = None  # HyperConnection's sizes as a dict; None = one stream
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs, select_bias: Optional[jax.Array]):
        def norm(name):
            return RMSNorm(eps=self.norm_eps, offset=self.norm_offset, dtype=self.dtype, name=name)

        def residual(name):
            return HyperConnection(**(self.hc or {"streams": 1}), norm_eps=self.norm_eps, dtype=self.dtype, name=name)

        def mix(x):
            """``(the token mixer's result, its stats or None)``."""
            shared = dict(norm_eps=self.norm_eps, quant=self.quant, dtype=self.dtype)
            attention = dict(rope_theta=self.rope_theta, backend=self.backend, logits_dtype=self.logits_dtype)
            if self.mixer in MIXER_BLOCKS:
                block, prefix, _ = MIXER_BLOCKS[self.mixer]
                options = dict(shared)
                if block is GatedSelfAttentionBlock:
                    options.update(attention, norm_offset=self.norm_offset)
                if block is ShortConvBlock:
                    del options["norm_eps"]  # no norm inside
                # A kind's own sizes come last: two kinds of softmax layer in
                # one decoder differ in their rotary base too.
                out, stats = block(**{**options, **self.mixer_sizes})(x)
                return out, {prefix + k: v for k, v in stats.items()}
            if self.mixer != "latent":
                raise ValueError(f"token mixer {self.mixer!r}: latent or one of {sorted(MIXER_BLOCKS)}")
            return LatentSelfAttentionBlock(
                num_heads=self.num_heads,
                q_rank=self.q_rank,
                kv_rank=self.kv_rank,
                nope_ch=self.nope_ch,
                rope_ch=self.rope_ch,
                v_ch=self.v_ch,
                rope_scaling=self.rope_scaling,
                qk_norm=self.latent_qk_norm,
                gate=self.latent_gate,
                **attention,
                **shared,
            )(x), None

        u, merge = residual("hc_attn")(inputs)
        a, stats = mix(norm("attn_norm")(u))
        x, attn_stats = merge(a)
        u, merge = residual("hc_ffn")(x)
        y = norm("ffn_norm")(u)
        if not self.num_experts:
            m, counts, balance = GatedFFBlock(hidden_ch=self.mlp_ch, quant=self.quant, dtype=self.dtype)(y), None, None
        else:
            m, counts, balance, moe_stats = SparseMoEBlock(
                num_experts=self.num_experts,
                top_k=self.top_k,
                hidden_ch=self.mlp_ch,
                routed_scale=self.routed_scale,
                experts_held=self.experts_held,
                scoring=self.scoring,
                shared_expert=self.shared_expert,
                shared_gate=self.shared_gate,
                weight_eps=self.router_weight_eps,
                n_group=self.n_group,
                topk_group=self.topk_group,
                limit=self.expert_limit,
                shared_limit=self.shared_limit,
                quant=self.quant,
                dtype=self.dtype,
                name="moe",
            )(y, select_bias)
            if moe_stats:
                stats = dict(stats or {}, **{"moe_" + k: v for k, v in moe_stats.items()})
        x, ffn_stats = merge(m)
        if attn_stats:
            err, gain = jax.tree.map(jnp.maximum, attn_stats, ffn_stats)
            stats = dict(stats or {}, hc_doubly_stochastic_err=err, hc_stream_gain=gain)
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
      ``"moe_bias_abs_max" [B]`` and the layers' ``stats`` reduced over the
      layers that have them (:data:`STAT_REDUCTIONS`), each ``[B]`` with the
      same number in every row: at ``hc_mult`` > 1
      ``"hc_doubly_stochastic_err"`` and ``"hc_stream_gain"`` (the largest of
      any sublayer's :class:`HyperConnection` ``stats``); with delta-rule
      layers ``"gdn_decay_min"`` (the smallest ``exp(g_t)`` of the step) and
      ``"gdn_state_rms_max"`` (the largest RMS of any head's final state);
      with gated attention layers ``"attn_gate_mean"`` (and, gated a head,
      ``"attn_gate_mean_window"`` / ``"attn_gate_mean_full"``: the mean over
      the layers of that kind); with short-convolution
      layers ``"sconv_out_rms_max"`` (the largest RMS of any block's and
      sequence's ``C * c``); with vector-decay layers ``"kda_decay_min"`` (the
      smallest ``g`` of the step, a log: how near the gate's lower bound it
      runs) and ``"kda_state_rms_max"``; with group-limited routing
      ``"moe_groups_held"`` (the mean over the routed layers of the share of
      tokens whose kept groups include a group of the experts held).
    """

    num_classes: int  # the vocabulary held here
    embed_dim: int
    num_layers: int
    mlp_ch: int
    expert_ch: int
    num_experts: int
    top_k: int
    routed_scale: float
    # Latent attention's sizes, where that is the token mixer.
    num_heads: int = 0
    q_rank: Optional[int] = 0  # None: the direct query (the public configs' q_lora_rank null)
    kv_rank: int = 0
    nope_ch: int = 0
    rope_ch: int = 0
    v_ch: int = 0
    latent_qk_norm: bool = False  # use_qk_norm: a norm a query head and one on the rotary key
    latent_gate: bool = False  # a sigmoid gate a head on the latent core's output (head_wise)
    # A hybrid decoder's token mixers: a kind a layer (``latent`` or a key of
    # MIXER_BLOCKS; a depth cut runs the first ``num_layers`` of them), or the
    # public configs' full_attention_interval, from which hybrid_mixers builds
    # the list; neither = latent attention in every layer. The blocks' sizes
    # are dicts of their constructors' arguments.
    mixers: Optional[tuple] = None
    full_attention_interval: int = 0
    gated_attention: Optional[Any] = None
    sliding_attention: Optional[Any] = None
    gated_delta: Optional[Any] = None
    short_conv: Optional[Any] = None
    kda: Optional[Any] = None
    norm_offset: bool = False  # RMSNorm weights stored as offsets from 1
    scoring: str = "sigmoid"  # the router's: sigmoid | softmax
    shared_expert: bool = True  # False: the expert layer is the routed sum alone
    shared_gate: bool = False  # the shared expert behind sigmoid(x w_s)
    router_weight_eps: float = 0.0  # added to the selected scores' sum before the division
    n_group: int = 1  # > 1: the router picks inside topk_group of n_group groups of experts
    topk_group: int = 1
    # The SwiGLU clamp of each layer's routed experts and of its shared expert
    # (the public configs' lists, a number a published layer; 0 = none).
    expert_limits: Optional[tuple] = None
    shared_limits: Optional[tuple] = None
    tie_head: bool = False  # the head reads the embedding's table
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

        mixers = ("latent",) * (self.num_layers + self.mtp_modules)
        if self.mixers or self.full_attention_interval:
            if self.mtp_modules:
                raise ValueError("a hybrid decoder has no multi-token-prediction module here")
            mixers = tuple(self.mixers or hybrid_mixers(self.num_layers, self.full_attention_interval))
            if len(mixers) < self.num_layers:
                raise ValueError(f"{len(mixers)} token mixers for {self.num_layers} layers")

        def sizes_of(mixer: str):
            return dict(getattr(self, MIXER_BLOCKS[mixer][2])) if mixer in MIXER_BLOCKS else None

        def limit_of(limits, index) -> float:
            return float(limits[index]) if limits and index is not None else 0.0

        def block(name: str, routed: bool, mixer: str = "latent", index: Optional[int] = None):
            return block_cls(
                num_heads=self.num_heads,
                q_rank=self.q_rank,
                latent_qk_norm=self.latent_qk_norm,
                latent_gate=self.latent_gate,
                n_group=self.n_group,
                topk_group=self.topk_group,
                expert_limit=limit_of(self.expert_limits, index),
                shared_limit=limit_of(self.shared_limits, index),
                kv_rank=self.kv_rank,
                nope_ch=self.nope_ch,
                rope_ch=self.rope_ch,
                v_ch=self.v_ch,
                mixer=mixer,
                mixer_sizes=sizes_of(mixer),
                norm_offset=self.norm_offset,
                scoring=self.scoring,
                shared_expert=self.shared_expert,
                shared_gate=self.shared_gate,
                router_weight_eps=self.router_weight_eps,
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
        counts, balances, layer_stats = [], [], []
        for i in range(self.num_layers):
            routed = i >= self.first_dense
            bias = select_bias.value[i - self.first_dense] if routed else None
            h, c, b, stats = block(f"layer_{i}", routed, mixers[i], i)(h, bias)
            layer_stats.append(stats)
            if routed:
                counts.append(c)
                balances.append(b)
        h = fan_in(h)
        final_norm = RMSNorm(eps=self.norm_eps, offset=self.norm_offset, dtype=self.dtype, name="final_norm")
        table = embed.embedding if self.tie_head else None
        main = head(final_norm(h), targets, table)
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
            layer_stats.append(stats)
            with jax.named_scope("mtp"):
                mtp_targets = jnp.concatenate([targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1)
                has_target = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
                out["ce_mtp"] = jnp.where(has_target[None, :], head(h_mtp, mtp_targets, table), 0.0)

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
        for key, reduce in STAT_REDUCTIONS.items():
            column = [stats[key] for stats in layer_stats if stats and key in stats]
            if column:
                out[key] = jnp.broadcast_to(reduce(jnp.stack(column)), tokens.shape[:1])
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
