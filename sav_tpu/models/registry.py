"""Named model registry.

Replaces the reference's if/elif factory (/root/reference/models/create_model.py:6-215)
with a declarative dict. All 31 reference config names resolve here, with the
reference's config bugs fixed against the papers (SURVEY.md §2.9):
  - #13 TNT-S/TNT-B hyperparameters un-swapped,
  - #14 CvT embed dim 384 (not 368),
  - #15 duplicate ``mixer_s_patch32`` key → ``mixer_b_patch16``; Mixer-L has
    24 layers.
Extra names beyond reference parity: ``vit_s_patch16`` / ``deit_s_patch16``
(the BASELINE.json north-star benchmark model) and ``vit_ti_patch16``
(the CPU-runnable smoke config).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax.numpy as jnp

from sav_tpu.models.botnet import BoTNet
from sav_tpu.models.cait import CaiT
from sav_tpu.models.ceit import CeiT
from sav_tpu.models.cvt import CvT
from sav_tpu.models.joyai import (
    KEPT_UNDER_REMAT_BESIDE_CONVOLUTION,
    KEPT_UNDER_REMAT_BESIDE_RECURRENCE,
    KEPT_UNDER_REMAT_BESIDE_STREAMS,
    KEPT_UNDER_REMAT_BESIDE_VECTOR_DECAY,
    KEPT_UNDER_REMAT_BESIDE_WINDOWS,
    JoyAILM,
    hybrid_mixers,
)
from sav_tpu.models.mlp_mixer import MLPMixer
from sav_tpu.models.ouro import OuroLM
from sav_tpu.models.tnt import TNT
from sav_tpu.models.vit import ViT

_REGISTRY: dict[str, tuple[type, dict[str, Any]]] = {}
# What the trainer's step feeds a model and scores it by
# (sav_tpu/train/tasks.py); every entry that names none classifies images.
_TASKS: dict[str, str] = {}


def register(name: str, cls: type, *, task: str = "image", **kwargs):
    _REGISTRY[name] = (cls, kwargs)
    _TASKS[name] = task


def _vit(embed_dim, num_layers, num_heads, patch):
    return dict(
        embed_dim=embed_dim,
        num_layers=num_layers,
        num_heads=num_heads,
        patch_shape=(patch, patch),
    )


# --- ViT family (create_model.py:10-37 + north-star extras) -----------------
register("vit_ti_patch16", ViT, **_vit(192, 12, 3, 16))
register("vit_s_patch32", ViT, **_vit(384, 12, 6, 32))
register("vit_s_patch16", ViT, **_vit(384, 12, 6, 16))
register("deit_s_patch16", ViT, **_vit(384, 12, 6, 16))
register("vit_b_patch32", ViT, **_vit(768, 12, 12, 32))
register("vit_b_patch16", ViT, **_vit(768, 12, 12, 16))
register("vit_l_patch32", ViT, **_vit(1024, 24, 16, 32))
register("vit_l_patch16", ViT, **_vit(1024, 24, 16, 16))
# RoPE variant: the reference declared rotary in its to-do (README.md:5) but
# never wired it (SURVEY.md §2.9 #12); here it is a working first-class config.
register("vit_s_patch16_rope", ViT, **_vit(384, 12, 6, 16), pos_embed="rotary")
# MoE variant (beyond reference parity): DeiT-S trunk with a top-2-routed
# 8-expert FF on every other block; experts shard over the 'expert' mesh axis.
register(
    "vit_moe_s_patch16_e8",
    ViT,
    **_vit(384, 12, 6, 16),
    moe_num_experts=8,
    moe_top_k=2,
)

# --- BoTNet (create_model.py:38-49) ----------------------------------------
register("botnet_t3", BoTNet, stage_sizes=(3, 4, 6, 6))
register("botnet_t4", BoTNet, stage_sizes=(3, 4, 23, 6))
register("botnet_t5", BoTNet, stage_sizes=(3, 4, 23, 12))

# --- TNT (create_model.py:50-63; S/B fixed per paper & tnt_test.py:14-15) ---
register(
    "tnt_s_patch16",
    TNT,
    embed_dim=384, inner_ch=24, num_layers=12, num_heads=6, inner_num_heads=4,
    patch_shape=(16, 16),
)
register(
    "tnt_b_patch16",
    TNT,
    embed_dim=640, inner_ch=40, num_layers=12, num_heads=10, inner_num_heads=4,
    patch_shape=(16, 16),
)

# --- CeiT (create_model.py:64-78) ------------------------------------------
register("ceit_t", CeiT, embed_dim=192, num_layers=12, num_heads=3, patch_shape=(4, 4))
register("ceit_s", CeiT, embed_dim=384, num_layers=12, num_heads=6, patch_shape=(4, 4))
register("ceit_b", CeiT, embed_dim=768, num_layers=12, num_heads=12, patch_shape=(4, 4))


# --- CaiT (create_model.py:79-168) -----------------------------------------
def _cait(embed_dim, num_layers, num_heads, stoch_depth_rate, layerscale_eps):
    return dict(
        embed_dim=embed_dim,
        num_layers=num_layers,
        num_layers_token_only=2,
        num_heads=num_heads,
        patch_shape=(16, 16),
        stoch_depth_rate=stoch_depth_rate,
        layerscale_eps=layerscale_eps,
    )


register("cait_xxs_24", CaiT, **_cait(192, 24, 4, 0.05, 1e-5))
register("cait_xxs_36", CaiT, **_cait(192, 36, 4, 0.1, 1e-6))
register("cait_xs_24", CaiT, **_cait(288, 24, 6, 0.05, 1e-5))
register("cait_xs_36", CaiT, **_cait(288, 36, 6, 0.1, 1e-6))
register("cait_s_24", CaiT, **_cait(384, 24, 8, 0.1, 1e-5))
register("cait_s_36", CaiT, **_cait(384, 36, 8, 0.2, 1e-6))
register("cait_s_48", CaiT, **_cait(384, 48, 8, 0.3, 1e-6))
register("cait_m_24", CaiT, **_cait(768, 24, 16, 0.2, 1e-5))
register("cait_m_36", CaiT, **_cait(768, 36, 16, 0.3, 1e-6))
register("cait_m_48", CaiT, **_cait(768, 48, 16, 0.4, 1e-6))

# --- CvT (create_model.py:169-183; 384 per paper & cvt_test.py:14-15) -------
register(
    "cvt-13", CvT,
    embed_dims=(64, 192, 384), num_layers=(1, 2, 10), num_heads=(1, 3, 6),
)
register(
    "cvt-21", CvT,
    embed_dims=(64, 192, 384), num_layers=(1, 4, 16), num_heads=(1, 3, 6),
)
register(
    "cvt-w24", CvT,
    embed_dims=(192, 768, 1024), num_layers=(2, 2, 20), num_heads=(3, 12, 16),
)


# --- MLP-Mixer (create_model.py:184-213; keys/layers fixed per paper) -------
def _mixer(embed_dim, num_layers, tokens_ch, channels_ch, patch):
    return dict(
        embed_dim=embed_dim,
        num_layers=num_layers,
        tokens_hidden_ch=tokens_ch,
        channels_hidden_ch=channels_ch,
        patch_shape=(patch, patch),
    )


register("mixer_s_patch32", MLPMixer, **_mixer(512, 8, 256, 2048, 32))
register("mixer_s_patch16", MLPMixer, **_mixer(512, 8, 256, 2048, 16))
register("mixer_b_patch32", MLPMixer, **_mixer(768, 12, 384, 3072, 32))
register("mixer_b_patch16", MLPMixer, **_mixer(768, 12, 384, 3072, 16))
register("mixer_l_patch32", MLPMixer, **_mixer(1024, 24, 512, 4096, 32))
register("mixer_l_patch16", MLPMixer, **_mixer(1024, 24, 512, 4096, 16))


# --- Ouro (looped language model; arXiv:2510.25741) -------------------------
# Sizes of https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json;
# ``num_classes`` is the vocabulary (49,152 there). 2.67 B parameters: one
# chip holds a cut in depth (model_overrides={"num_layers": 4, ...}).
register(
    "ouro_2_6b",
    OuroLM,
    task="tokens",
    embed_dim=2048, num_layers=48, num_heads=16, head_ch=128, mlp_ch=5632,
    ut_steps=4, rope_theta=1e6, norm_eps=1e-6,
)

# --- JoyAI-LLM-Flash (latent attention, routed + shared experts, MTP) -------
# Sizes of https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/
# config.json; ``num_classes`` is the vocabulary (129,280 there). 48.9 B
# parameters: one chip holds a cut in depth and its share of every expert
# layer (model_overrides={"num_layers": 5, "experts_held": (0, 16), ...}).
register(
    "joyai_llm_flash",
    JoyAILM,
    task="tokens_mtp",
    embed_dim=2048, num_layers=40, num_heads=32, q_rank=1536, kv_rank=512,
    nope_ch=128, rope_ch=64, v_ch=128, mlp_ch=7168, expert_ch=768,
    num_experts=256, top_k=8, routed_scale=2.5, first_dense=1,
    rope_theta=32e6, norm_eps=1e-6,
)

# --- Xing4.0-29B-A4B (the same sublayers between hyper-connected streams) ---
# Sizes of https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/
# config.json; ``num_classes`` is the vocabulary (131,072 there). 29.5 B
# parameters (3.9 B active a token) + 0.77 B in the MTP module: one chip holds
# a cut in depth and its share of every expert layer (model_overrides=
# {"num_layers": 5, "first_dense": 1, "experts_held": (0, 8), "mtp_modules": 0}).
# ``kept_under_remat`` is what fits that cut's step into one v5e's 16 GB (all
# of KEPT_UNDER_REMAT compiles to 15.53 GB, this choice to 14.47 GB), not a
# property of the architecture: a chip with more room passes KEPT_UNDER_REMAT.
register(
    "xing4_0_29b_a4b",
    JoyAILM,
    task="tokens_mtp",
    embed_dim=3584, num_layers=40, num_heads=32, q_rank=768, kv_rank=512,
    nope_ch=128, rope_ch=64, v_ch=128, mlp_ch=9216, expert_ch=1024,
    num_experts=64, top_k=4, routed_scale=2.0, first_dense=2, mtp_modules=1,
    rope_theta=1e4, norm_eps=1e-6,
    rope_scaling={
        "type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
    },
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0),
    kept_under_remat=KEPT_UNDER_REMAT_BESIDE_STREAMS,
)

# --- Qwen3-Next-80B-A3B (delta-rule layers with a softmax layer every fourth) -
# Sizes of https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/
# config.json; ``num_classes`` is the vocabulary (151,936 there). 79.7 B
# parameters (3 B active a token): one chip holds one period of the layer
# pattern and its share of every expert layer (model_overrides={"num_layers":
# 4, "experts_held": (0, 32)}). No dense layer (``mlp_only_layers`` empty:
# ``mlp_ch``, the config's ``intermediate_size``, is unused), no selection
# bias (``bias_update_rate`` 0: the row stays zero), no MTP module.
register(
    "qwen3_next_80b_a3b",
    JoyAILM,
    task="tokens_mtp",
    embed_dim=2048, num_layers=48, mlp_ch=5120, expert_ch=512,
    num_experts=512, top_k=10, routed_scale=1.0, first_dense=0, mtp_modules=0,
    bias_update_rate=0.0, scoring="softmax", shared_gate=True, norm_offset=True,
    full_attention_interval=4,
    gated_attention={"num_heads": 16, "kv_heads": 2, "head_ch": 256, "rotary_ch": 64},
    gated_delta={"key_heads": 16, "heads": 32, "key_ch": 128, "value_ch": 128, "conv_width": 4},
    rope_theta=1e7, norm_eps=1e-6,
    kept_under_remat=KEPT_UNDER_REMAT_BESIDE_RECURRENCE,
)

# --- LFM2-24B-A2B (short convolutions with a softmax layer, no shared expert) -
# Sizes of https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json;
# ``num_classes`` is the vocabulary (65,536 there). ``mixers`` is the config's
# ``layer_types`` letter for letter (30 ``conv``, 10 ``full_attention``: no
# interval gives them). Two leading dense layers at 11,776, then 64
# sigmoid-routed experts of 1,536, top-4 of score + a selection bias that is
# state, no shared expert; 32 query heads of 64 on 8 key/value heads, plain
# norm weights, rotary on the whole head; the head reads the embedding's
# table. 23.8 B parameters (2.3 B active a token): one chip holds a cut in
# depth and its share of every expert layer (model_overrides={"num_layers": 5,
# "first_dense": 1, "mixers": [...], "experts_held": (0, 8)}).
LFM2_LAYER_TYPES = ("conv", "conv", "full_attention") + ("conv", "conv", "conv", "full_attention") * 9 + ("conv",)
register(
    "lfm2_24b_a2b",
    JoyAILM,
    task="tokens_mtp",
    embed_dim=2048, num_layers=40, mlp_ch=11776, expert_ch=1536,
    num_experts=64, top_k=4, routed_scale=1.0, first_dense=2, mtp_modules=0,
    bias_update_rate=1e-3, scoring="sigmoid", shared_expert=False, router_weight_eps=1e-6,
    tie_head=True, mixers=LFM2_LAYER_TYPES,
    gated_attention={"num_heads": 32, "kv_heads": 8, "head_ch": 64, "rotary_ch": 64, "gate": False},
    short_conv={"conv_width": 3},
    rope_theta=1e6, norm_eps=1e-5,
    kept_under_remat=KEPT_UNDER_REMAT_BESIDE_CONVOLUTION,
)


# --- Ling-3.0-flash (vector-decay delta rule 5:1 with gated latent attention) -
# The language model of https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/
# blob/main/config.json (the vision tower has no key there and is not built);
# ``num_classes`` is the vocabulary (157,184 there). Layer ``i`` mixes by latent
# attention where ``(i + 1) % layer_group_size (6) == 0`` (7 layers: the direct
# query, q_lora_rank null; use_qk_norm; a head-wise gate) and by Kimi delta
# attention otherwise (35 layers: 32 heads of 128, convolution 4, the safe gate
# at kda_lower_bound -5). Two leading dense layers at 6,144, then 512
# sigmoid-routed experts of 768, top-8 inside 4 of 8 groups, a selection bias
# that is state, one shared expert; the experts' SwiGLU clamped in the last
# layers (the config's two lists). 124.4 B parameters (5.5 B active a token):
# one chip holds the first period with one dense layer and its share of every
# expert layer (model_overrides={"num_layers": 6, "first_dense": 1,
# "experts_held": (0, 8)}).
LING_EXPERT_LIMITS = (0,) * 35 + (4,) * 7
LING_SHARED_LIMITS = (0,) * 34 + (5,) * 6 + (7,) * 2
register(
    "ling_3.0_flash",
    JoyAILM,
    task="tokens_mtp",
    embed_dim=2560, num_layers=42, mlp_ch=6144, expert_ch=768,
    num_heads=32, q_rank=None, kv_rank=512, nope_ch=128, rope_ch=64, v_ch=128,
    latent_qk_norm=True, latent_gate=True,
    mixers=hybrid_mixers(42, 6, full="latent", linear="kda"),
    kda={"heads": 32, "key_ch": 128, "value_ch": 128, "conv_width": 4, "lower_bound": -5.0},
    num_experts=512, top_k=8, routed_scale=2.5, n_group=8, topk_group=4,
    first_dense=2, mtp_modules=0, bias_update_rate=1e-3, scoring="sigmoid",
    expert_limits=LING_EXPERT_LIMITS, shared_limits=LING_SHARED_LIMITS,
    rope_theta=6e6, norm_eps=1e-6,
    kept_under_remat=KEPT_UNDER_REMAT_BESIDE_VECTOR_DECAY,
)


# --- Laguna-S-2.1 (three sliding-window layers of 72 query heads to one full
# layer of 48, per-head output gates, two rotaries) ---------------------------
# Sizes of https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json;
# ``num_classes`` is the vocabulary (100,352 there). ``mixers`` is the config's
# ``layer_types`` (layer ``i`` is ``full_attention`` where ``i % 4 == 0``, 12
# layers, and ``sliding_attention`` otherwise, 36), and the head counts are its
# ``num_attention_heads_per_layer``: 48 query heads in a full layer, 72 in a
# window layer, each on 8 key/value heads of 128. A window layer sees 512
# positions (``sliding_window``), rotary on the whole head at base 10,000; a
# full layer turns the leading 64 lanes (``partial_rotary_factor`` 0.5) at base
# 500,000 under YaRN (factor 128 over 8,192 positions) with ``attention_factor``
# on the tables. Both norm q and k a head (plain weights) and gate the output a
# head. One leading dense layer at 12,288, then 256 softmax-routed experts of
# 1,024, top-10 normalised and scaled by 2.5, no selection bias, a shared
# expert behind a sigmoid gate; an untied head. 117.56 B parameters by the
# tree's count: one chip holds the first five layers and its share of every
# expert layer (model_overrides={"num_layers": 5, "experts_held": (0, 8)}).
LAGUNA_LAYER_TYPES = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention") * 12
register(
    "laguna_s_2.1",
    JoyAILM,
    task="tokens_mtp",
    embed_dim=3072, num_layers=48, mlp_ch=12288, expert_ch=1024,
    num_experts=256, top_k=10, routed_scale=2.5, first_dense=1, mtp_modules=0,
    bias_update_rate=0.0, scoring="softmax", shared_gate=True, norm_offset=False,
    mixers=LAGUNA_LAYER_TYPES,
    gated_attention={
        "num_heads": 48, "kv_heads": 8, "head_ch": 128, "rotary_ch": 64, "gate": "head", "rope_theta": 5e5,
        "rope_scaling": {
            "rope_type": "yarn", "factor": 128.0, "original_max_position_embeddings": 8192,
            "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.4852030263919618,
        },
    },
    sliding_attention={
        "num_heads": 72, "kv_heads": 8, "head_ch": 128, "rotary_ch": 128, "gate": "head", "rope_theta": 1e4,
        "window": 512,
    },
    norm_eps=1e-6,
    kept_under_remat=KEPT_UNDER_REMAT_BESIDE_WINDOWS,
)


def model_names() -> list[str]:
    return sorted(_REGISTRY)


def create_model(
    model_name: str,
    *,
    num_classes: int = 1000,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    logits_dtype=None,
    seq_parallel: Optional[str] = None,
    seq_mesh=None,
    layout=None,
    quant: Optional[str] = None,
    **overrides,
):
    """Instantiate a named model config.

    Args:
      model_name: a key from :func:`model_names`.
      num_classes: classifier width.
      dtype: compute dtype (params stay fp32).
      backend: attention backend ('xla' | 'fused' | 'pallas' | None=auto —
        the measured three-way dispatch) threaded to every attention block.
      logits_dtype: softmax dtype for the XLA attention path, threaded to
        every attention block (None = inherit ``dtype``, the reference's
        semantics; 'float32' forces f32 softmax under bf16 compute).
      seq_parallel: 'ring' | 'ulysses' — route self-attention through
        sequence parallelism over ``seq_mesh``'s 'seq' axis
        (sav_tpu.parallel.seq_parallel; ViT/DeiT every block, TNT outer
        stream, CeiT trunk — others raise).
      seq_mesh: the jax.sharding.Mesh carrying the 'seq' axis; required
        with ``seq_parallel``.
      quant: int8 quantized projection/FFN dots (sav_tpu/ops/quant.py):
        "int8" (AQT-style QAT training arm) or "int8_serve" (int8
        weights + per-channel scales, the quantized serving tree) —
        threaded to every projection/FFN/head dot in every family; the
        attention QK/AV core stays in ``dtype`` (PERF §5). None = the
        plain float path, byte-identical param tree to before.
      layout: a :class:`~sav_tpu.parallel.layout.BoundLayout` threaded to
        models with a layout seam (ViT family): encoder blocks pin token
        activations to the layout's activation spec — the 2D-TP
        between-block constraint (docs/parallelism.md). Models without
        the seam ignore it (their specs still come from the layout's
        param rules at placement time).
      **overrides: per-call hyperparameter overrides.
    """
    if model_name not in _REGISTRY:
        raise ValueError(
            f"unknown model {model_name!r}; available: {', '.join(model_names())}"
        )
    cls, kwargs = _REGISTRY[model_name]
    merged = dict(kwargs, num_classes=num_classes, dtype=dtype, **overrides)
    # Attention-free models (MLP-Mixer) have no backend seam — skip injection.
    if backend is not None and "backend" in cls.__dataclass_fields__:
        merged["backend"] = backend
    if logits_dtype is not None and "logits_dtype" in cls.__dataclass_fields__:
        merged["logits_dtype"] = logits_dtype
    if layout is not None and "layout" in cls.__dataclass_fields__:
        merged["layout"] = layout
    if quant is not None:
        if "quant" not in cls.__dataclass_fields__:
            raise ValueError(
                f"{model_name!r} does not support the int8 quant arm "
                "(every registered family does — a custom class must "
                "declare a 'quant' field to opt in)"
            )
        merged["quant"] = quant
    if seq_parallel is not None:
        if "seq_parallel" not in cls.__dataclass_fields__:
            raise ValueError(
                f"{model_name!r} does not support sequence parallelism "
                "(SP-capable: ViT/DeiT, TNT outer stream, CeiT trunk, "
                "CaiT trunk (ring-only, talking-heads); CvT's strided conv "
                "projections and BoTNet's 2-D relative-position bias keep "
                "the dense path — see docs/parallelism.md)"
            )
        merged["seq_parallel"] = seq_parallel
        merged["seq_mesh"] = seq_mesh
    return cls(**merged)


def model_task(model_name: str) -> str:
    """The task the named model trains on, a key of
    ``sav_tpu.train.tasks.TASKS``."""
    if model_name not in _REGISTRY:
        raise ValueError(
            f"unknown model {model_name!r}; available: {', '.join(model_names())}"
        )
    return _TASKS[model_name]


def model_supports(model_name: str, field: str) -> bool:
    """Whether the named model's class has ``field`` as a constructor
    option (e.g. 'remat' — ViT-family only; 'backend' — attention models)."""
    if model_name not in _REGISTRY:
        raise ValueError(
            f"unknown model {model_name!r}; available: {', '.join(model_names())}"
        )
    cls, _ = _REGISTRY[model_name]
    return field in cls.__dataclass_fields__
