"""Layer zoo re-exports (parity with /root/reference/models/layers/__init__.py:1-7)."""

from sav_tpu.models.layers.attention import (
    AttentionBlock,
    SelfAttentionBlock,
    TalkingHeadsBlock,
)
from sav_tpu.models.layers.bot_attention import BoTMHSA
from sav_tpu.models.layers.class_attention import (
    ClassSelfAttentionBlock,
    LCSelfAttentionBlock,
)
from sav_tpu.models.layers.cvt_attention import (
    ConvProjectionBlock,
    CvTAttentionBlock,
    CvTSelfAttentionBlock,
)
from sav_tpu.models.layers.latent_attention import LatentSelfAttentionBlock
from sav_tpu.models.layers.feedforward import FFBlock, GatedFFBlock, LeFFBlock
from sav_tpu.models.layers.moe import MoEFFBlock, SparseMoEBlock
from sav_tpu.models.layers.normalization import LayerScaleBlock, RMSNorm
from sav_tpu.models.layers.position_embed import (
    AddAbsPosEmbed,
    FixedPositionalEmbedding,
    RotaryPositionalEmbedding,
)
from sav_tpu.models.layers.regularization import StochasticDepthBlock
from sav_tpu.models.layers.squeeze_excite import SqueezeExciteBlock
from sav_tpu.models.layers.stems import Image2TokenBlock, PatchEmbedBlock

__all__ = [
    "AttentionBlock",
    "SelfAttentionBlock",
    "TalkingHeadsBlock",
    "BoTMHSA",
    "ClassSelfAttentionBlock",
    "LCSelfAttentionBlock",
    "ConvProjectionBlock",
    "CvTAttentionBlock",
    "CvTSelfAttentionBlock",
    "FFBlock",
    "GatedFFBlock",
    "LeFFBlock",
    "LatentSelfAttentionBlock",
    "MoEFFBlock",
    "SparseMoEBlock",
    "LayerScaleBlock",
    "RMSNorm",
    "AddAbsPosEmbed",
    "FixedPositionalEmbedding",
    "RotaryPositionalEmbedding",
    "StochasticDepthBlock",
    "SqueezeExciteBlock",
    "Image2TokenBlock",
    "PatchEmbedBlock",
]
