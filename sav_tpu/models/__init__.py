"""Model zoo re-exports (parity with /root/reference/models/__init__.py:1-8)."""

from sav_tpu.models.botnet import BoTNet
from sav_tpu.models.cait import CaiT
from sav_tpu.models.ceit import CeiT
from sav_tpu.models.cvt import CvT
from sav_tpu.models.joyai import JoyAILM
from sav_tpu.models.mlp_mixer import MLPMixer
from sav_tpu.models.ouro import OuroLM
from sav_tpu.models.registry import (
    create_model,
    model_names,
    model_supports,
    model_task,
    register,
)
from sav_tpu.models.surgery import adapt_pos_embeds, resize_pos_embed_table
from sav_tpu.models.tnt import TNT
from sav_tpu.models.vit import ViT

__all__ = [
    "adapt_pos_embeds",
    "resize_pos_embed_table",
    "ViT",
    "BoTNet",
    "CeiT",
    "CaiT",
    "CvT",
    "TNT",
    "MLPMixer",
    "OuroLM",
    "JoyAILM",
    "create_model",
    "model_names",
    "model_supports",
    "model_task",
    "register",
]
