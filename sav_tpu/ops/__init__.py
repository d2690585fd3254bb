from sav_tpu.ops.attention import (
    AttentionDispatch,
    clear_dispatch_log,
    dot_product_attention,
    resolve_attention_backend,
    snapshot_dispatch_log,
    xla_attention,
)
from sav_tpu.ops.flash_attention import flash_attention, flash_botnet_attention
from sav_tpu.ops.fused_attention import fused_attention, fused_eligible
from sav_tpu.ops.relative import relative_logits_2d
from sav_tpu.ops.rotary import fixed_positional_embedding, apply_rotary_pos_emb

__all__ = [
    "AttentionDispatch",
    "clear_dispatch_log",
    "dot_product_attention",
    "resolve_attention_backend",
    "snapshot_dispatch_log",
    "xla_attention",
    "flash_attention",
    "flash_botnet_attention",
    "fused_attention",
    "fused_eligible",
    "relative_logits_2d",
    "fixed_positional_embedding",
    "apply_rotary_pos_emb",
]
