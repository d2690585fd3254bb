"""ViT / DeiT model FLOPs from a configuration's sizes (a configuration
names this file by its ``flops`` key): what the algorithm needs, no
recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``; the
attention core costs ``4 L^2 D`` per image and layer (QK^T and PV);
training costs three forwards (the backward pass is twice the forward's
matmul work). LayerNorm, softmax, GELU and bias adds are left out: a few
percent at these shapes. The arithmetic is that of the program's
``sav_tpu/obs/costs.py`` (verdict in PERF.md: sound), from sizes and not
from a parameter tree.
"""

from __future__ import annotations


def forward_flops_per_image(config: dict) -> float:
    d = config["hidden_size"]
    ff = config["intermediate_size"]
    p = config["patch_size"]
    patches = (config["image_size"] // p) ** 2
    length = patches + 1
    patch_embed = 2.0 * patches * (p * p * config["num_channels"]) * d
    per_layer = (
        2.0 * length * d * 3 * d      # Q, K, V projections
        + 2.0 * length * d * d        # output projection
        + 4.0 * length * length * d   # QK^T and PV
        + 2.0 * 2.0 * length * d * ff  # MLP
    )
    head = 2.0 * d * config["num_classes"]
    return patch_embed + config["num_hidden_layers"] * per_layer + head


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)
