"""Xing4.0 model FLOPs, its kernels' FLOPs and bytes, and the bytes its
residual path has to move, from a configuration's sizes (a configuration names
this file by its ``flops`` key): what the algorithm needs, no recomputation
counted.

The sublayers are ``flops/joyai.py``'s at other widths, so their counts are
that file's functions, imported: the latent projections, the causal attention
core at two head sizes, SwiGLU, the router, the routed experts at the EXPECTED
share of routings on the experts held (``k x held / published`` = 4 x 8 / 64 =
0.5 a token), and the two kernels' floors, which the accepted readers look up
by name (``attention_floor_seconds``, ``grouped_matmul_floor_seconds``,
``held_routings_per_token``). YaRN changes no count.

What this family adds is the hyper-connection around every sublayer (two a
layer), per token: the projection ``[n d] x [n d, 2n + n^2]``, the weighted
stream sum ``u`` (``n d`` multiply-adds), the mix ``H_res X`` (``n^2 d``) and
``h_post y`` added to every stream (``n d``). The RMS, the gates and the
Sinkhorn iterations (20 x two normalisations of 16 numbers) are left out, as
norms are everywhere here. It is 0.86 MFLOP a token and sublayer against 57
MFLOP for the attention's projections: the path's cost is the bytes.

An "image" is one sequence of ``sequence_length`` predicted positions.
"""

from __future__ import annotations

from benchmark.flops.joyai import (  # noqa: F401 - the kernels' counts, for the readers that look them up here
    attention_core_flops_per_sequence,
    attention_floor_seconds,
    expert_layer_ffn_flops_per_sequence,
    grouped_matmul_floor_seconds,
    held_routings_per_token,
    latent_projection_flops_per_sequence,
    swiglu_flops_per_sequence,
)

SUBLAYERS_A_LAYER = 2  # attention, FFN: a hyper-connection each


def hyper_connection_flops_per_sequence(config: dict) -> float:
    """One sublayer's residual path over one sequence, forward."""
    n, d = config["hc_mult"], config["hidden_size"]
    maps = 2 * n + n * n
    return 2.0 * config["sequence_length"] * (n * d * maps + n * d + n * n * d + n * d)


def forward_flops_by_owner(config: dict) -> dict:
    """Forward FLOPs of one sequence by owner, the MTP module's layer and
    head under ``mtp`` and counted in the totals of nothing else."""
    s, d = config["sequence_length"], config["hidden_size"]
    dense_layers = config["first_k_dense_replace"]
    routed_layers = config["num_layers"] - dense_layers
    attention = latent_projection_flops_per_sequence(config)
    core = attention_core_flops_per_sequence(config)
    ffn = expert_layer_ffn_flops_per_sequence(config)
    paths = SUBLAYERS_A_LAYER * hyper_connection_flops_per_sequence(config)
    head = 2.0 * s * d * config["vocab_size"]
    modules = config["num_nextn_predict_layers"]
    return {
        "mla_projections": config["num_layers"] * attention,
        "attention_core": config["num_layers"] * core,
        "dense_mlp": dense_layers * swiglu_flops_per_sequence(config, config["intermediate_size"]),
        "router": routed_layers * ffn["router"],
        "shared_experts": routed_layers * ffn["shared"],
        "routed_experts": routed_layers * ffn["routed"],
        "hyper_connections": config["num_layers"] * paths,
        "head": head,
        "mtp": modules * (2.0 * s * 2 * d * d + attention + core + sum(ffn.values()) + paths + head),
    }


def forward_flops_per_image(config: dict) -> float:
    return sum(forward_flops_by_owner(config).values())


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# ------------------------------------------------- the residual path's bytes
#
# The same count whatever implements the path: per sublayer application and
# token, forward, the ``n`` streams read once, the sublayer's result ``y`` read
# once and the ``n`` streams written once, at the compute dtype's size; as
# much again where the forward is computed again under remat; twice that for
# the backward (the cotangents of the same three, and the saved streams read
# for them). The maps (24 float32 a token) and Phi are left out: under 1% of
# the streams. A fused implementation moves this and no more; what an
# unfused one moves beyond it (float32 intermediates, a pass a reduction,
# the streams read once a result) is in the seconds and not in the count.


def hc_stream_bytes_per_token(config: dict, itemsize: int = 2) -> float:
    """One sublayer application, forward."""
    n, d = config["hc_mult"], config["hidden_size"]
    return (n + 1 + n) * d * itemsize


def hc_stream_floor_seconds(config: dict, tokens: int, recomputed: bool, hbm_bytes_per_s: float) -> float:
    """The least seconds a step's residual paths could take over ``tokens``
    tokens: every layer's two sublayers and the MTP module's."""
    sublayers = SUBLAYERS_A_LAYER * (config["num_layers"] + config["num_nextn_predict_layers"])
    passes = 1 + (1 if recomputed else 0) + 2
    return passes * sublayers * tokens * hc_stream_bytes_per_token(config) / hbm_bytes_per_s
