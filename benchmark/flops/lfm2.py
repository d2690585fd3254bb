"""LFM2 model FLOPs, and its kernels' FLOPs and bytes, from a configuration's
sizes (a configuration names this file by its ``flops`` key): what the
algorithm needs, no recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``. Training
costs three forwards. Norms, rotary, softmax, SiLU, the sort and the gathers
of the expert layer are left out (under a percent of the FLOPs).

**The short convolution's core** (between the block's two projections): per
token and channel one product ``B * x~``, ``W`` multiply-adds and one product
with ``C``: ``2 W + 2`` FLOP, nothing beside its bytes. Forward it reads ``B``,
``C``, ``x~`` and writes ``C * c``: four arrays of ``[tokens, D]`` in the
compute dtype; its backward reads those three and the cotangent and writes
three gradients: seven. The floor is the bytes over the bandwidth, whatever
implements the core.

**The attention core** is counted at the REAL head: ``4 D S (S + 1) / 2`` a
query head forward at ``D = hidden / heads`` (64), 2.5 times that backward; q
and o at the query heads (32), k and v at the key/value heads (8), once each;
the logsumexp one float32 a row and query head. What a kernel does beyond
that (lanes padded to 128, k and v repeated to the query heads, the copies
around the calls) is in a reader's seconds and not in this count.

Routed experts are counted at the EXPECTED share of routings that land on the
experts held: ``k x held / published`` experts a token (4 x 8 / 64 = 0.5),
which is what uniform routing gives. No shared expert. The grouped matmuls'
counts and a query head's causal pairs are ``flops/qwen3_next.py``'s, whose
keys this family's file shares.

An "image" is one sequence of ``sequence_length`` predicted positions: the
benchmark's rate counts sequences.
"""

from __future__ import annotations

from benchmark.flops.qwen3_next import (  # noqa: F401 - the same counts at the same keys, for the readers that look them up here
    attention_backward_flops,
    attention_forward_flops,
    grouped_matmul_bytes,
    grouped_matmul_floor_seconds,
    grouped_matmul_flops,
    held_routings_per_token,
    swiglu_flops_per_sequence,
)


def layer_kinds(config: dict) -> dict:
    """How many of the cut's layers are short convolutions, how many softmax
    attention, how many dense and how many routed."""
    kinds = config["layer_types_held"]
    dense = config["first_k_dense_replace"]
    return {
        "conv": kinds.count("conv"), "full": kinds.count("full_attention"),
        "dense": dense, "routed": config["num_layers"] - dense,
    }


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


# ----------------------------------------------------------------- the model


def short_conv_projection_flops_per_sequence(config: dict) -> float:
    """``in_proj`` (D -> 3 D) and ``out_proj`` (D -> D)."""
    d = config["hidden_size"]
    return 2.0 * config["sequence_length"] * 4 * d * d


def short_conv_core_flops_per_sequence(config: dict) -> float:
    return config["sequence_length"] * config["hidden_size"] * (2.0 * config["conv_L_cache"] + 2.0)


def attention_projection_flops_per_sequence(config: dict) -> float:
    """``q_proj``, ``k_proj``, ``v_proj`` and ``out_proj``."""
    d, dim = config["hidden_size"], head_dim(config)
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * config["sequence_length"] * (2 * d * heads * dim + 2 * d * kv_heads * dim)


def attention_core_flops_per_sequence(config: dict) -> float:
    return config["num_attention_heads"] * attention_forward_flops(config["sequence_length"], head_dim(config))


def forward_flops_by_owner(config: dict) -> dict:
    """Forward FLOPs of one sequence by owner."""
    kinds, s, d = layer_kinds(config), config["sequence_length"], config["hidden_size"]
    return {
        "conv_projections": kinds["conv"] * short_conv_projection_flops_per_sequence(config),
        "conv_core": kinds["conv"] * short_conv_core_flops_per_sequence(config),
        "attention_projections": kinds["full"] * attention_projection_flops_per_sequence(config),
        "attention_core": kinds["full"] * attention_core_flops_per_sequence(config),
        "dense_mlp": kinds["dense"] * swiglu_flops_per_sequence(config, config["intermediate_size"]),
        "router": kinds["routed"] * 2.0 * s * d * config["num_experts_published"],
        "routed_experts": kinds["routed"] * held_routings_per_token(config)
        * swiglu_flops_per_sequence(config, config["moe_intermediate_size"]),
        "head": 2.0 * s * d * config["vocab_size"],
    }


def forward_flops_per_image(config: dict) -> float:
    return sum(forward_flops_by_owner(config).values())


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# ------------------------------------------------ the short convolution's floor


def short_conv_core_bytes_per_token(config: dict, itemsize: int = 2) -> dict:
    """Forward: ``B``, ``C``, ``x~`` in, ``C * c`` out. Backward: those three
    and the cotangent in, three gradients out. The ``[W, D]`` kernel and its
    gradient are nothing beside them."""
    one = config["hidden_size"] * itemsize
    return {"forward": 4.0 * one, "backward": 7.0 * one}


def short_conv_floor_seconds(config: dict, tokens: int, recomputed: bool,
                             peak_flops: float, hbm_bytes_per_s: float) -> float:
    """The least seconds a step's short-convolution layers could take for the
    core over ``tokens`` tokens: a forward, one more where it is recomputed,
    and a backward; a pass the larger of its FLOPs over the peak and its bytes
    over the bandwidth (the bytes, by two orders)."""
    nbytes = short_conv_core_bytes_per_token(config)
    flops = short_conv_core_flops_per_sequence(config) / config["sequence_length"]  # a token, forward
    forward = tokens * max(flops / peak_flops, nbytes["forward"] / hbm_bytes_per_s)
    backward = tokens * max(2.0 * flops / peak_flops, nbytes["backward"] / hbm_bytes_per_s)
    return layer_kinds(config)["conv"] * ((2 if recomputed else 1) * forward + backward)


# ----------------------------------------------------------- the attention's floor


def attention_forward_bytes(config: dict, itemsize: int = 2) -> float:
    """One sequence: q in and o out at the query heads, k and v in at the
    key/value heads, the logsumexp out."""
    s, dim = config["sequence_length"], head_dim(config)
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return s * dim * (2 * heads + 2 * kv_heads) * itemsize + 4.0 * s * heads


def attention_backward_bytes(config: dict, itemsize: int = 2) -> float:
    """q, o, dO in and dq out at the query heads; k, v in and dk, dv out at
    the key/value heads; the logsumexp in."""
    s, dim = config["sequence_length"], head_dim(config)
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return s * dim * (4 * heads + 4 * kv_heads) * itemsize + 4.0 * s * heads


def attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds the chip could take for one forward call and for ONE
    BACKWARD CALL over ``rows`` sequences (a reader counts one backward floor
    for each backward call it finds, not half): per direction the larger of
    FLOPs over the peak and bytes over the bandwidth, with which of the two it
    was. (The name and the result's keys are those the expert families'
    readers look up.)"""
    s, dim, heads = config["sequence_length"], head_dim(config), config["num_attention_heads"]
    out = {}
    for name, flops, nbytes in (
        ("forward", heads * attention_forward_flops(s, dim), attention_forward_bytes(config)),
        ("backward", heads * attention_backward_flops(s, dim), attention_backward_bytes(config)),
    ):
        by_flops, by_bytes = rows * flops / peak_flops, rows * nbytes / hbm_bytes_per_s
        out[name] = max(by_flops, by_bytes)
        out[name + "_bound"] = "flops" if by_flops >= by_bytes else "bytes"
    return out
