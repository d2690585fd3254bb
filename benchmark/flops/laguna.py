"""Laguna model FLOPs, and its attention kernels' FLOPs and bytes, from a
configuration's sizes (a configuration names this file by its ``flops`` key):
what the algorithm needs, no recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``. Training
costs three forwards. Norms, rotary, softmax, SiLU, the gates' products, the
sort and the gathers of the expert layer are left out (under a percent of the
FLOPs).

**Two kinds of attention core**, by the config's ``layer_types`` and
``num_attention_heads_per_layer``, each counted at the pairs its mask shows. A
query head of a ``full_attention`` layer sees the triangle, ``S (S + 1) / 2``
pairs; one of a ``sliding_attention`` layer sees A BAND, ``sum_i min(i + 1,
sliding_window)`` pairs (position ``i`` looks at itself and the ``window - 1``
before it), 1,966,336 of the triangle's 8,390,656 at 4,096 positions and a
window of 512. Forward ``4 D`` FLOP a pair (the logits and the weighted sum),
backward 2.5 times that. Bytes: q in and o out at the layer's query heads, k
and v in at the key/value heads, the logsumexp a float32 a row and query head;
backward q, o, dO in and dq out at the query heads, k, v in and dk, dv out at
the key/value heads. What a kernel does beyond that (the masked pairs of the
blocks an edge crosses, the skipped cells' grid steps, a group's dk and dv
written a query head and summed after the call) is in a reader's seconds and
not in this count: a kernel that still swept the triangle in a window layer
would read a LOW share of the band's floor, not a high one.

Routed experts are counted at the EXPECTED share of routings that land on the
experts held: ``k x held / published`` experts a token (10 x 8 / 256 =
0.3125), which is what uniform routing gives. The grouped matmuls' counts are
``flops/qwen3_next.py``'s, whose keys this family's file shares.

An "image" is one sequence of ``sequence_length`` predicted positions: the
benchmark's rate counts sequences.
"""

from __future__ import annotations

from benchmark.flops.qwen3_next import (  # noqa: F401 - the same counts at the same keys, for the readers that look them up here
    grouped_matmul_bytes,
    grouped_matmul_floor_seconds,
    grouped_matmul_flops,
    held_routings_per_token,
    swiglu_flops_per_sequence,
)

KINDS = {"window": "sliding_attention", "full": "full_attention"}


def layer_kinds(config: dict) -> dict:
    """How many of the cut's layers are window layers, how many full, how many
    dense and how many routed (the first ``num_layers`` entries of the
    config's per-layer lists)."""
    depth = config["num_layers"]
    types, ffn = config["layer_types"][:depth], config["mlp_layer_types"][:depth]
    return {
        "window": types.count(KINDS["window"]), "full": types.count(KINDS["full"]),
        "dense": ffn.count("dense"), "routed": ffn.count("sparse"),
    }


def heads_of(config: dict, kind: str) -> int:
    """Query heads of a layer of ``kind`` (``window`` | ``full``): the entry of
    ``num_attention_heads_per_layer`` at the first layer of that type."""
    return config["num_attention_heads_per_layer"][config["layer_types"].index(KINDS[kind])]


def visible_pairs(config: dict, kind: str) -> float:
    """(query, key) pairs one query head's mask shows over a sequence."""
    s = config["sequence_length"]
    if kind == "full":
        return s * (s + 1) / 2
    window = min(config["sliding_window"], s)
    return window * (window + 1) / 2 + (s - window) * window  # sum_i min(i + 1, window)


# ----------------------------------------------------------------- the model


def attention_projection_flops_per_sequence(config: dict, kind: str) -> float:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` and the head-wise gate ``W_g``."""
    d, dim, kv_heads = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    heads = heads_of(config, kind)
    return 2.0 * config["sequence_length"] * (2 * d * heads * dim + 2 * d * kv_heads * dim + d * heads)


def attention_forward_flops(config: dict, kind: str) -> float:
    """One sequence, every query head of a layer of ``kind``."""
    return heads_of(config, kind) * 4.0 * config["head_dim"] * visible_pairs(config, kind)


def attention_backward_flops(config: dict, kind: str) -> float:
    """The logits again, dP, dV, dQ and dK over the same pairs: 2.5 forwards."""
    return 2.5 * attention_forward_flops(config, kind)


def expert_layer_ffn_flops_per_sequence(config: dict) -> dict:
    """The expert layer's FFN by owner: router, the gated shared expert,
    routed experts at the expected share held."""
    s, d = config["sequence_length"], config["hidden_size"]
    return {
        "router": 2.0 * s * d * config["num_experts_published"],
        "shared": swiglu_flops_per_sequence(config, config["shared_expert_intermediate_size"]) + 2.0 * s * d,
        "routed": held_routings_per_token(config) * swiglu_flops_per_sequence(config, config["moe_intermediate_size"]),
    }


def forward_flops_by_owner(config: dict) -> dict:
    """Forward FLOPs of one sequence by owner."""
    kinds, ffn = layer_kinds(config), expert_layer_ffn_flops_per_sequence(config)
    return {
        "window_projections": kinds["window"] * attention_projection_flops_per_sequence(config, "window"),
        "window_core": kinds["window"] * attention_forward_flops(config, "window"),
        "full_projections": kinds["full"] * attention_projection_flops_per_sequence(config, "full"),
        "full_core": kinds["full"] * attention_forward_flops(config, "full"),
        "dense_mlp": kinds["dense"] * swiglu_flops_per_sequence(config, config["intermediate_size"]),
        "router": kinds["routed"] * ffn["router"],
        "shared_experts": kinds["routed"] * ffn["shared"],
        "routed_experts": kinds["routed"] * ffn["routed"],
        "head": 2.0 * config["sequence_length"] * config["hidden_size"] * config["vocab_size"],
    }


def forward_flops_per_image(config: dict) -> float:
    return sum(forward_flops_by_owner(config).values())


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# --------------------------------------------------- the two attention floors


def attention_forward_bytes(config: dict, kind: str, itemsize: int = 2) -> float:
    """One sequence: q in and o out at the query heads, k and v in at the
    key/value heads, the logsumexp out."""
    s, dim = config["sequence_length"], config["head_dim"]
    heads, kv_heads = heads_of(config, kind), config["num_key_value_heads"]
    return s * dim * (2 * heads + 2 * kv_heads) * itemsize + 4.0 * s * heads


def attention_backward_bytes(config: dict, kind: str, itemsize: int = 2) -> float:
    """q, o, dO in and dq out at the query heads; k, v in and dk, dv out at
    the key/value heads; the logsumexp in."""
    s, dim = config["sequence_length"], config["head_dim"]
    heads, kv_heads = heads_of(config, kind), config["num_key_value_heads"]
    return s * dim * (4 * heads + 4 * kv_heads) * itemsize + 4.0 * s * heads


def _attention_floor_seconds(config: dict, kind: str, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    out = {}
    for name, flops, nbytes in (
        ("forward", attention_forward_flops(config, kind), attention_forward_bytes(config, kind)),
        ("backward", attention_backward_flops(config, kind), attention_backward_bytes(config, kind)),
    ):
        by_flops, by_bytes = rows * flops / peak_flops, rows * nbytes / hbm_bytes_per_s
        out[name] = max(by_flops, by_bytes)
        out[name + "_bound"] = "flops" if by_flops >= by_bytes else "bytes"
    return out


def window_attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds the chip could take for ONE forward call and for ONE
    backward call of a window layer's core over ``rows`` sequences, THE BAND'S
    work: per direction the larger of FLOPs over the peak and bytes over the
    bandwidth, with which of the two it was. A reader counts a forward floor a
    forward call (first run or recomputed) and a backward floor a backward
    call, every window layer of the cut."""
    return _attention_floor_seconds(config, "window", rows, peak_flops, hbm_bytes_per_s)


def full_attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """As :func:`window_attention_floor_seconds` for a full layer's core: the
    triangle's work at that layer's head count."""
    return _attention_floor_seconds(config, "full", rows, peak_flops, hbm_bytes_per_s)
