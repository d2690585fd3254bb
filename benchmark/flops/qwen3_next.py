"""Qwen3-Next model FLOPs, and its kernels' FLOPs and bytes, from a
configuration's sizes (a configuration names this file by its ``flops`` key):
what the algorithm needs, no recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``. Training
costs three forwards. Norms, rotary, softmax, SiLU, the gates, the sort and
the gathers of the expert layer are left out (under a percent of the FLOPs).

**The gated delta rule** is counted as the chunked algorithm at ``C`` = 64
(the published kernels' chunk), whatever implements it. Per token and value
head, forward::

    2 C d_k            K K^T (a row of the chunk's C x C system)
    2 C (d_k + d_v)    T applied to beta e^gamma K and to beta V
    2 C d_k            Q K^T
    2 C d_v            the masked Q K^T times V'
    3 x 2 d_k d_v      W S, Q S and K^T V' (the three that touch the state)

= 180,224 at ``d_k = d_v`` = 128 (the form with ``W = T (beta e^gamma K)``
and ``U = T (beta V)``; ``sav_tpu/ops/gated_delta.py`` applies ``T`` once, to
``V - e^gamma K S``, and does 163,840: the count stays the stated algorithm's,
0.4% of a step's FLOPs apart, and the rule's floor below is bound by its
bytes under either count). The triangular system's solution is left
out (``C^2`` a token in a blocked substitution: 2% of the above). Backward
twice the forward. Bytes: each operand once at its own head count: q and k at
``H_k x d_k``, v and o at ``H x d_v`` in the compute dtype, g and beta one
float32 a value head; the backward twice that.

**The gated attention core** is ``flops/joyai.py``'s causal count at one head
size (``4 D S (S + 1) / 2`` a query head forward, 2.5 times that backward)
with its own bytes: q and o at the query heads, k and v at the key/value
heads, once each; the logsumexp one float32 a row and query head.

Routed experts are counted at the EXPECTED share of routings that land on
the experts held: ``k x held / published`` experts a token (10 x 32 / 512 =
0.625), which is what uniform routing gives.

An "image" is one sequence of ``sequence_length`` predicted positions: the
benchmark's rate counts sequences.
"""

from __future__ import annotations

CHUNK = 64


def layer_kinds(config: dict) -> dict:
    """How many of the cut's layers are softmax attention and how many the
    delta rule: layer ``i`` is full where ``(i + 1) % full_attention_interval == 0``."""
    full = sum((i + 1) % config["full_attention_interval"] == 0 for i in range(config["num_layers"]))
    return {"full": full, "linear": config["num_layers"] - full}


# ----------------------------------------------------------------- the model


def gated_delta_projection_flops_per_sequence(config: dict) -> float:
    """``in_proj_qkvz``, ``in_proj_ba`` and ``out_proj``."""
    d = config["hidden_size"]
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    weights = d * (2 * keys + 2 * values) + d * 2 * config["linear_num_value_heads"] + values * d
    return 2.0 * config["sequence_length"] * weights


def gated_delta_conv_flops_per_sequence(config: dict) -> float:
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return 2.0 * config["sequence_length"] * (2 * keys + values) * config["linear_conv_kernel_dim"]


def gated_delta_rule_flops_per_token_and_head(config: dict) -> float:
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return 2.0 * CHUNK * dk + 2.0 * CHUNK * (dk + dv) + 2.0 * CHUNK * dk + 2.0 * CHUNK * dv + 3 * 2.0 * dk * dv


def gated_delta_rule_flops_per_sequence(config: dict) -> float:
    return (
        config["sequence_length"] * config["linear_num_value_heads"]
        * gated_delta_rule_flops_per_token_and_head(config)
    )


def gated_attention_projection_flops_per_sequence(config: dict) -> float:
    """``q_proj`` (query and gate), ``k_proj``, ``v_proj`` and ``o_proj``."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * config["sequence_length"] * (d * heads * 2 * dim + 2 * d * kv_heads * dim + heads * dim * d)


def attention_forward_flops(seq: int, dim: int) -> float:
    """One query head: the logits and the weighted sum over the visible pairs."""
    return 4.0 * dim * seq * (seq + 1) / 2


def attention_core_flops_per_sequence(config: dict) -> float:
    return config["num_attention_heads"] * attention_forward_flops(config["sequence_length"], config["head_dim"])


def swiglu_flops_per_sequence(config: dict, width: int) -> float:
    return 2.0 * config["sequence_length"] * 3 * config["hidden_size"] * width


def held_routings_per_token(config: dict) -> float:
    """Expected routings of a token that land on the experts held."""
    return config["num_experts_per_tok"] * config["num_experts"] / config["num_experts_published"]


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
    layers = config["num_layers"]
    return {
        "gdn_projections": kinds["linear"] * gated_delta_projection_flops_per_sequence(config),
        "gdn_conv": kinds["linear"] * gated_delta_conv_flops_per_sequence(config),
        "gdn_rule": kinds["linear"] * gated_delta_rule_flops_per_sequence(config),
        "attention_projections": kinds["full"] * gated_attention_projection_flops_per_sequence(config),
        "attention_core": kinds["full"] * attention_core_flops_per_sequence(config),
        "router": layers * ffn["router"],
        "shared_experts": layers * ffn["shared"],
        "routed_experts": layers * ffn["routed"],
        "head": 2.0 * config["sequence_length"] * config["hidden_size"] * config["vocab_size"],
    }


def forward_flops_per_image(config: dict) -> float:
    return sum(forward_flops_by_owner(config).values())


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# ------------------------------------------------------- the delta rule's floor


def gated_delta_rule_bytes_per_token(config: dict, itemsize: int = 2) -> float:
    """Forward: q, k in, v in, o out, g and beta in."""
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return (2 * keys + 2 * values) * itemsize + 2 * config["linear_num_value_heads"] * 4.0


def gated_delta_floor_seconds(config: dict, tokens: int, recomputed: bool,
                              peak_flops: float, hbm_bytes_per_s: float) -> float:
    """The least seconds a step's delta-rule layers could take for the rule
    over ``tokens`` tokens: a forward, one more where it is recomputed, and a
    backward of twice the forward; a pass the larger of its FLOPs over the peak
    and its bytes over the bandwidth."""
    flops = tokens * config["linear_num_value_heads"] * gated_delta_rule_flops_per_token_and_head(config)
    nbytes = tokens * gated_delta_rule_bytes_per_token(config)
    forward = max(flops / peak_flops, nbytes / hbm_bytes_per_s)
    passes = 1 + (1 if recomputed else 0) + 2
    return layer_kinds(config)["linear"] * passes * forward


# --------------------------------------------------- the gated attention's floor


def attention_backward_flops(seq: int, dim: int) -> float:
    """The logits again, dP, dV, dQ and dK over the same pairs: 2.5 forwards."""
    return 2.5 * attention_forward_flops(seq, dim)


def attention_forward_bytes(config: dict, itemsize: int = 2) -> float:
    """One sequence: q in and o out at the query heads, k and v in at the
    key/value heads, the logsumexp out."""
    s, dim = config["sequence_length"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return s * dim * (2 * heads + 2 * kv_heads) * itemsize + 4.0 * s * heads


def attention_backward_bytes(config: dict, itemsize: int = 2) -> float:
    """q, o, dO in and dq out at the query heads; k, v in and dk, dv out at
    the key/value heads; the logsumexp in."""
    s, dim = config["sequence_length"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return s * dim * (4 * heads + 4 * kv_heads) * itemsize + 4.0 * s * heads


def attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds the chip could take for one forward call and for one
    application's backward over ``rows`` sequences: per direction the larger of
    FLOPs over the peak and bytes over the bandwidth, with which of the two it
    was. (The name and the result's keys are those the expert family's reader
    looks up.)"""
    s, dim, heads = config["sequence_length"], config["head_dim"], config["num_attention_heads"]
    out = {}
    for name, flops, nbytes in (
        ("forward", heads * attention_forward_flops(s, dim), attention_forward_bytes(config)),
        ("backward", heads * attention_backward_flops(s, dim), attention_backward_bytes(config)),
    ):
        by_flops, by_bytes = rows * flops / peak_flops, rows * nbytes / hbm_bytes_per_s
        out[name] = max(by_flops, by_bytes)
        out[name + "_bound"] = "flops" if by_flops >= by_bytes else "bytes"
    return out


# ------------------------------------------------ the grouped matmuls
#
# As flops/joyai.py counts them, at this family's keys: one routed layer
# application over ``routings`` rows that landed on the experts held.


def grouped_matmul_flops(config: dict, routings: float) -> dict:
    one = 2.0 * routings * config["hidden_size"] * config["moe_intermediate_size"]
    return {"forward": 3 * one, "backward": 6 * one}


def grouped_matmul_bytes(config: dict, routings: float, itemsize: int = 2) -> dict:
    d, width, held = config["hidden_size"], config["moe_intermediate_size"], config["num_experts"]
    kernels = 3 * held * d * width * itemsize
    rows_forward = routings * (2 * (d + width) + (width + d)) * itemsize  # gate, up, down: in + out
    return {"forward": kernels + rows_forward, "backward": 2 * kernels + 2 * rows_forward}


def grouped_matmul_floor_seconds(config: dict, routings: float, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds for one routed layer application's grouped matmuls,
    forward and backward, at ``routings`` rows on the experts held."""
    flops, nbytes = grouped_matmul_flops(config, routings), grouped_matmul_bytes(config, routings)
    out = {}
    for name in ("forward", "backward"):
        by_flops, by_bytes = flops[name] / peak_flops, nbytes[name] / hbm_bytes_per_s
        out[name] = max(by_flops, by_bytes)
        out[name + "_bound"] = "flops" if by_flops >= by_bytes else "bytes"
    return out
