"""Ling-3.0-flash model FLOPs, and its kernels' FLOPs and bytes, from a
configuration's sizes (a configuration names this file by its ``flops`` key):
what the algorithm needs, no recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``. Training
costs three forwards. Norms, rotary, softmax, SiLU, the gates, the group
step, the sort and the gathers of the expert layer are left out (under a
percent of the FLOPs).

**The vector-decay delta rule** is counted as the chunked algorithm at ``C`` =
64, whatever implements it, in the form that applies the triangular inverse
once (to ``V - (K e^gamma) S``). Per token and head, forward::

    2 C d_k            the pair terms sum_c k_ic k_jc exp(.) (a row of the C x C system)
    2 C d_k            the pair terms of q with k
    2 C d_v            T beta applied to V - (K e^gamma) S
    2 C d_v            the masked pair terms times V'
    3 x 2 d_k d_v      (K e^gamma) S, (Q e^gamma) S and (K e^{gamma_C - gamma})^T V'

= 163,840 at ``d_k = d_v`` = 128. The triangular system's solution and the
decays' products with the operands' lanes are left out. Backward twice the
forward. Bytes: each operand once: q, k, v in and o out at ``H x 128`` in the
compute dtype, ``g`` one float32 a KEY LANE (``H x d_k``: the vector decay is
a third of the rule's bytes) and ``beta`` one a head; the backward twice
that. At these sizes a pass is bound by its bytes (49,280 a token: 60 ns at
the chip's bandwidth against 27 ns of matrix work at its peak).

**The latent attention core** is ``flops/joyai.py``'s causal count at the two
head sizes (192 / 128), with the same bytes; the gate, a number a head and
token, is left out.

Routed experts are counted at the EXPECTED share of routings that land on
the experts held: ``k x held / published`` experts a token (8 x 8 / 512 =
0.125), which is what uniform routing gives; group-limited selection changes
which tokens reach the held experts' group, not the expectation.

An "image" is one sequence of ``sequence_length`` predicted positions: the
benchmark's rate counts sequences.
"""

from __future__ import annotations

CHUNK = 64


def layer_kinds(config: dict) -> dict:
    """How many of the cut's layers mix by latent attention and how many by
    KDA: layer ``i`` is latent where ``(i + 1) % layer_group_size == 0``."""
    latent = sum((i + 1) % config["layer_group_size"] == 0 for i in range(config["num_layers"]))
    return {"latent": latent, "kda": config["num_layers"] - latent}


# ----------------------------------------------------------------- the model


def kda_projection_flops_per_sequence(config: dict) -> float:
    """q, k, v, f, g (``[D, H d]`` each), b (``[D, H]``) and the output merge."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    lanes = heads * config["head_dim"]
    return 2.0 * config["sequence_length"] * (5 * d * lanes + d * heads + lanes * d)


def kda_conv_flops_per_sequence(config: dict) -> float:
    lanes = config["num_attention_heads"] * config["head_dim"]
    return 2.0 * config["sequence_length"] * 3 * lanes * config["short_conv_kernel_size"]


def kda_rule_flops_per_token_and_head(config: dict) -> float:
    dk = dv = config["head_dim"]
    return 2 * 2.0 * CHUNK * dk + 2 * 2.0 * CHUNK * dv + 3 * 2.0 * dk * dv


def kda_rule_flops_per_sequence(config: dict) -> float:
    return config["sequence_length"] * config["num_attention_heads"] * kda_rule_flops_per_token_and_head(config)


def _latent(config: dict) -> dict:
    return {
        "s": config["sequence_length"],
        "d": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "d_qk": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "d_v": config["v_head_dim"],
    }


def latent_projection_flops_per_sequence(config: dict) -> float:
    """The direct query, ``kv_a``, ``kv_b``, the head-wise gate and the output merge."""
    z, rank = _latent(config), config["kv_lora_rank"]
    weights = (
        z["d"] * z["heads"] * z["d_qk"]
        + z["d"] * (rank + config["qk_rope_head_dim"])
        + rank * z["heads"] * (config["qk_nope_head_dim"] + z["d_v"])
        + z["d"] * z["heads"]
        + z["heads"] * z["d_v"] * z["d"]
    )
    return 2.0 * z["s"] * weights


def attention_forward_flops(seq: int, d_qk: int, d_v: int) -> float:
    return 2.0 * (d_qk + d_v) * seq * (seq + 1) / 2


def attention_backward_flops(seq: int, d_qk: int, d_v: int) -> float:
    return 2.0 * (3 * d_qk + 2 * d_v) * seq * (seq + 1) / 2


def attention_core_flops_per_sequence(config: dict) -> float:
    z = _latent(config)
    return z["heads"] * attention_forward_flops(z["s"], z["d_qk"], z["d_v"])


def swiglu_flops_per_sequence(config: dict, width: int) -> float:
    return 2.0 * config["sequence_length"] * 3 * config["hidden_size"] * width


def held_routings_per_token(config: dict) -> float:
    """Expected routings of a token that land on the experts held."""
    return config["num_experts_per_tok"] * config["num_experts"] / config["num_experts_published"]


def expert_layer_ffn_flops_per_sequence(config: dict) -> dict:
    """The expert layer's FFN by owner: router, the shared expert, routed
    experts at the expected share held."""
    s, d = config["sequence_length"], config["hidden_size"]
    return {
        "router": 2.0 * s * d * config["num_experts_published"],
        "shared": swiglu_flops_per_sequence(config, config["moe_shared_expert_intermediate_size"]),
        "routed": held_routings_per_token(config) * swiglu_flops_per_sequence(config, config["moe_intermediate_size"]),
    }


def forward_flops_by_owner(config: dict) -> dict:
    """Forward FLOPs of one sequence by owner."""
    kinds, ffn = layer_kinds(config), expert_layer_ffn_flops_per_sequence(config)
    dense = config["first_k_dense_replace"]
    routed = config["num_layers"] - dense
    return {
        "kda_projections": kinds["kda"] * kda_projection_flops_per_sequence(config),
        "kda_conv": kinds["kda"] * kda_conv_flops_per_sequence(config),
        "kda_rule": kinds["kda"] * kda_rule_flops_per_sequence(config),
        "latent_projections": kinds["latent"] * latent_projection_flops_per_sequence(config),
        "attention_core": kinds["latent"] * attention_core_flops_per_sequence(config),
        "dense_mlp": dense * swiglu_flops_per_sequence(config, config["intermediate_size"]),
        "router": routed * ffn["router"],
        "shared_experts": routed * ffn["shared"],
        "routed_experts": routed * ffn["routed"],
        "head": 2.0 * config["sequence_length"] * config["hidden_size"] * config["vocab_size"],
    }


def forward_flops_per_image(config: dict) -> float:
    return sum(forward_flops_by_owner(config).values())


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# ------------------------------------------------------------ the rule's floor


def kda_rule_bytes_per_token(config: dict, itemsize: int = 2) -> float:
    """Forward: q, k, v in and o out in the compute dtype; g a float32 a key
    lane, beta a float32 a head."""
    heads, dim = config["num_attention_heads"], config["head_dim"]
    return 4 * heads * dim * itemsize + heads * dim * 4.0 + heads * 4.0


def kda_rule_floor_seconds(config: dict, tokens: int, recomputed: bool,
                           peak_flops: float, hbm_bytes_per_s: float) -> float:
    """The least seconds a step's KDA layers could take for the rule over
    ``tokens`` tokens: a forward, one more where it is recomputed, and a
    backward of twice the forward; a pass the larger of its matrix work over
    the peak and its bytes over the bandwidth. The same whatever implements
    the rule."""
    flops = tokens * config["num_attention_heads"] * kda_rule_flops_per_token_and_head(config)
    nbytes = tokens * kda_rule_bytes_per_token(config)
    forward = max(flops / peak_flops, nbytes / hbm_bytes_per_s)
    passes = 1 + (1 if recomputed else 0) + 2
    return layer_kinds(config)["kda"] * passes * forward


# ------------------------------------------- the latent attention kernel
#
# As flops/joyai.py counts it (per head and sequence; each operand and result
# once in the compute dtype at its own head size, the logsumexp a float32 a
# row), under the names the accepted latent-attention reader looks up.


def attention_forward_bytes(seq: int, d_qk: int, d_v: int, itemsize: int = 2) -> float:
    return seq * (2 * d_qk + 2 * d_v) * itemsize + 4.0 * seq  # q, k, v in; o and the logsumexp out


def attention_backward_bytes(seq: int, d_qk: int, d_v: int, itemsize: int = 2) -> float:
    return seq * (4 * d_qk + 4 * d_v) * itemsize + 4.0 * seq  # q, k, v, o, dO, lse in; dq, dk, dv out


def attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds the chip could take for one forward call and for one
    backward call over ``rows`` sequences: per direction the larger of FLOPs
    over the peak and bytes over the bandwidth, with which of the two it was."""
    z = _latent(config)
    cores, args = rows * z["heads"], (z["s"], z["d_qk"], z["d_v"])
    out = {}
    for name, flops, nbytes in (
        ("forward", attention_forward_flops(*args), attention_forward_bytes(*args)),
        ("backward", attention_backward_flops(*args), attention_backward_bytes(*args)),
    ):
        by_flops, by_bytes = cores * flops / peak_flops, cores * nbytes / hbm_bytes_per_s
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
