"""JoyAI-LLM-Flash model FLOPs, and its two kernels' FLOPs and bytes, from a
configuration's sizes (a configuration names this file by its ``flops``
key): what the algorithm needs, no recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``. A causal
attention core needs the logits and the weighted sum at ``j <= i`` only:
``S (S + 1) / 2`` pairs of positions; a pair and head costs ``2 D_qk`` for the
logit and ``2 D_v`` for the weighted sum, and the two head sizes differ here
(192 / 128). Training costs three forwards. Norms, rotary, softmax, SiLU,
the sort and the gathers of the expert layer are left out (under a percent
of the FLOPs; their time is ``model.moe_dispatch_share``'s).

Routed experts are counted at the EXPECTED share of routings that land on
the experts held: ``k x held / published`` experts a token (8 x 16 / 256 =
0.5), which is what uniform routing gives and what ``moe_held_share`` reads
at the seeded weights (0.0625). Under imbalance the held experts see more or
fewer, and ``device.mfu`` then reads slightly off: PERF.md states it.

An "image" is one sequence of ``sequence_length`` predicted positions: the
benchmark's rate counts sequences.
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    return {
        "s": config["sequence_length"],
        "d": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "d_qk": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "d_v": config["v_head_dim"],
    }


def latent_projection_flops_per_sequence(config: dict) -> float:
    """``q_a``, ``q_b``, ``kv_a``, ``kv_b`` and the output merge."""
    z = _sizes(config)
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    weights = (
        z["d"] * q_rank
        + q_rank * z["heads"] * z["d_qk"]
        + z["d"] * (kv_rank + config["qk_rope_head_dim"])
        + kv_rank * z["heads"] * (config["qk_nope_head_dim"] + z["d_v"])
        + z["heads"] * z["d_v"] * z["d"]
    )
    return 2.0 * z["s"] * weights


def attention_core_flops_per_sequence(config: dict) -> float:
    z = _sizes(config)
    return z["heads"] * attention_forward_flops(z["s"], z["d_qk"], z["d_v"])


def swiglu_flops_per_sequence(config: dict, width: int) -> float:
    return 2.0 * config["sequence_length"] * 3 * config["hidden_size"] * width


def held_routings_per_token(config: dict) -> float:
    """Expected routings of a token that land on the experts held."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / config["n_routed_experts_published"]


def expert_layer_ffn_flops_per_sequence(config: dict) -> dict:
    """The expert layer's FFN by owner: router, shared experts, routed
    experts at the expected share held."""
    s, d, width = config["sequence_length"], config["hidden_size"], config["moe_intermediate_size"]
    return {
        "router": 2.0 * s * d * config["n_routed_experts_published"],
        "shared": swiglu_flops_per_sequence(config, width * config["n_shared_experts"]),
        "routed": held_routings_per_token(config) * swiglu_flops_per_sequence(config, width),
    }


def forward_flops_by_owner(config: dict) -> dict:
    """Forward FLOPs of one sequence by owner, the MTP module's layer and
    head under ``mtp`` and counted in the totals of nothing else."""
    s, d = config["sequence_length"], config["hidden_size"]
    dense_layers = config["first_k_dense_replace"]
    routed_layers = config["num_layers"] - dense_layers
    attention = latent_projection_flops_per_sequence(config)
    core = attention_core_flops_per_sequence(config)
    ffn = expert_layer_ffn_flops_per_sequence(config)
    head = 2.0 * s * d * config["vocab_size"]
    modules = config["num_nextn_predict_layers"]
    return {
        "mla_projections": config["num_layers"] * attention,
        "attention_core": config["num_layers"] * core,
        "dense_mlp": dense_layers * swiglu_flops_per_sequence(config, config["intermediate_size"]),
        "router": routed_layers * ffn["router"],
        "shared_experts": routed_layers * ffn["shared"],
        "routed_experts": routed_layers * ffn["routed"],
        "head": head,
        "mtp": modules * (2.0 * s * 2 * d * d + attention + core + sum(ffn.values()) + head),
    }


def forward_flops_per_image(config: dict) -> float:
    return sum(forward_flops_by_owner(config).values())


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# ------------------------------------------- the latent attention kernel
#
# Per head and sequence. Forward: QK^T at the query/key head and PV at the
# value head over the visible pairs. Backward: five matmuls over the same
# pairs (the logits again and dQ, dK at the query/key head; dP and dV at the
# value head), 2.5 times the forward at equal head sizes and counted by head
# size here. Bytes: each operand and each result crosses HBM once, in the
# compute dtype, at its own head size (nothing is padded to the other's); the
# logsumexp is one float32 a row.


def attention_forward_flops(seq: int, d_qk: int, d_v: int) -> float:
    return 2.0 * (d_qk + d_v) * seq * (seq + 1) / 2


def attention_backward_flops(seq: int, d_qk: int, d_v: int) -> float:
    return 2.0 * (3 * d_qk + 2 * d_v) * seq * (seq + 1) / 2


def attention_forward_bytes(seq: int, d_qk: int, d_v: int, itemsize: int = 2) -> float:
    return seq * (2 * d_qk + 2 * d_v) * itemsize + 4.0 * seq  # q, k, v in; o and the logsumexp out


def attention_backward_bytes(seq: int, d_qk: int, d_v: int, itemsize: int = 2) -> float:
    # q, k, v, o, dO, lse in; dq, dk, dv out
    return seq * (4 * d_qk + 4 * d_v) * itemsize + 4.0 * seq


def attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds the chip could take for one forward call and for one
    backward (both of its kernels) over ``rows`` sequences: per direction the
    larger of FLOPs over the peak and bytes over the bandwidth, with which of
    the two it was."""
    z = _sizes(config)
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
# One routed layer application over ``routings`` rows that landed on the
# experts held (a count the program reports, not the expectation above):
# forward three grouped matmuls (gate, up: [R, D] x [D, F]; down: [R, F] x
# [F, D]), backward two matmuls for each of them (the rows' gradient and
# the kernels'). Bytes: the rows in and out once a matmul in the compute
# dtype, and every held expert's kernel once (float32 parameters are cast to
# the compute dtype before the matmul; the cast is not the matmul's).


def grouped_matmul_flops(config: dict, routings: float) -> dict:
    one = 2.0 * routings * config["hidden_size"] * config["moe_intermediate_size"]
    return {"forward": 3 * one, "backward": 6 * one}


def grouped_matmul_bytes(config: dict, routings: float, itemsize: int = 2) -> dict:
    d, width, held = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
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
