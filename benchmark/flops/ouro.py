"""Ouro model FLOPs, and the causal attention kernel's FLOPs and bytes, from
a configuration's sizes (a configuration names this file by its ``flops``
key): what the algorithm needs, no recomputation counted.

A matmul of ``n`` rows by a ``[k, m]`` matrix costs ``2 n k m``. A causal
attention core needs the logits and the weighted sum at ``j <= i`` only:
``S (S + 1) / 2`` pairs of positions, ``4 D`` operations a pair and head
(QK^T and PV). The stack runs ``total_ut_steps`` times and the head once a
pass. Training costs three forwards. Norms, rotary, softmax, SiLU and the
exit gate's ``2 D`` a token and pass are left out (under a percent).

An "image" is one sequence of ``sequence_length`` predicted positions: the
benchmark's rate counts sequences.
"""

from __future__ import annotations


def layer_application_flops_per_sequence(config: dict) -> float:
    s, d = config["sequence_length"], config["hidden_size"]
    heads, dh, ff = config["num_attention_heads"], config["head_dim"], config["intermediate_size"]
    projections = 2.0 * s * 4 * d * heads * dh  # Q, K, V, out
    mlp = 2.0 * s * 3 * d * ff  # gate, up, down
    return projections + mlp + heads * causal_attention_forward_flops(s, dh)


def forward_flops_per_image(config: dict) -> float:
    s, d = config["sequence_length"], config["hidden_size"]
    head = 2.0 * s * d * config["vocab_size"]
    per_pass = config["num_layers"] * layer_application_flops_per_sequence(config) + head
    return config["total_ut_steps"] * per_pass


def train_flops_per_image(config: dict) -> float:
    return 3.0 * forward_flops_per_image(config)


# ---------------------------------------------- the causal attention kernel
#
# Per head and sequence. Forward: QK^T and PV over the visible pairs.
# Backward: five matmuls over the same pairs (the logits again, dV, dP, dQ,
# dK), 2.5 times the forward; a kernel that recomputes more, or that works
# on masked pairs, does work this count leaves out, and its share falls.
# Bytes: each operand and each result crosses HBM once, in the compute
# dtype; the logsumexp is one float32 a row.


def causal_attention_forward_flops(seq: int, head_dim: int) -> float:
    return 4.0 * head_dim * seq * (seq + 1) / 2


def causal_attention_backward_flops(seq: int, head_dim: int) -> float:
    return 2.5 * causal_attention_forward_flops(seq, head_dim)


def causal_attention_forward_bytes(seq: int, head_dim: int, itemsize: int = 2) -> float:
    return 4.0 * seq * head_dim * itemsize + 4.0 * seq  # q, k, v in; o and the logsumexp out


def causal_attention_backward_bytes(seq: int, head_dim: int, itemsize: int = 2) -> float:
    return 8.0 * seq * head_dim * itemsize + 4.0 * seq  # q, k, v, o, dO, lse in; dq, dk, dv out


def causal_attention_floor_seconds(config: dict, rows: int, peak_flops: float, hbm_bytes_per_s: float) -> dict:
    """The least seconds the chip could take for one forward call and for one
    backward (both of its kernels) over ``rows`` sequences: per direction
    the larger of FLOPs over the peak and bytes over the bandwidth, with
    which of the two it was."""
    s, dh = config["sequence_length"], config["head_dim"]
    cores = rows * config["num_attention_heads"]
    out = {}
    for name, flops, nbytes in (
        ("forward", causal_attention_forward_flops(s, dh), causal_attention_forward_bytes(s, dh)),
        ("backward", causal_attention_backward_flops(s, dh), causal_attention_backward_bytes(s, dh)),
    ):
        by_flops, by_bytes = cores * flops / peak_flops, cores * nbytes / hbm_bytes_per_s
        out[name] = max(by_flops, by_bytes)
        out[name + "_bound"] = "flops" if by_flops >= by_bytes else "bytes"
    return out
