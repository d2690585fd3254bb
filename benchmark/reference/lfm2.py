"""Plain float32 reference for the LFM2 cell: forward, loss, gradient, AdamW
and the selection bias's step.

Written from the model's public ``config.json`` (https://huggingface.co/
LiquidAI/LFM2-24B-A2B) and the equations of the family's public modelling
code (``lfm2_moe``), in plain ``jax.numpy``, float32, traced under
``jax.default_matmul_precision("highest")``. No kernel, no fused convolution,
no sort, no gather of routed rows, no grouped key/value index. It imports
nothing of the program and is handed nothing the program made: the benchmark
draws the weights (``benchmark/weights.py``) and the token batches from the
seed and gives the same arrays to both sides. The optimizer is
``reference/vit.py``'s AdamW; the plain-weight RMSNorm, the SwiGLU and the
bias's step are ``reference/joyai.py``'s, the loop over the experts held
``reference/qwen3_next.py``'s.

Model. ``N(x) = x / rms(x) w`` (plain weight, eps ``norm_eps``); ``h =
E[tokens]``; for each layer ``h += Op_i(N(h)); h += FFN_i(N(h))``; ``logits =
N(h) E^T``: the head is the embedding's table (assumed: the row has no
``tie_word_embeddings`` key). No bias anywhere. ``Op_i`` is the short
convolution where ``layer_types[i]`` is ``conv`` and attention where it is
``full_attention``; ``FFN_i`` is SwiGLU at ``intermediate_size`` in the first
``num_dense_layers`` layers and the expert layer after them.

- Short convolution. ``[B | C | x~] = x W_in`` (three equal parts, in that
  order); ``u = B * x~``; ``c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t`` a
  channel, THREE SHIFTED PRODUCTS with zeros before the sequence starts
  (``conv_L_cache`` 3, ``conv_bias`` false); ``y = (C * c) W_out``. No
  activation.
- Attention (``H`` query heads on ``H_kv`` key/value heads of ``D = hidden /
  H``). q and k pass a per-head ``N`` over ``D`` (one weight of ``D`` each,
  shared by the heads); all ``D`` lanes rotate, lane ``i`` paired with ``i + D
  / 2`` at ``theta ** (-i / (D / 2))``; each key/value head is REPEATED to its
  ``H / H_kv`` query heads; causal softmax at ``D^-0.5``, the explicit mask, a
  block of queries after another; ``y = out W_o``. No gate.
- Expert layer. ``s = sigmoid(x W_r)`` over all published experts; the ``k``
  largest of ``s + b`` (``b`` the selection bias, a constant of the step);
  ``g = s_sel / (sum s_sel + 1e-6) routed_scaling_factor``; ``y = sum_{selected
  and held} g_i E_i(x)``: a loop over the experts HELD (``expert_offset``,
  ``num_experts`` of ``num_experts_published``), each run on every token and
  weighted by a vector that is zero where the token did not select it. NO
  shared expert. What the absent experts would add is left out, as in the
  program. No dropped token.
- Balance term, a sequence: ``sum_e f_e P_e``, ``f_e = E / (k S) c_e``, ``P_e``
  the mean over the sequence of ``s_e / sum s``, weighted by ``alpha``
  (assumed: the configuration's ``recipe.balance_alpha``; the form is the
  expert families' of this repo).
- The bias's step after every update: ``b_e += gamma sign(mean(c) - c_e)`` on
  the step's counts a routed layer (assumed: ``recipe.bias_update_rate``).

Loss: ``mean CE + alpha sum_layers mean_seq balance``.

Departures from the source, each as the program has it: the depth, the number
of leading dense layers, the experts held and the vocabulary are the cut's
(``num_layers``, ``first_k_dense_replace``, ``num_experts`` /
``expert_offset``, ``vocab_size``); the kept layers' kinds are the file's
``layer_types_held`` (published layers 1-5), not a prefix of ``layer_types``;
nothing stands in for the experts on other chips.

Memory. A batch goes through one sequence at a time; around each layer
application, each block of queries and the head stands a ``jax.checkpoint``
(at 8,192 positions and 32 heads a block's logits are 1 GB). None of that
changes the arithmetic. Adam's moments wait on the host between the steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai import mlp, rms_norm as norm, stepped_bias
from benchmark.reference.qwen3_next import routed_part
from benchmark.reference.vit import adamw_update, learning_rate

QUERY_BLOCK = 1024  # rows of the dense causal logits alive at a time
WEIGHT_EPS = 1e-6  # in the selected scores' normalisation (the family's code)


def short_conv(x, p):
    """One sequence ``[S, D]`` through the double-gated short convolution."""
    seq, dim = x.shape
    gates = x @ p["to_qkv"]["in_proj"]["kernel"]
    b, c, inner = gates[:, :dim], gates[:, dim:2 * dim], gates[:, 2 * dim:]
    u, w = b * inner, p["conv"]["kernel"]  # [3, D]
    zeros = jnp.zeros((2, dim), x.dtype)
    before_2 = jnp.concatenate([zeros, u])[:seq]  # u_{t-2}
    before_1 = jnp.concatenate([zeros[:1], u])[:seq]  # u_{t-1}
    conv = w[0] * before_2 + w[1] * before_1 + w[2] * u
    return (c * conv) @ p["to_out"]["out_proj"]["kernel"]


def rotate(x, theta: float):
    """Rotary on every lane of ``x [S, H, D]``: lane ``i`` pairs with ``i + D
    / 2`` at angular frequency ``theta ** (-i / (D / 2))``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :])[:, None, :]  # [S, 1, half]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle), second * jnp.cos(angle) + first * jnp.sin(angle)],
        axis=-1,
    )


def attention(x, p, heads: int, kv_heads: int, theta: float, eps: float):
    """One sequence ``[S, D]`` through the grouped-query attention of
    ``heads`` query heads of ``D / heads`` on ``kv_heads`` key/value heads."""
    qkv, (seq, dim) = p["to_qkv"], (x.shape[0], x.shape[1] // heads)
    q = (x @ qkv["q"]["kernel"]).reshape(seq, heads, dim)
    k = (x @ qkv["k"]["kernel"]).reshape(seq, kv_heads, dim)
    v = (x @ qkv["v"]["kernel"]).reshape(seq, kv_heads, dim)
    q = rotate(norm(q, qkv["q_norm"], eps), theta)
    k = rotate(norm(k, qkv["k_norm"], eps), theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))  # query head h reads head h // group

    block = math.gcd(seq, QUERY_BLOCK)

    @jax.checkpoint  # the backward holds one block's [H, block, S] logits, not the sequence's
    def attend(operands):
        q_rows, rows = operands
        scores = jnp.einsum("qhe,khe->hqk", q_rows, k) * dim ** -0.5
        visible = jnp.arange(seq)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    # One block of queries after another (a loop, so that no two blocks' logits are alive at once).
    out = jax.lax.map(attend, (q.reshape(seq // block, block, heads, dim), jnp.arange(seq).reshape(-1, block)))
    return jnp.einsum("qhe,hed->qd", out.reshape(seq, heads, dim), p["to_out"]["kernel"])


def route(x, p, bias, model: dict):
    """``(scores [S, E], chosen [S, k], weights [S, k])``."""
    scores = jax.nn.sigmoid(x @ p["route"]["kernel"])
    _, chosen = jax.lax.top_k(scores + bias, model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True) + WEIGHT_EPS
    return scores, chosen, model["routed_scaling_factor"] * picked / total


def expert_layer(x, p, bias, model: dict):
    """One sequence ``[S, D]`` -> ``(y, counts [E], balance)``."""
    experts, k = model["num_experts_published"], model["num_experts_per_tok"]
    scores, chosen, weights = route(x, p, jax.lax.stop_gradient(bias), model)
    y = routed_part(x, p, chosen, weights, model["num_experts"], model["expert_offset"])
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1)).astype(jnp.float32)
    share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    balance = jnp.sum(counts * (experts / (k * x.shape[0])) * share)
    return y, counts, balance


MIXERS = {"conv": "ShortConvBlock_0", "full_attention": "GatedSelfAttentionBlock_0"}


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _layer(h, p, bias, kind, model_items):
    model = dict(model_items)
    eps = model["norm_eps"]
    x = norm(h, p["attn_norm"], eps)
    if kind == "conv":
        h = h + short_conv(x, p[MIXERS[kind]])
    else:
        h = h + attention(
            x, p[MIXERS[kind]], model["num_attention_heads"], model["num_key_value_heads"], model["rope_theta"], eps
        )
    x = norm(h, p["ffn_norm"], eps)
    if "moe" not in p:
        return h + mlp(x, p["GatedFFBlock_0"]), None, None
    y, counts, balance = expert_layer(x, p["moe"], bias, model)
    return h + y, counts, balance


def layer(h, p, bias, kind: str, model: dict):
    return _layer(h, p, bias, kind, _static(model))


def _static(model: dict) -> tuple:
    """The sizes the equations read, hashable for ``jax.checkpoint``."""
    keys = (
        "num_attention_heads", "num_key_value_heads", "norm_eps", "num_experts",
        "num_experts_published", "expert_offset", "num_experts_per_tok", "routed_scaling_factor",
    )
    return tuple((key, model[key]) for key in keys) + (("rope_theta", float(model["rope_parameters"]["rope_theta"])),)


@jax.checkpoint
def head_cross_entropy(table, h, targets):
    logits = h @ table.T
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def hidden_states(params, bias, inputs, model: dict):
    """``(the stack's output before the final norm, balance summed over the
    routed layers, counts [R, E])`` of one sequence of ids ``[S]``; ``bias``
    is ``[R, E]``, a row a routed layer."""
    h, row = params["embed"]["embedding"][inputs], 0
    counts, balance = [], 0.0
    for i, kind in enumerate(model["layer_types_held"]):
        routed = "moe" in params[f"layer_{i}"]
        h, c, b = layer(h, params[f"layer_{i}"], bias[row] if routed else None, kind, model)
        if routed:
            counts.append(c)
            balance, row = balance + b, row + 1
    return h, balance, jnp.stack(counts)


def sequence_logits(params, bias, inputs, model: dict):
    h, _, _ = hidden_states(params, bias, inputs, model)
    return norm(h, params["final_norm"], model["norm_eps"]) @ params["embed"]["embedding"].T


def sequence_loss(params, bias, tokens, model: dict, sequences: int):
    """This sequence's part of the batch's loss (the parts add up to it)."""
    h, balance, counts = hidden_states(params, bias, tokens[:-1], model)
    ce = head_cross_entropy(
        params["embed"]["embedding"], norm(h, params["final_norm"], model["norm_eps"]), tokens[1:]
    )
    return (jnp.mean(ce) + model["recipe"]["balance_alpha"] * balance) / sequences, counts


def initial_bias(model: dict):
    routed = model["num_layers"] - model["first_k_dense_replace"]
    return jnp.zeros((routed, model["num_experts_published"]), jnp.float32)


def make_loss_and_grad(model: dict):
    """``fn(params, bias, tokens [B, S + 1]) -> (loss, gradient, counts [R,
    E])``, one sequence at a time."""

    @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(2, 3, 4))
    def block(params, bias, acc_loss, acc_grad, acc_counts, tokens, sequences):
        with jax.default_matmul_precision("highest"):
            (loss, counts), grad = jax.value_and_grad(sequence_loss, has_aux=True)(
                params, bias, tokens, model, sequences
            )
        return acc_loss + loss, jax.tree.map(jnp.add, acc_grad, grad), acc_counts + counts

    def loss_and_grad(params, bias, tokens):
        acc_loss = jnp.zeros((), jnp.float32)
        acc_grad = jax.tree.map(jnp.zeros_like, params)
        acc_counts = jnp.zeros_like(bias)
        for row in tokens:
            acc_loss, acc_grad, acc_counts = block(
                params, bias, acc_loss, acc_grad, acc_counts, row, tokens.shape[0]
            )
        return acc_loss, acc_grad, acc_counts

    return loss_and_grad


def check_layout(params, config: dict) -> None:
    """The parameter tree has the layout this file reads and the sizes the
    configuration's file states: the cut's layers and their kinds, the
    experts held, no shared expert, no head of its own."""
    d, heads, kv_heads = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    dim, held, width = d // heads, config["num_experts"], config["moe_intermediate_size"]
    kinds, dense = config["layer_types_held"], config["first_k_dense_replace"]
    layers = [f"layer_{i}" for i in range(config["num_layers"])]
    of_kind = lambda kind: sorted(name for name, k in zip(layers, kinds) if k == kind)
    with_block = lambda block: sorted(k for k in params if k.startswith("layer_") and block in params[k])
    conv_layers, full_layers = with_block("ShortConvBlock_0"), with_block("GatedSelfAttentionBlock_0")
    if not (conv_layers and full_layers and len(kinds) == len(layers)):
        raise ValueError(f"the program's model is not the configuration's: {conv_layers}, {full_layers} for {kinds}")
    conv = params[conv_layers[0]]["ShortConvBlock_0"]
    attn = params[full_layers[0]]["GatedSelfAttentionBlock_0"]
    moe = params[layers[dense]]["moe"]
    found = {
        "embedding": tuple(params["embed"]["embedding"].shape),
        "in_proj": tuple(conv["to_qkv"]["in_proj"]["kernel"].shape),
        "conv": tuple(conv["conv"]["kernel"].shape),
        "out_proj": tuple(conv["to_out"]["out_proj"]["kernel"].shape),
        "q": tuple(attn["to_qkv"]["q"]["kernel"].shape),
        "k": tuple(attn["to_qkv"]["k"]["kernel"].shape),
        "q_norm": tuple(attn["to_qkv"]["q_norm"]["scale"].shape),
        "attn_out": tuple(attn["to_out"]["kernel"].shape),
        "dense_gate": tuple(params["layer_0"]["GatedFFBlock_0"]["fc1"]["gate"]["kernel"].shape),
        "router": tuple(moe["route"]["kernel"].shape),
        "routed_gate": tuple(moe["experts"]["fc1"]["gate_experts_w1"].shape),
        "routed_down": tuple(moe["experts"]["fc2"]["experts_w2"].shape),
        "beside_the_routed": sorted(k for k in moe if k not in ("route", "experts")),
        "top_level": sorted(k for k in params if not k.startswith("layer_")),
        "conv_layers": conv_layers,
        "full_layers": full_layers,
        "routed_layers": sorted(k for k in params if k.startswith("layer_") and "moe" in params[k]),
    }
    stated = {
        "embedding": (config["vocab_size"], d),
        "in_proj": (d, 3 * d),
        "conv": (config["conv_L_cache"], d),
        "out_proj": (d, d),
        "q": (d, heads * dim),
        "k": (d, kv_heads * dim),
        "q_norm": (dim,),
        "attn_out": (heads, dim, d),
        "dense_gate": (d, config["intermediate_size"]),
        "router": (d, config["num_experts_published"]),
        "routed_gate": (held, d, width),
        "routed_down": (held, width, d),
        "beside_the_routed": [],  # no shared expert
        "top_level": ["embed", "final_norm"],  # the head is the table
        "conv_layers": of_kind("conv"),
        "full_layers": of_kind("full_attention"),
        "routed_layers": sorted(layers[dense:]),
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


def follow_steps(params, batches, hp: dict, model: dict):
    """Follow the first ``len(batches)`` updates from ``params`` and a zero
    selection bias.

    ``batches`` is a list of token arrays ``[B, S + 1]``; ``hp`` holds the
    recipe's numbers under the names of ``reference/vit.py``'s
    ``learning_rate``; ``model`` the configuration's file (sizes and
    ``recipe``). Returns each step's loss, the first gradient as the
    optimizer's moments get it (after the clip), the parameters' change after
    the last update (the last two as lists of host arrays in the tree's
    order), each step's routing counts and the selection bias after the last
    update."""
    loss_and_grad = make_loss_and_grad(model)
    update = jax.jit(
        functools.partial(
            adamw_update, weight_decay=hp["weight_decay"], clip_grad_norm=hp["clip_grad_norm"]
        ),
        donate_argnums=(1, 2, 3),
    )
    start, bias = params, initial_bias(model)
    mu = nu = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, step_counts, first_grad = [], [], None
    for count, tokens in enumerate(batches):
        loss, grads, counts = loss_and_grad(params, bias, tokens)
        bias = stepped_bias(bias, counts, model["recipe"]["bias_update_rate"])
        params, mu, nu, clipped = update(
            params, grads, mu, nu, jnp.float32(learning_rate(count, hp)), jnp.float32(count + 1)
        )
        losses.append(float(loss))
        step_counts.append(np.asarray(counts))
        if first_grad is None:
            first_grad = [np.asarray(g) for g in jax.tree.leaves(clipped)]
        del grads, clipped
        mu, nu = jax.device_get((mu, nu))
    change = [np.asarray(a - b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(start))]
    return {
        "losses": losses, "first_grad": first_grad, "change": change,
        "counts": step_counts, "select_bias": np.asarray(bias),
    }
