"""Plain float32 reference for the Qwen3-Next cell: forward, loss, gradient
and AdamW.

Written from the model's public ``config.json`` (https://huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct) and the equations of the family's public
modelling code (the gated delta rule is arXiv:2412.06464's), in plain
``jax.numpy``, float32, traced under ``jax.default_matmul_precision
("highest")``. No kernel, no chunked form, no sort, no gather of routed rows.
It imports nothing of the program and is handed nothing the program made: the
benchmark draws the weights (``benchmark/weights.py``) and the token batches
from the seed and gives the same arrays to both sides. The optimizer is
``reference/vit.py``'s AdamW.

Model. ``N(x) = x / rms(x) (1 + w)`` (the stored weight is the offset from 1);
``h = E[tokens]``; for each layer ``h += Mix_i(N(h)); h += MoE(N(h))``;
``logits = N(h) W_head`` (untied). No bias anywhere. Layer ``i`` mixes tokens
by softmax attention where ``(i + 1) % full_attention_interval == 0`` and by
the gated delta rule otherwise.

- Gated delta-rule block (``H_k`` key heads of ``d_k``, ``H`` value heads of
  ``d_v``, ``r = H / H_k``). ``x W_qkvz`` is laid out by key head, ``[q d_k |
  k d_k | v r d_v | z r d_v]`` for each; ``x W_ba`` by key head ``[b r | a
  r]``. ``[q | k | v]``, heads flattened, pass a causal depthwise convolution
  of width 4 (position ``t`` reads ``t - 3 .. t``) and SiLU. q and k are
  L2-normalised over ``d_k`` (``x rsqrt(sum x^2 + 1e-6)``), each key head is
  repeated to its ``r`` value heads, q is scaled by ``d_k^-0.5``. ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``. Per value head,
  from ``S_0 = 0 [d_k, d_v]``, ONE TOKEN AT A TIME::

      S'  = exp(g_t) S_{t-1}
      S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
      o_t = S_t^T q_t

  ``y = W_o (o / rms(o) w silu(z))`` per value head (``w`` plain, eps as the
  model's).
- Gated attention block (``H`` query heads on ``H_kv`` key/value heads of
  ``D``). ``x W_q`` is by head ``[query D | gate D]``; query and k pass a
  per-head ``N`` over ``D``; their first ``D partial_rotary_factor`` lanes
  rotate, lane ``i`` paired with ``i + half`` at ``theta ** (-i / half)``;
  each key/value head is repeated to its ``H / H_kv`` query heads; causal
  softmax at ``D^-0.5``, the explicit mask, a block of queries at a time;
  ``y = W_o (out sigmoid(gate))``.
- Expert layer. ``p = softmax(x W_r)`` over all published experts; the ``k``
  largest; ``g = p_sel / sum p_sel``; ``y = sum_{selected and held} g_i
  E_i(x) + sigmoid(x w_s) E_shared(x)``: a loop over the experts HELD
  (``expert_offset``, ``num_experts`` of ``num_experts_published``), each run
  on every token and weighted by a vector that is zero where the token did not
  select it. What the absent experts would add is left out, as in the
  program. No selection bias, no dropped token.
- Balance term, a sequence: ``sum_e f_e P_e``, ``f_e = E / (k S) c_e``, ``P_e``
  the mean over the sequence of ``p_e``, weighted by ``alpha`` (assumed: the
  configuration's ``recipe.balance_alpha``).

Loss: ``mean CE + alpha sum_layers mean_seq balance``.

Memory. A batch goes through one sequence at a time; around each layer
application and the head stands a ``jax.checkpoint``, and the recurrence is a
scan over blocks of ``TOKEN_BLOCK`` tokens with a checkpoint a block, so that
its backward holds one block's states (2 MB a token at the published sizes)
and not the sequence's. None of that changes the arithmetic. Adam's moments
wait on the host between the steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.vit import adamw_update, learning_rate

QUERY_BLOCK = 1024  # rows of the dense causal logits alive at a time
TOKEN_BLOCK = 256  # tokens of the recurrence between two checkpoints
L2_EPS = 1e-6


def norm(x, p, eps):
    """RMSNorm with the weight stored as its offset from 1."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + p["offset"])


def causal_conv(x, kernel):
    """``x [S, C]``, ``kernel [W, C]``: ``y_t = sum_i kernel[i] x_{t - W + 1 + i}``."""
    width = kernel.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(kernel[i] * padded[i:i + x.shape[0]] for i in range(width))


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence on one sequence, a token at a time: ``q, k [S, H, d_k]``,
    ``v [S, H, d_v]``, ``g, beta [S, H]`` -> ``o [S, H, d_v]``."""
    seq, heads, dk = q.shape
    block = math.gcd(seq, TOKEN_BLOCK)

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k)
        state = state + k[:, :, None] * (beta[:, None] * (v - read))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = tuple(x.reshape((seq // block, block) + x.shape[1:]) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(tokens, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), blocks)
    return out.reshape(v.shape)


def gated_delta_block(x, p, model: dict):
    """One sequence ``[S, D]`` through the gated delta-rule block."""
    key_heads, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    heads, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    group, seq = heads // key_heads, x.shape[0]
    qkvz = (x @ p["to_qkv"]["qkvz"]["kernel"]).reshape(seq, key_heads, 2 * dk + 2 * group * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + group * dv].reshape(seq, heads, dv)
    z = qkvz[..., 2 * dk + group * dv:].reshape(seq, heads, dv)
    ba = (x @ p["to_qkv"]["ba"]["kernel"]).reshape(seq, key_heads, 2 * group)
    b, a = ba[..., :group].reshape(seq, heads), ba[..., group:].reshape(seq, heads)

    mixed = jnp.concatenate([q.reshape(seq, -1), k.reshape(seq, -1), v.reshape(seq, -1)], axis=-1)
    mixed = jax.nn.silu(causal_conv(mixed, p["conv"]["kernel"]))
    q = mixed[:, :key_heads * dk].reshape(seq, key_heads, dk)
    k = mixed[:, key_heads * dk:2 * key_heads * dk].reshape(seq, key_heads, dk)
    v = mixed[:, 2 * key_heads * dk:].reshape(seq, heads, dv)
    q = jnp.repeat(l2_normalise(q), group, axis=1) * dk ** -0.5
    k = jnp.repeat(l2_normalise(k), group, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + model["rms_norm_eps"])
    o = o * p["gate_norm"]["scale"] * jax.nn.silu(z)
    return jnp.einsum("she,hed->sd", o, p["to_out"]["kernel"])


def rotate_leading_lanes(x, lanes: int, theta: float):
    """Rotary on the first ``lanes`` of ``x [S, H, D]``: lane ``i`` pairs
    with ``i + lanes / 2`` at angular frequency ``theta ** (-i / (lanes /
    2))``; the other lanes pass."""
    half = lanes // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :])[:, None, :]  # [S, 1, half]
    first, second = x[..., :half], x[..., half:lanes]
    return jnp.concatenate(
        [
            first * jnp.cos(angle) - second * jnp.sin(angle),
            second * jnp.cos(angle) + first * jnp.sin(angle),
            x[..., lanes:],
        ],
        axis=-1,
    )


def gated_attention(x, p, model: dict):
    """One sequence ``[S, D]`` through the gated grouped-query attention."""
    heads, kv_heads, dim = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    lanes, theta, eps = int(dim * model["partial_rotary_factor"]), float(model["rope_theta"]), model["rms_norm_eps"]
    qkv, seq = p["to_qkv"], x.shape[0]
    both = (x @ qkv["q"]["kernel"]).reshape(seq, heads, 2 * dim)
    query, gate = both[..., :dim], both[..., dim:]
    k = (x @ qkv["k"]["kernel"]).reshape(seq, kv_heads, dim)
    v = (x @ qkv["v"]["kernel"]).reshape(seq, kv_heads, dim)
    query = rotate_leading_lanes(norm(query, qkv["q_norm"], eps), lanes, theta)
    k = rotate_leading_lanes(norm(k, qkv["k_norm"], eps), lanes, theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))  # query head h reads head h // group
    out = []
    for start in range(0, seq, QUERY_BLOCK):
        rows = jnp.arange(start, min(start + QUERY_BLOCK, seq))
        scores = jnp.einsum("qhe,khe->hqk", query[rows], k) * dim ** -0.5
        visible = jnp.arange(seq)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khe->qhe", probs, v))
    out = jnp.concatenate(out) * jax.nn.sigmoid(gate)
    return jnp.einsum("qhe,hed->qd", out, p["to_out"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def mlp(x, p):
    return swiglu(x, p["fc1"]["gate"]["kernel"], p["fc1"]["up"]["kernel"], p["fc2"]["kernel"])


def route(x, p, model: dict):
    """``(p [S, E], chosen [S, k], weights [S, k])``: the softmax over all the
    published experts, its ``k`` largest, and those over their sum."""
    logits = x @ p["route"]["kernel"]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1, keepdims=True)
    _, chosen = jax.lax.top_k(probs, model["num_experts_per_tok"])
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    return probs, chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_part(x, p, chosen, weights, held: int, offset: int):
    """``sum_{selected and held} g_i E_i(x)`` for the ``held`` experts from
    ``offset``: each on every token, weighted by zero where not selected."""
    w1g, w1u = p["experts"]["fc1"]["gate_experts_w1"], p["experts"]["fc1"]["up_experts_w1"]
    w2 = p["experts"]["fc2"]["experts_w2"]
    y = jnp.zeros_like(x)
    for local in range(held):
        weight = jnp.sum(jnp.where(chosen == offset + local, weights, 0.0), axis=-1)  # [S]
        y = y + weight[:, None] * swiglu(x, w1g[local], w1u[local], w2[local])
    return y


def shared_part(x, p):
    return jax.nn.sigmoid(x @ p["shared_gate"]["kernel"]) * mlp(x, p["shared"])


def expert_layer(x, p, model: dict):
    """One sequence ``[S, D]`` -> ``(y, counts [E], balance)``."""
    experts, k = model["num_experts_published"], model["num_experts_per_tok"]
    probs, chosen, weights = route(x, p, model)
    y = shared_part(x, p) + routed_part(x, p, chosen, weights, model["num_experts"], model["expert_offset"])
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1)).astype(jnp.float32)
    balance = jnp.sum(counts * (experts / (k * x.shape[0])) * jnp.mean(probs, axis=0))
    return y, counts, balance


def mixer_of(layer_index: int, model: dict) -> str:
    full = (layer_index + 1) % model["full_attention_interval"] == 0
    return "GatedSelfAttentionBlock_0" if full else "GatedDeltaNetBlock_0"


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _layer(h, p, mixer, model_items):
    model = dict(model_items)
    eps = model["rms_norm_eps"]
    mix = gated_attention if mixer == "GatedSelfAttentionBlock_0" else gated_delta_block
    h = h + mix(norm(h, p["attn_norm"], eps), p[mixer], model)
    y, counts, balance = expert_layer(norm(h, p["ffn_norm"], eps), p["moe"], model)
    return h + y, counts, balance


def layer(h, p, layer_index: int, model: dict):
    return _layer(h, p, mixer_of(layer_index, model), _static(model))


def _static(model: dict) -> tuple:
    """The sizes the equations read, hashable for ``jax.checkpoint``."""
    keys = (
        "num_attention_heads", "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta",
        "rms_norm_eps", "linear_num_key_heads", "linear_key_head_dim", "linear_num_value_heads",
        "linear_value_head_dim", "num_experts", "num_experts_published", "expert_offset",
        "num_experts_per_tok", "full_attention_interval",
    )
    return tuple((key, model[key]) for key in keys)


@jax.checkpoint
def head_cross_entropy(w_head, h, targets):
    logits = h @ w_head
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def hidden_states(params, inputs, model: dict):
    """``(the stack's output before the final norm, balance summed over the
    layers, counts [layers, E])`` of one sequence of ids ``[S]``."""
    h = params["embed"]["embedding"][inputs]
    counts, balance = [], 0.0
    for i in range(model["num_layers"]):
        h, c, b = layer(h, params[f"layer_{i}"], i, model)
        counts.append(c)
        balance = balance + b
    return h, balance, jnp.stack(counts)


def sequence_logits(params, inputs, model: dict):
    h, _, _ = hidden_states(params, inputs, model)
    return norm(h, params["final_norm"], model["rms_norm_eps"]) @ params["lm_head"]["kernel"]


def sequence_loss(params, tokens, model: dict, sequences: int):
    """This sequence's part of the batch's loss (the parts add up to it)."""
    h, balance, counts = hidden_states(params, tokens[:-1], model)
    ce = head_cross_entropy(
        params["lm_head"]["kernel"], norm(h, params["final_norm"], model["rms_norm_eps"]), tokens[1:]
    )
    return (jnp.mean(ce) + model["recipe"]["balance_alpha"] * balance) / sequences, counts


def make_loss_and_grad(model: dict):
    """``fn(params, tokens [B, S + 1]) -> (loss, gradient, counts [layers,
    E])``, one sequence at a time."""

    @functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(1, 2, 3))
    def block(params, acc_loss, acc_grad, acc_counts, tokens, sequences):
        with jax.default_matmul_precision("highest"):
            (loss, counts), grad = jax.value_and_grad(sequence_loss, has_aux=True)(
                params, tokens, model, sequences
            )
        return acc_loss + loss, jax.tree.map(jnp.add, acc_grad, grad), acc_counts + counts

    def loss_and_grad(params, tokens):
        acc_loss = jnp.zeros((), jnp.float32)
        acc_grad = jax.tree.map(jnp.zeros_like, params)
        acc_counts = jnp.zeros((model["num_layers"], model["num_experts_published"]), jnp.float32)
        for row in tokens:
            acc_loss, acc_grad, acc_counts = block(params, acc_loss, acc_grad, acc_counts, row, tokens.shape[0])
        return acc_loss, acc_grad, acc_counts

    return loss_and_grad


def check_layout(params, config: dict) -> None:
    """The parameter tree has the layout this file reads and the sizes the
    configuration's file states: the cut's layers, the experts held."""
    d, heads, kv_heads, dim = (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    )
    key_heads, dk = config["linear_num_key_heads"], config["linear_key_head_dim"]
    value_heads, dv = config["linear_num_value_heads"], config["linear_value_head_dim"]
    held, width = config["num_experts"], config["moe_intermediate_size"]
    interval, depth = config["full_attention_interval"], config["num_layers"]
    full = [f"layer_{i}" for i in range(depth) if (i + 1) % interval == 0]
    linear = [f"layer_{i}" for i in range(depth) if (i + 1) % interval]
    gdn = params[linear[0]]["GatedDeltaNetBlock_0"]
    attn, moe = params[full[0]]["GatedSelfAttentionBlock_0"], params[full[0]]["moe"]
    found = {
        "embedding": tuple(params["embed"]["embedding"].shape),
        "qkvz": tuple(gdn["to_qkv"]["qkvz"]["kernel"].shape),
        "ba": tuple(gdn["to_qkv"]["ba"]["kernel"].shape),
        "conv": tuple(gdn["conv"]["kernel"].shape),
        "A_log": tuple(gdn["A_log"].shape),
        "gate_norm": tuple(gdn["gate_norm"]["scale"].shape),
        "gdn_out": tuple(gdn["to_out"]["kernel"].shape),
        "q": tuple(attn["to_qkv"]["q"]["kernel"].shape),
        "k": tuple(attn["to_qkv"]["k"]["kernel"].shape),
        "q_norm": tuple(attn["to_qkv"]["q_norm"]["offset"].shape),
        "attn_out": tuple(attn["to_out"]["kernel"].shape),
        "router": tuple(moe["route"]["kernel"].shape),
        "routed_gate": tuple(moe["experts"]["fc1"]["gate_experts_w1"].shape),
        "routed_down": tuple(moe["experts"]["fc2"]["experts_w2"].shape),
        "shared_gate_up": tuple(moe["shared"]["fc1"]["gate"]["kernel"].shape),
        "shared_gate": tuple(moe["shared_gate"]["kernel"].shape),
        "head": tuple(params["lm_head"]["kernel"].shape),
        "full_layers": sorted(k for k in params if k.startswith("layer_") and "GatedSelfAttentionBlock_0" in params[k]),
        "linear_layers": sorted(k for k in params if k.startswith("layer_") and "GatedDeltaNetBlock_0" in params[k]),
        "routed_layers": sorted(k for k in params if k.startswith("layer_") and "moe" in params[k]),
    }
    group = value_heads // key_heads
    stated = {
        "embedding": (config["vocab_size"], d),
        "qkvz": (d, key_heads * (2 * dk + 2 * group * dv)),
        "ba": (d, 2 * value_heads),
        "conv": (config["linear_conv_kernel_dim"], 2 * key_heads * dk + value_heads * dv),
        "A_log": (value_heads,),
        "gate_norm": (dv,),
        "gdn_out": (value_heads, dv, d),
        "q": (d, heads * 2 * dim),
        "k": (d, kv_heads * dim),
        "q_norm": (dim,),
        "attn_out": (heads, dim, d),
        "router": (d, config["num_experts_published"]),
        "routed_gate": (held, d, width),
        "routed_down": (held, width, d),
        "shared_gate_up": (d, config["shared_expert_intermediate_size"]),
        "shared_gate": (d, 1),
        "head": (d, config["vocab_size"]),
        "full_layers": sorted(full),
        "linear_layers": sorted(linear),
        "routed_layers": sorted(f"layer_{i}" for i in range(depth)),
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


def follow_steps(params, batches, hp: dict, model: dict):
    """Follow the first ``len(batches)`` updates from ``params``.

    ``batches`` is a list of token arrays ``[B, S + 1]``; ``hp`` holds the
    recipe's numbers under the names of ``reference/vit.py``'s
    ``learning_rate``; ``model`` the configuration's file (sizes and
    ``recipe``). Returns each step's loss, the first gradient as the
    optimizer's moments get it (after the clip) and the parameters' change
    after the last update (the last two as lists of host arrays in the tree's
    order)."""
    loss_and_grad = make_loss_and_grad(model)
    update = jax.jit(
        functools.partial(
            adamw_update, weight_decay=hp["weight_decay"], clip_grad_norm=hp["clip_grad_norm"]
        ),
        donate_argnums=(1, 2, 3),
    )
    start = params
    mu = nu = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, first_grad = [], None
    for count, tokens in enumerate(batches):
        loss, grads, _ = loss_and_grad(params, tokens)
        params, mu, nu, clipped = update(
            params, grads, mu, nu, jnp.float32(learning_rate(count, hp)), jnp.float32(count + 1)
        )
        losses.append(float(loss))
        if first_grad is None:
            first_grad = [np.asarray(g) for g in jax.tree.leaves(clipped)]
        del grads, clipped
        mu, nu = jax.device_get((mu, nu))
    change = [np.asarray(a - b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(start))]
    return {"losses": losses, "first_grad": first_grad, "change": change}
