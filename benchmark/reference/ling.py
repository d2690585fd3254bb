"""Plain float32 reference for the Ling-3.0-flash cell: forward, loss,
gradient, AdamW and the selection bias's step.

Written from the language-model keys of the public ``config.json``
(https://huggingface.co/inclusionAI/Ling-3.0-flash-VL) and the papers whose
methods those keys name (Kimi delta attention, arXiv:2510.26692 section 3;
latent attention, arXiv:2405.04434 section 2.1; the router, arXiv:2412.19437
section 2.1.2), in plain ``jax.numpy``, float32, traced under
``jax.default_matmul_precision("highest")``. No kernel, no chunked form, no
sort, no gather of routed rows. It imports nothing of the program and is
handed nothing the program made: the benchmark draws the weights
(``benchmark/weights.py``) and the token batches from the seed and gives the
same arrays to both sides. The optimizer is ``reference/vit.py``'s AdamW.

Model. ``N(x) = x / rms(x) w`` (plain weight, eps ``rms_norm_eps``); ``h =
E[tokens]``; for each layer ``h += Mix_i(N(h)); h += FFN_i(N(h))``; ``logits =
N(h) W_head`` (untied). No bias anywhere. Layer ``i`` mixes by latent attention
where ``(i + 1) % layer_group_size == 0`` and by Kimi delta attention
otherwise; its FFN is SwiGLU at ``intermediate_size`` for ``i <
first_k_dense_replace`` and the expert layer after.

- KDA block (``H`` heads of ``d = head_dim`` keys and values, no grouping).
  ``q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))``, the
  convolution causal, depthwise, of width 4, WRITTEN AS FOUR SHIFTED PRODUCTS
  (position ``t`` reads ``t - 3 .. t``, zeros before the sequence). q and k
  are L2-normalised over ``d`` (``x rsqrt(sum x^2 + 1e-6)``), q scaled by
  ``d^-0.5``; no rotary. ``g = kda_lower_bound sigmoid(exp(A_log_h) (x W_f +
  dt_bias))``, a number a key lane in ``(lower_bound, 0)`` (the safe gate);
  ``beta = sigmoid(x W_b)``. Per head, from ``S_0 = 0 [d, d]``, ONE TOKEN AT A
  TIME with the decay vector written out::

      S'  = exp(g_t)[:, None] * S_{t-1}          # a decay a key lane (row of S)
      S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
      o_t = S_t^T q_t

  ``y = W_o (o / rms(o) w sigmoid(x W_g))`` per head (``w`` plain).
- Latent attention (``q_lora_rank`` null: a direct query). ``q = x W_q``, a
  head is ``[q_nope | q_rope]``. ``[c_kv | k_rope] = x W_kva``; ``c_kv =
  N(c_kv)``; a head of ``c_kv W_kvb`` is ``[k_nope | v]``. ``use_qk_norm``:
  each head's whole query passes ``N`` over its 192 lanes with one weight
  shared by the heads, the rotary key ``N`` over its 64, both before the
  rotation. Rotary on ``q_rope`` of every head and on the one ``k_rope``,
  which is BROADCAST to the heads; adjacent pairs ``(2i, 2i + 1)`` at ``pos
  theta ** (-2i / rope)``. Logits ``q . [k_nope | k_rope] / sqrt(nope +
  rope)``, causal, softmax; the explicit mask, a block of queries at a time.
  ``y = W_o concat_h(sigmoid(x W_gate)_h out_h)``: a gate a head and token.
- Expert layer. ``s = sigmoid(x W_r)`` over all published experts; ``s' = s +
  b``. THE GROUP STEP WRITTEN OUT: the experts in ``n_group`` groups of
  consecutive ids; a group's score is the sum of its two largest ``s'``; group
  ``g`` is kept where fewer than ``topk_group`` groups come before it (a higher
  score, or the same score and a lower index); the ``k`` largest ``s'`` among
  the kept groups' experts are chosen (the others at ``-inf``); ``w = s_chosen
  / sum s_chosen * routed_scaling_factor`` (the scores without the bias).
  ``y = E_shared(x) + sum_{i chosen and held} w_i E_i(x)``: a loop over the
  experts HELD (``expert_offset``, ``num_experts`` of
  ``num_experts_published``), each run on every token and weighted by a vector
  that is zero where the token did not choose it. What the absent experts
  would add is left out, as in the program. No dropped token.
- The clamp. ``E(x) = W_2 (silu(min(W_1 x, L)) * clip(W_3 x, -L, L))`` where
  the layer's limit ``L`` > 0 (``expert_swiglu_limit_list`` for the routed
  experts, ``share_expert_swiglu_limit_list`` for the shared one, a number a
  published layer), plain SwiGLU where 0.
- ``b`` (no gradient) starts at 0 and after each step ``b_e += gamma
  sign(mean(c) - c_e)``, ``c`` the step's routings by expert.
- Balance term, a sequence: ``sum_e f_e P_e``, ``f_e = E / (k S) c_e``, ``P_e``
  the mean over the sequence of ``s_e / sum s``, weighted by ``alpha``.

Loss: ``mean CE + alpha sum_layers mean_seq balance``.

Departures from the sources, each listed under ``assumed`` in
``benchmark/configs/ling_3.0_flash.json``: the safe gate's form (the paper's
own gate is ``-exp(A_log) softplus(.)``, unbounded below); the two norms of
``use_qk_norm`` and where they sit; the head-wise gate on the latent layers
only; the group's score as the sum of its two largest; the clamp's form; no
MTP module; Adam's second-moment decay is the program's 0.999.

Memory. A batch goes through one sequence at a time; around each layer
application, each block of queries and the head stands a ``jax.checkpoint``,
and the recurrence is a scan over blocks of ``TOKEN_BLOCK`` tokens with a
checkpoint a block, so that its backward holds one block's states (2 MB a
token at the published sizes) and not the sequence's. None of that changes
the arithmetic. Adam's moments and the seeded parameters wait on the host
between the steps and the update donates every tree it is handed
(:func:`follow_steps`, as ``reference/xing.py``'s at the same 12 GB of state).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai import rms_norm as norm, rotate_pairs, stepped_bias
from benchmark.reference.vit import adamw_update, learning_rate
from benchmark.reference.xing import _trim_heap as trim_heap

QUERY_BLOCK = 1024  # rows of the dense causal logits alive at a time
TOKEN_BLOCK = 256  # tokens of the recurrence between two checkpoints
L2_EPS = 1e-6


def conv4_silu(x, kernel):
    """``silu(y)``, ``y_t = w_0 x_{t-3} + w_1 x_{t-2} + w_2 x_{t-1} + w_3 x_t`` a
    channel, on ``x [S, C]`` with ``kernel [4, C]``: four shifted products,
    zeros before the sequence."""
    seq = x.shape[0]
    zeros = jnp.zeros((3, x.shape[1]), x.dtype)
    back_3 = jnp.concatenate([zeros, x])[:seq]
    back_2 = jnp.concatenate([zeros[:2], x])[:seq]
    back_1 = jnp.concatenate([zeros[:1], x])[:seq]
    return jax.nn.silu(kernel[0] * back_3 + kernel[1] * back_2 + kernel[2] * back_1 + kernel[3] * x)


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def vector_decay_delta_rule(q, k, v, g, beta):
    """The recurrence on one sequence, a token at a time: ``q, k, g [S, H,
    d_k]``, ``v [S, H, d_v]``, ``beta [S, H]`` -> ``o [S, H, d_v]``."""
    seq, heads, dk = q.shape
    block = math.gcd(seq, TOKEN_BLOCK)

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, :, None] * state  # Diag(exp(g_t)) S: key lane c of every head by its own decay
        read = jnp.einsum("hkv,hk->hv", state, k)
        state = state + k[:, :, None] * (beta[:, None] * (v - read))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = tuple(x.reshape((seq // block, block) + x.shape[1:]) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(tokens, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), blocks)
    return out.reshape(v.shape)


def kda_gate(a, a_log, dt_bias, lower_bound: float):
    """``g [S, H, d]`` from the gate projection ``a [S, H d]``: the safe gate."""
    heads = a_log.shape[0]
    shifted = (a + dt_bias).reshape(a.shape[0], heads, -1)
    return lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[None, :, None] * shifted)


def kda_block(x, p, model: dict):
    """One sequence ``[S, D]`` through the Kimi delta attention block."""
    heads, dim, seq = model["num_attention_heads"], model["head_dim"], x.shape[0]
    proj, conv = p["to_qkv"], p["conv"]
    q = conv4_silu(x @ proj["q"]["kernel"], conv["q_kernel"]).reshape(seq, heads, dim)
    k = conv4_silu(x @ proj["k"]["kernel"], conv["k_kernel"]).reshape(seq, heads, dim)
    v = conv4_silu(x @ proj["v"]["kernel"], conv["v_kernel"]).reshape(seq, heads, dim)
    q, k = l2_normalise(q) * dim ** -0.5, l2_normalise(k)
    g = kda_gate(x @ proj["f"]["kernel"], p["A_log"], p["dt_bias"], float(model["kda_lower_bound"]))
    beta = jax.nn.sigmoid(x @ proj["b"]["kernel"])
    o = vector_decay_delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + model["rms_norm_eps"])
    o = o * p["gate_norm"]["scale"] * jax.nn.sigmoid(x @ proj["g"]["kernel"]).reshape(seq, heads, dim)
    return jnp.einsum("she,hed->sd", o, p["to_out"]["kernel"])


def gated_latent_attention(x, p, model: dict):
    """One sequence ``[S, D]`` through the latent attention with a direct
    query, the norms of ``use_qk_norm`` and the head-wise output gate."""
    heads, nope = model["num_attention_heads"], model["qk_nope_head_dim"]
    rope, vdim, rank = model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"]
    theta, eps = float(model["rope_theta"]), model["rms_norm_eps"]
    qkv, seq = p["to_qkv"], x.shape[0]
    q = (x @ qkv["q"]["kernel"]).reshape(seq, heads, nope + rope)
    kv = x @ qkv["kv_a"]["kernel"]
    c_kv, k_rope = norm(kv[:, :rank], qkv["kv_norm"], eps), kv[:, rank:]
    kv = (c_kv @ qkv["kv_b"]["kernel"]).reshape(seq, heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q, k_rope = norm(q, qkv["q_head_norm"], eps), norm(k_rope, qkv["k_rope_norm"], eps)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1)
    k_rope = rotate_pairs(k_rope, theta)  # [S, rope]: one head, shared
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, None, :], (seq, heads, rope))], axis=-1)

    block = math.gcd(seq, QUERY_BLOCK)

    @jax.checkpoint  # the backward holds one block's [H, block, S] logits, not the sequence's
    def attend(operands):
        q_rows, rows = operands
        scores = jnp.einsum("qhe,khe->hqk", q_rows, k) * (nope + rope) ** -0.5
        visible = jnp.arange(seq)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    out = jax.lax.map(attend, (q.reshape(seq // block, block, heads, nope + rope), jnp.arange(seq).reshape(-1, block)))
    out = out.reshape(seq, heads, vdim) * jax.nn.sigmoid(x @ qkv["gate"]["kernel"])[:, :, None]
    return jnp.einsum("qhe,hed->qd", out, p["to_out"]["kernel"])


def clamped_swiglu(x, gate, up, down, limit: float):
    """``W_2 (silu(min(W_1 x, L)) * clip(W_3 x, -L, L))`` where ``L`` > 0, the
    plain SwiGLU where 0."""
    a, b = x @ gate, x @ up
    if limit > 0:
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    return (jax.nn.silu(a) * b) @ down


def mlp(x, p, limit: float = 0.0):
    return clamped_swiglu(x, p["fc1"]["gate"]["kernel"], p["fc1"]["up"]["kernel"], p["fc2"]["kernel"], limit)


def groups_kept(biased, n_group: int, topk_group: int):
    """``biased [S, E]`` -> ``[S, n_group]`` bool: a group's score is the sum of
    its two largest entries; a group is kept where fewer than ``topk_group``
    groups come before it (a higher score, or the same and a lower index)."""
    by_group = biased.reshape(biased.shape[0], n_group, -1)
    score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)  # [S, G]
    index = jnp.arange(n_group)
    mine, other = score[:, :, None], score[:, None, :]  # [S, g, 1] against [S, 1, g']
    before = (other > mine) | ((other == mine) & (index[None, None, :] < index[None, :, None]))
    return jnp.sum(before, axis=-1) < topk_group


def route(x, p, bias, model: dict):
    """``(scores [S, E], chosen [S, k], weights [S, k])``."""
    scores = jax.nn.sigmoid(x @ p["route"]["kernel"])
    biased = scores + bias
    kept = groups_kept(biased, model["n_group"], model["topk_group"])
    per_group = scores.shape[-1] // model["n_group"]
    allowed = jnp.repeat(kept, per_group, axis=-1)  # expert e belongs to group e // per_group
    _, chosen = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = model["routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return scores, chosen, weights


def routed_part(x, p, chosen, weights, held: int, offset: int, limit: float):
    """``sum_{chosen and held} w_i E_i(x)`` for the ``held`` experts from
    ``offset``: each on every token, weighted by zero where not chosen."""
    w1g, w1u = p["experts"]["fc1"]["gate_experts_w1"], p["experts"]["fc1"]["up_experts_w1"]
    w2 = p["experts"]["fc2"]["experts_w2"]
    y = jnp.zeros_like(x)
    for local in range(held):
        weight = jnp.sum(jnp.where(chosen == offset + local, weights, 0.0), axis=-1)  # [S]
        y = y + weight[:, None] * clamped_swiglu(x, w1g[local], w1u[local], w2[local], limit)
    return y


def expert_layer(x, p, bias, model: dict, limit: float, shared_limit: float):
    """One sequence ``[S, D]`` -> ``(y, counts [E], balance)``."""
    experts, k = model["num_experts_published"], model["num_experts_per_tok"]
    scores, chosen, weights = route(x, p, jax.lax.stop_gradient(bias), model)
    y = mlp(x, p["shared"], shared_limit) + routed_part(
        x, p, chosen, weights, model["num_experts"], model["expert_offset"], limit
    )
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1)).astype(jnp.float32)
    share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    balance = jnp.sum(counts * (experts / (k * x.shape[0])) * share)
    return y, counts, balance


def is_latent(layer_index: int, model: dict) -> bool:
    return (layer_index + 1) % model["layer_group_size"] == 0


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _layer(h, p, bias, layer_index, model_items):
    model = dict(model_items)
    eps = model["rms_norm_eps"]
    x = norm(h, p["attn_norm"], eps)
    if is_latent(layer_index, model):
        h = h + gated_latent_attention(x, p["LatentSelfAttentionBlock_0"], model)
    else:
        h = h + kda_block(x, p["KDABlock_0"], model)
    x = norm(h, p["ffn_norm"], eps)
    if "moe" not in p:
        return h + mlp(x, p["GatedFFBlock_0"]), None, None
    limits = (model["expert_swiglu_limit_list"][layer_index], model["share_expert_swiglu_limit_list"][layer_index])
    y, counts, balance = expert_layer(x, p["moe"], bias, model, *map(float, limits))
    return h + y, counts, balance


def layer(h, p, bias, layer_index: int, model: dict):
    return _layer(h, p, bias, layer_index, _static(model))


def _static(model: dict) -> tuple:
    """The sizes the equations read, hashable for ``jax.checkpoint``."""
    keys = (
        "num_attention_heads", "head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "rope_theta", "rms_norm_eps", "kda_lower_bound", "layer_group_size", "num_experts",
        "num_experts_published", "expert_offset", "num_experts_per_tok", "routed_scaling_factor", "n_group",
        "topk_group",
    )
    lists = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")
    return tuple((key, model[key]) for key in keys) + tuple((key, tuple(model[key])) for key in lists)


@jax.checkpoint
def head_cross_entropy(w_head, h, targets):
    logits = h @ w_head
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def hidden_states(params, bias, inputs, model: dict):
    """``(the stack's output before the final norm, balance summed over the
    routed layers, counts [R, E])`` of one sequence of ids ``[S]``; ``bias``
    is ``[R, E]``, a row a routed layer."""
    h, row = params["embed"]["embedding"][inputs], 0
    counts, balance = [], 0.0
    for i in range(model["num_layers"]):
        routed = "moe" in params[f"layer_{i}"]
        h, c, b = layer(h, params[f"layer_{i}"], bias[row] if routed else None, i, model)
        if routed:
            counts.append(c)
            balance, row = balance + b, row + 1
    return h, balance, jnp.stack(counts)


def sequence_logits(params, bias, inputs, model: dict):
    h, _, _ = hidden_states(params, bias, inputs, model)
    return norm(h, params["final_norm"], model["rms_norm_eps"]) @ params["lm_head"]["kernel"]


def sequence_loss(params, bias, tokens, model: dict, sequences: int):
    """This sequence's part of the batch's loss (the parts add up to it)."""
    h, balance, counts = hidden_states(params, bias, tokens[:-1], model)
    ce = head_cross_entropy(
        params["lm_head"]["kernel"], norm(h, params["final_norm"], model["rms_norm_eps"]), tokens[1:]
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
    experts held, a head of its own."""
    d, heads, dim = config["hidden_size"], config["num_attention_heads"], config["head_dim"]
    nope, rope, vdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    held, width, dense = config["num_experts"], config["moe_intermediate_size"], config["first_k_dense_replace"]
    layers = [f"layer_{i}" for i in range(config["num_layers"])]
    latent_layers = [name for i, name in enumerate(layers) if is_latent(i, config)]
    kda_layers = [name for i, name in enumerate(layers) if not is_latent(i, config)]
    with_block = lambda block: sorted(k for k in params if k.startswith("layer_") and block in params[k])
    if not (with_block("KDABlock_0") and with_block("LatentSelfAttentionBlock_0") and dense < len(layers)):
        raise ValueError(f"the program's model has no period of the configuration's: {sorted(params)}")
    kda = params[with_block("KDABlock_0")[0]]["KDABlock_0"]
    attn = params[with_block("LatentSelfAttentionBlock_0")[0]]["LatentSelfAttentionBlock_0"]
    moe = params[layers[dense]]["moe"]
    found = {
        "embedding": tuple(params["embed"]["embedding"].shape),
        "kda_inputs": {name: tuple(kda["to_qkv"][name]["kernel"].shape) for name in sorted(kda["to_qkv"])},
        "kda_conv": {name: tuple(kda["conv"][name].shape) for name in sorted(kda["conv"])},
        "A_log": tuple(kda["A_log"].shape),
        "dt_bias": tuple(kda["dt_bias"].shape),
        "gate_norm": tuple(kda["gate_norm"]["scale"].shape),
        "kda_out": tuple(kda["to_out"]["kernel"].shape),
        "latent_inputs": {
            name: tuple(next(iter(attn["to_qkv"][name].values())).shape) for name in sorted(attn["to_qkv"])
        },
        "latent_out": tuple(attn["to_out"]["kernel"].shape),
        "dense_gate": tuple(params["layer_0"]["GatedFFBlock_0"]["fc1"]["gate"]["kernel"].shape),
        "router": tuple(moe["route"]["kernel"].shape),
        "routed_gate": tuple(moe["experts"]["fc1"]["gate_experts_w1"].shape),
        "routed_down": tuple(moe["experts"]["fc2"]["experts_w2"].shape),
        "shared_gate": tuple(moe["shared"]["fc1"]["gate"]["kernel"].shape),
        "head": tuple(params["lm_head"]["kernel"].shape),
        "kda_layers": with_block("KDABlock_0"),
        "latent_layers": with_block("LatentSelfAttentionBlock_0"),
        "routed_layers": sorted(k for k in params if k.startswith("layer_") and "moe" in params[k]),
    }
    stated = {
        "embedding": (config["vocab_size"], d),
        "kda_inputs": {"b": (d, heads), **{name: (d, heads * dim) for name in ("f", "g", "k", "q", "v")}},
        "kda_conv": {name: (config["short_conv_kernel_size"], heads * dim) for name in ("k_kernel", "q_kernel", "v_kernel")},
        "A_log": (heads,),
        "dt_bias": (heads * dim,),
        "gate_norm": (dim,),
        "kda_out": (heads, dim, d),
        "latent_inputs": {
            "gate": (d, heads), "k_rope_norm": (rope,), "kv_a": (d, config["kv_lora_rank"] + rope),
            "kv_b": (config["kv_lora_rank"], heads * (nope + vdim)), "kv_norm": (config["kv_lora_rank"],),
            "q": (d, heads * (nope + rope)), "q_head_norm": (nope + rope,),
        },
        "latent_out": (heads, vdim, d),
        "dense_gate": (d, config["intermediate_size"]),
        "router": (d, config["num_experts_published"]),
        "routed_gate": (held, d, width),
        "routed_down": (held, width, d),
        "shared_gate": (d, config["moe_shared_expert_intermediate_size"]),
        "head": (d, config["vocab_size"]),
        "kda_layers": sorted(kda_layers),
        "latent_layers": sorted(latent_layers),
        "routed_layers": sorted(layers[dense:]),
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


def follow_steps(params, batches, hp: dict, model: dict):
    """Follow the first ``len(batches)`` updates from ``params`` and a zero
    selection bias. CONSUMES ``params``: the first update donates them.

    ``batches`` is a list of token arrays ``[B, S + 1]``; ``hp`` holds the
    recipe's numbers under the names of ``reference/vit.py``'s
    ``learning_rate``; ``model`` the configuration's file (sizes and
    ``recipe``). Returns each step's loss, the first gradient as the
    optimizer's moments get it (after the clip), the parameters' change after
    the last update (the last two as lists of host arrays in the tree's
    order), each step's routing counts and the selection bias after the last
    update. Memory as ``reference/xing.py::follow_steps`` (12.27 GB of state
    here): the update donates all four trees, the seeded parameters and the
    moments wait on the host, the zero moments are made on the device, and the
    change is subtracted on the device a leaf at a time."""
    loss_and_grad = make_loss_and_grad(model)
    update = jax.jit(
        functools.partial(
            adamw_update, weight_decay=hp["weight_decay"], clip_grad_norm=hp["clip_grad_norm"]
        ),
        donate_argnums=(0, 1, 2, 3),
    )
    trim_heap()
    start = [np.asarray(leaf) for leaf in jax.tree.leaves(params)]
    bias = initial_bias(model)
    mu = nu = None
    losses, step_counts, first_grad, last = [], [], None, len(batches) - 1
    for count, tokens in enumerate(batches):
        loss, grads, counts = loss_and_grad(params, bias, tokens)
        bias = stepped_bias(bias, counts, model["recipe"]["bias_update_rate"])
        if mu is None:  # the zero moments are made on the device, where the first update consumes them
            mu, nu = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params)
        params, mu, nu, clipped = update(
            params, grads, mu, nu, jnp.float32(learning_rate(count, hp)), jnp.float32(count + 1)
        )
        losses.append(float(loss))
        step_counts.append(np.asarray(counts))
        if first_grad is None:
            first_grad = [np.asarray(g) for g in jax.tree.leaves(clipped)]
            trim_heap()  # both of this file's programs are compiled by now
        del grads, clipped
        # The moments wait on the host while the next step's gradient is taken; after the last nobody reads them.
        mu, nu = jax.device_get((mu, nu)) if count < last else (None, None)
    after, change = jax.tree.leaves(params), []
    del params
    for index in range(len(after)):
        change.append(np.asarray(after[index] - jnp.asarray(start[index])))
        after[index] = start[index] = None
    return {
        "losses": losses, "first_grad": first_grad, "change": change,
        "counts": step_counts, "select_bias": np.asarray(bias),
    }
