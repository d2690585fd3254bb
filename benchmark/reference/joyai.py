"""Plain float32 reference for the JoyAI-LLM-Flash cell: forward, loss,
gradient, AdamW and the selection-bias update.

Written from the model's public ``config.json`` (https://huggingface.co/
jdopensource/JoyAI-LLM-Flash) and the paper whose methods it names
(arXiv:2412.19437: latent attention 2.1.1, the expert layer 2.1.2,
multi-token prediction 2.2, the recipe 4.2), in plain ``jax.numpy``, float32,
traced under ``jax.default_matmul_precision("highest")``. No kernel, no sort,
no gather of routed rows. It imports nothing of the program and is handed
nothing the program made: the benchmark draws the weights
(``benchmark/weights.py``) and the token batches from the seed and gives the
same arrays to both sides. The optimizer is ``reference/vit.py``'s AdamW.

Model (``x = RMSNorm(h)`` is a layer's pre-norm; no bias anywhere).
``h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h))``; layer 0's FFN is SwiGLU,
every later layer's the expert layer; a final RMSNorm; the untied head.

- Latent attention: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, a head is
  ``[q_nope | q_rope]``. ``[c_kv | k_rope] = x W_kva``; ``c_kv =
  RMSNorm(c_kv)``; a head of ``c_kv W_kvb`` is ``[k_nope | v]``. Rotary on
  ``q_rope`` of every head and on the one ``k_rope`` all heads share,
  adjacent pairs ``(2i, 2i + 1)`` by ``pos * theta ** (-2i / rope)``. Logits
  ``q . [k_nope | k_rope] / sqrt(nope + rope)``, causal, softmax; the
  explicit mask, a block of queries at a time.
- Expert layer: ``s = sigmoid(x W_r)``; the top ``k`` of ``s + b``; weights
  the selected ``s`` over their sum times ``routed_scaling_factor``; ``y =
  sum_{i selected and held} g_i E_i(x) + E_shared(x)``: a loop over the
  experts HELD (``expert_offset``, ``n_routed_experts`` of
  ``n_routed_experts_published``), each run on every token and weighted by a
  ``[S]`` vector that is zero where the token did not select it. What the
  absent experts would add is left out, as in the program: the cell is one
  chip's share of an expert-parallel layer. No token is dropped.
- ``b`` (no gradient) starts at 0 and after each step ``b_e += gamma
  sign(mean(c) - c_e)``, ``c`` the step's routings by expert over all
  ``n_routed_experts_published``.
- Balance loss, a sequence: ``sum_e f_e P_e``, ``f_e = E / (k S) c_e``, ``P_e``
  the mean over the sequence of ``s_e / sum s`` (eq. 17-20), weighted by
  ``alpha``.
- Multi-token prediction: ``h'_i = W_eh [RMSNorm(h_i) ; RMSNorm(E[t_{i+1}])]``
  with ``h_i`` the main stack's output BEFORE its final norm, one expert
  layer of its own, its own final norm, the model's head; scored on
  ``t_{i+2}``. A sequence of ``S + 1`` ids gives ``S`` main terms and
  ``S - 1`` MTP terms.

Loss: ``mean CE_main + lambda mean CE_mtp + alpha sum_layers mean_seq
balance``.

What the config and the paper leave open, set as the program sets it and
listed under ``assumed`` in ``benchmark/configs/joyai_llm_flash.json``: the
order of ``W_eh``'s two input halves (hidden state first); that ``h_i`` is
taken before the final norm; ``gamma``, ``alpha``, ``lambda``; the recipe.
Departure from the published recipe: Adam's second-moment decay is the
program's 0.999.

Memory. A batch goes through one sequence at a time; around each layer
application and each head stands a ``jax.checkpoint``, which changes no
arithmetic. Adam's moments wait on the host between the steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.vit import adamw_update, learning_rate

QUERY_BLOCK = 1024  # rows of the dense causal logits alive at a time


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rotate_pairs(x, theta):
    """Rotary position embedding on ``[S, ..., R]``: lane ``2i`` is paired
    with lane ``2i + 1``, at angular frequency ``theta ** (-2i / R)``."""
    s, r = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]  # [S, R/2]
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack(
        [even * jnp.cos(angle) - odd * jnp.sin(angle), odd * jnp.cos(angle) + even * jnp.sin(angle)],
        axis=-1,
    )
    return out.reshape(x.shape)


def latent_attention(x, p, model: dict):
    """Causal multi-head latent self-attention on one sequence ``[S, D]``."""
    heads, nope = model["num_attention_heads"], model["qk_nope_head_dim"]
    rope, vdim, rank = model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"]
    theta, eps = float(model["rope_theta"]), model["rms_norm_eps"]
    qkv, s = p["to_qkv"], x.shape[0]
    c_q = rms_norm(x @ qkv["q_a"]["kernel"], qkv["q_norm"], eps)
    q = (c_q @ qkv["q_b"]["kernel"]).reshape(s, heads, nope + rope)
    kv = x @ qkv["kv_a"]["kernel"]
    c_kv, k_rope = rms_norm(kv[:, :rank], qkv["kv_norm"], eps), kv[:, rank:]
    kv = (c_kv @ qkv["kv_b"]["kernel"]).reshape(s, heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1)
    k_rope = rotate_pairs(k_rope, theta)  # [S, rope]: one head, shared
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, None, :], (s, heads, rope))], axis=-1)
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = jnp.arange(start, min(start + QUERY_BLOCK, s))
        scores = jnp.einsum("qhe,khe->hqk", q[rows], k) * (nope + rope) ** -0.5
        visible = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khe->qhe", probs, v))
    return jnp.einsum("qhe,hed->qd", jnp.concatenate(out), p["to_out"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def mlp(x, p):
    return swiglu(x, p["fc1"]["gate"]["kernel"], p["fc1"]["up"]["kernel"], p["fc2"]["kernel"])


def route(x, p, bias, model: dict):
    """``(scores [S, E], chosen [S, k], weights [S, k])``."""
    scores = jax.nn.sigmoid(x @ p["route"]["kernel"])
    _, chosen = jax.lax.top_k(scores + bias, model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = model["routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return scores, chosen, weights


def expert_layer(x, p, bias, model: dict):
    """One sequence ``[S, D]`` -> ``(y, counts [E], balance)``."""
    experts, k = model["n_routed_experts_published"], model["num_experts_per_tok"]
    scores, chosen, weights = route(x, p, jax.lax.stop_gradient(bias), model)
    w1g, w1u = p["experts"]["fc1"]["gate_experts_w1"], p["experts"]["fc1"]["up_experts_w1"]
    w2 = p["experts"]["fc2"]["experts_w2"]
    y = mlp(x, p["shared"])
    for held in range(model["n_routed_experts"]):
        expert = model["expert_offset"] + held
        weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)  # [S]
        y = y + weight[:, None] * swiglu(x, w1g[held], w1u[held], w2[held])
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1)).astype(jnp.float32)
    share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    balance = jnp.sum(counts * (experts / (k * x.shape[0])) * share)
    return y, counts, balance


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _layer(h, p, bias, model_items):
    model = dict(model_items)
    eps = model["rms_norm_eps"]
    h = h + latent_attention(rms_norm(h, p["attn_norm"], eps), p["LatentSelfAttentionBlock_0"], model)
    x = rms_norm(h, p["ffn_norm"], eps)
    if "moe" not in p:
        return h + mlp(x, p["GatedFFBlock_0"]), None, None
    y, counts, balance = expert_layer(x, p["moe"], bias, model)
    return h + y, counts, balance


def layer(h, p, bias, model: dict):
    return _layer(h, p, bias, _static(model))


def _static(model: dict) -> tuple:
    """The sizes the equations read, hashable for ``jax.checkpoint``."""
    keys = (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "rope_theta", "rms_norm_eps", "n_routed_experts", "n_routed_experts_published",
        "expert_offset", "num_experts_per_tok", "routed_scaling_factor",
    )
    return tuple((key, model[key]) for key in keys)


@jax.checkpoint
def head_cross_entropy(w_head, h, targets):
    logits = h @ w_head
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def sequence_terms(params, bias, tokens, model: dict):
    """One sequence of ``S + 1`` ids -> ``(ce [S], ce_mtp [S - 1], balance
    summed over the routed layers, counts [R, E])``; ``bias`` is ``[R, E]``,
    a row a routed layer and the module's last."""
    eps, depth = model["rms_norm_eps"], model["num_layers"]
    inputs, targets = tokens[:-1], tokens[1:]
    table = params["embed"]["embedding"]
    h, row = table[inputs], 0
    counts, balance = [], 0.0
    for i in range(depth):
        routed = "moe" in params[f"layer_{i}"]
        h, c, b = layer(h, params[f"layer_{i}"], bias[row] if routed else None, model)
        if routed:
            counts.append(c)
            balance, row = balance + b, row + 1
    w_head = params["lm_head"]["kernel"]
    ce = head_cross_entropy(w_head, rms_norm(h, params["final_norm"], eps), targets)
    # The module: position i reads h_i (before the final norm) and the
    # embedding of t_{i+1}, and is scored on t_{i+2}; the last position of
    # the S has no t_{i+2} and is left out.
    mtp = params["mtp"]
    both = jnp.concatenate(
        [rms_norm(h, mtp["h_norm"], eps), rms_norm(table[targets], mtp["e_norm"], eps)],
        axis=-1,
    )
    x, c, b = layer(both @ mtp["eh_proj"]["kernel"], mtp["layer"], bias[row], model)
    counts.append(c)
    ce_mtp = head_cross_entropy(w_head, rms_norm(x, mtp["final_norm"], eps)[:-1], targets[1:])
    return ce, ce_mtp, balance + b, jnp.stack(counts)


def sequence_loss(params, bias, tokens, model: dict, sequences: int):
    """This sequence's part of the batch's loss (the parts add up to it)."""
    recipe = model["recipe"]
    ce, ce_mtp, balance, counts = sequence_terms(params, bias, tokens, model)
    loss = (
        jnp.mean(ce) + recipe["mtp_lambda"] * jnp.mean(ce_mtp) + recipe["balance_alpha"] * balance
    ) / sequences
    return loss, counts


def stepped_bias(bias, counts, gamma: float):
    """``b_e += gamma sign(mean(c) - c_e)`` on the step's counts ``[R, E]``."""
    return bias + gamma * jnp.sign(jnp.mean(counts, axis=-1, keepdims=True) - counts)


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


def initial_bias(model: dict):
    routed = model["num_layers"] - model["first_k_dense_replace"] + model["num_nextn_predict_layers"]
    return jnp.zeros((routed, model["n_routed_experts_published"]), jnp.float32)


def check_layout(params, config: dict) -> None:
    """The parameter tree has the layout this file reads and the sizes the
    configuration's file states: the cut's layers, the experts held."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, vdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    held, width = config["n_routed_experts"], config["moe_intermediate_size"]
    attn, moe = params["layer_1"]["LatentSelfAttentionBlock_0"], params["layer_1"]["moe"]
    found = {
        "embedding": tuple(params["embed"]["embedding"].shape),
        "q_a": tuple(attn["to_qkv"]["q_a"]["kernel"].shape),
        "q_b": tuple(attn["to_qkv"]["q_b"]["kernel"].shape),
        "kv_a": tuple(attn["to_qkv"]["kv_a"]["kernel"].shape),
        "kv_b": tuple(attn["to_qkv"]["kv_b"]["kernel"].shape),
        "to_out": tuple(attn["to_out"]["kernel"].shape),
        "dense_gate": tuple(params["layer_0"]["GatedFFBlock_0"]["fc1"]["gate"]["kernel"].shape),
        "router": tuple(moe["route"]["kernel"].shape),
        "routed_gate": tuple(moe["experts"]["fc1"]["gate_experts_w1"].shape),
        "routed_down": tuple(moe["experts"]["fc2"]["experts_w2"].shape),
        "shared_gate": tuple(moe["shared"]["fc1"]["gate"]["kernel"].shape),
        "eh_proj": tuple(params["mtp"]["eh_proj"]["kernel"].shape),
        "head": tuple(params["lm_head"]["kernel"].shape),
        "layers": sorted(k for k in params if k.startswith("layer_")),
        "routed_layers": sorted(k for k in params if k.startswith("layer_") and "moe" in params[k]),
    }
    stated = {
        "embedding": (config["vocab_size"], d),
        "q_a": (d, config["q_lora_rank"]),
        "q_b": (config["q_lora_rank"], heads * (nope + rope)),
        "kv_a": (d, config["kv_lora_rank"] + rope),
        "kv_b": (config["kv_lora_rank"], heads * (nope + vdim)),
        "to_out": (heads, vdim, d),
        "dense_gate": (d, config["intermediate_size"]),
        "router": (d, config["n_routed_experts_published"]),
        "routed_gate": (held, d, width),
        "routed_down": (held, width, d),
        "shared_gate": (d, width * config["n_shared_experts"]),
        "eh_proj": (2 * d, d),
        "head": (d, config["vocab_size"]),
        "layers": sorted(f"layer_{i}" for i in range(config["num_layers"])),
        "routed_layers": sorted(
            f"layer_{i}" for i in range(config["first_k_dense_replace"], config["num_layers"])
        ),
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
    optimizer's moments get it (after the clip), the parameters' change
    after the last update (the last two as lists of host arrays in the
    tree's order) and the selection bias after the last update."""
    loss_and_grad = make_loss_and_grad(model)
    update = jax.jit(
        functools.partial(
            adamw_update, weight_decay=hp["weight_decay"], clip_grad_norm=hp["clip_grad_norm"]
        ),
        donate_argnums=(1, 2, 3),
    )
    start, bias = params, initial_bias(model)
    mu = nu = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, first_grad = [], None
    for count, tokens in enumerate(batches):
        loss, grads, counts = loss_and_grad(params, bias, tokens)
        bias = stepped_bias(bias, counts, model["recipe"]["bias_update_rate"])
        params, mu, nu, clipped = update(
            params, grads, mu, nu, jnp.float32(learning_rate(count, hp)), jnp.float32(count + 1)
        )
        losses.append(float(loss))
        if first_grad is None:
            first_grad = [np.asarray(g) for g in jax.tree.leaves(clipped)]
        del grads, clipped
        mu, nu = jax.device_get((mu, nu))
    change = [np.asarray(a - b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(start))]
    return {"losses": losses, "first_grad": first_grad, "change": change, "select_bias": np.asarray(bias)}
