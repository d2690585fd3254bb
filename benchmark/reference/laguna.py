"""Plain float32 reference for the Laguna cell: forward, loss, gradient and
AdamW.

Written from the catalog row of the model's public ``config.json``
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json,
``model_type`` ``laguna``) and the equations ISSUE 46 wrote down from its
keys, in plain ``jax.numpy``, float32, traced under
``jax.default_matmul_precision("highest")``. No kernel, no block skipped, no
sort, no gather of routed rows. It imports nothing of the program and is
handed nothing the program made: the benchmark draws the weights
(``benchmark/weights.py``) and the token batches from the seed and gives the
same arrays to both sides. The optimizer is ``reference/vit.py``'s AdamW.

Model. ``N(x) = x / rms(x) w`` (a plain weight, eps ``rms_norm_eps``); ``h =
E[tokens]``; for each layer ``h += Attn_l(N(h)); h += FFN_l(N(h))``; ``logits =
N(h) W_head`` (untied). No bias anywhere.

- Attention, by ``layer_types[l]`` and ``num_attention_heads_per_layer[l]``:
  ``H_l`` query heads (48 in a ``full_attention`` layer, 72 in a
  ``sliding_attention`` one) on ``num_key_value_heads`` key/value heads of
  ``head_dim``; query head ``h`` reads key/value head ``h // (H_l / H_kv)``. q
  and k pass a per-head ``N`` over the head's lanes (ASSUMED: the config's key
  names are Qwen3-MoE's, whose blocks norm q and k a head). Rotary, lane ``i``
  paired with ``i + lanes / 2``, by ``rope_parameters[layer type]``: a window
  layer turns the whole head at ``rope_theta`` (``rope_type`` default,
  ``partial_rotary_factor`` 1); a full layer turns its first ``head_dim
  partial_rotary_factor`` lanes at YaRN's blended frequencies, THE RAMP
  WRITTEN OUT BELOW, with cos and sin times ``attention_factor`` (the lanes
  that pass are not scaled). ``out = softmax(q k^T head_dim^-0.5 + mask) v``,
  the mask an iota comparison, ``j <= i`` and in a window layer also ``j > i -
  sliding_window``, a block of queries at a time. Per-head gate (``gating``
  ``per-head``): ``y = W_o concat_h(sigmoid(x W_g)_h out_h)``, ``W_g [D,
  H_l]``.
- FFN, by ``mlp_layer_types[l]``: ``dense`` is SwiGLU at ``intermediate_size``
  (SiLU ASSUMED: ``hidden_act`` is not given). ``sparse``: ``p = softmax(x
  W_r)`` over all published experts (ASSUMED: the activation is not a key), the
  ``k`` largest, ``w = moe_routed_scaling_factor p_sel / sum p_sel``; ``y =
  sum_{selected and held} w_e E_e(x) + sigmoid(x w_s) E_shared(x)`` (the scalar
  gate ``w_s`` ASSUMED, as in the families that spell the key
  ``shared_expert_intermediate_size``): a loop over the experts HELD
  (``expert_offset``, ``num_experts`` of ``num_experts_published``), each run
  on every token and weighted by a vector that is zero where the token did not
  select it. What the absent experts would add is left out, as in the program.
- Balance term, a sequence: ``sum_e f_e P_e``, ``f_e = E / (k S) c_e``, ``P_e``
  the mean over the sequence of ``p_e``, weighted by ``recipe.balance_alpha``
  (ASSUMED).

Loss: ``mean CE + alpha sum_layers balance``.

Memory. A batch goes through one sequence at a time; around each layer
application and the head stands a ``jax.checkpoint``, and the dense logits
live a block of ``QUERY_BLOCK`` queries at a time (ISSUE 46 says 1,024; at 72
heads a block's ``[H, block, S]`` logits are 1.2 GB there and three of them
beside 9.7 GB of parameters and gradients do not fit: 512). None of that
changes the arithmetic. Adam's moments wait on the host between the steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.vit import adamw_update, learning_rate
from benchmark.reference.xing import _trim_heap as trim_heap

QUERY_BLOCK = 512  # rows of the dense logits alive at a time


def norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def yarn_frequencies(lanes: int, rope: dict):
    """``[lanes / 2]`` angular frequencies under YaRN (arXiv:2309.00071), by
    hand: pair ``i`` turns ``original base^(-2i / lanes) / 2 pi`` times over
    the original context; a pair that turns ``beta_fast`` times or more keeps
    its frequency, one that turns ``beta_slow`` times or fewer takes it over
    ``factor``, and between the two pair indices (floor of the first, ceiling
    of the second) the two are blended linearly in the index."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    original, half = rope["original_max_position_embeddings"], lanes // 2
    plain = base ** (-jnp.arange(half, dtype=jnp.float32) / half)

    def pair_index(rotations: float) -> float:
        return lanes * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_index(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_index(rope["beta_slow"])), lanes - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotate(x, head_dim: int, rope: dict):
    """Rotary on the first ``head_dim partial_rotary_factor`` lanes of ``x [S,
    H, D]``, lane ``i`` paired with ``i + lanes / 2``; the other lanes pass
    unscaled. ``rope`` is the layer type's group of ``rope_parameters``."""
    lanes = int(head_dim * rope["partial_rotary_factor"])
    half, amplitude = lanes // 2, 1.0
    if rope["rope_type"] == "yarn":
        freq, amplitude = yarn_frequencies(lanes, rope), rope["attention_factor"]  # the factor on the tables
    else:
        freq = float(rope["rope_theta"]) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :])[:, None, :]  # [S, 1, half]
    cos, sin = amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)
    first, second = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin, x[..., lanes:]], axis=-1)


def attention(x, p, kind: str, heads: int, model: dict):
    """One sequence ``[S, D]`` through the gated grouped-query attention of a
    ``kind`` (``full_attention`` | ``sliding_attention``) layer of ``heads``
    query heads."""
    kv_heads, dim, eps = model["num_key_value_heads"], model["head_dim"], model["rms_norm_eps"]
    rope = model["rope_parameters"][kind]
    window = model["sliding_window"] if kind == "sliding_attention" else None
    qkv, seq = p["to_qkv"], x.shape[0]
    q = (x @ qkv["q"]["kernel"]).reshape(seq, heads, dim)
    k = (x @ qkv["k"]["kernel"]).reshape(seq, kv_heads, dim)
    v = (x @ qkv["v"]["kernel"]).reshape(seq, kv_heads, dim)
    q = rotate(norm(q, qkv["q_norm"], eps), dim, rope)  # ASSUMED: the per-head norms
    k = rotate(norm(k, qkv["k_norm"], eps), dim, rope)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))  # query head h reads head h // group

    block = math.gcd(seq, QUERY_BLOCK)

    @jax.checkpoint  # the backward holds one block's [H, block, S] logits, not the sequence's
    def attend(operands):
        q_rows, rows = operands
        scores = jnp.einsum("qhe,khe->hqk", q_rows, k) * dim ** -0.5
        cols = jnp.arange(seq)[None, :]
        visible = cols <= rows[:, None]
        if window is not None:  # itself and the window - 1 before it
            visible = visible & (cols > rows[:, None] - window)
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    # One block of queries after another (a loop, so that no two blocks' logits are alive at once).
    out = jax.lax.map(attend, (q.reshape(seq // block, block, heads, dim), jnp.arange(seq).reshape(-1, block)))
    out = out.reshape(seq, heads, dim) * jax.nn.sigmoid(x @ qkv["gate"]["kernel"])[:, :, None]  # a gate a head
    return jnp.einsum("qhe,hed->qd", out, p["to_out"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down  # ASSUMED: SiLU


def mlp(x, p):
    return swiglu(x, p["fc1"]["gate"]["kernel"], p["fc1"]["up"]["kernel"], p["fc2"]["kernel"])


def route(x, p, model: dict):
    """``(p [S, E], chosen [S, k], weights [S, k])``: the softmax over all the
    published experts (ASSUMED), its ``k`` largest, and those over their sum
    times ``moe_routed_scaling_factor``."""
    logits = x @ p["route"]["kernel"]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1, keepdims=True)
    _, chosen = jax.lax.top_k(probs, model["num_experts_per_tok"])
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    return probs, chosen, model["moe_routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_part(x, p, chosen, weights, held: int, offset: int):
    """``sum_{selected and held} w_e E_e(x)`` for the ``held`` experts from
    ``offset``: each on every token, weighted by zero where not selected (the
    weight multiplies the expert's output: ``moe_apply_router_weight_on_input``
    false)."""
    w1g, w1u = p["experts"]["fc1"]["gate_experts_w1"], p["experts"]["fc1"]["up_experts_w1"]
    w2 = p["experts"]["fc2"]["experts_w2"]
    y = jnp.zeros_like(x)
    for local in range(held):
        weight = jnp.sum(jnp.where(chosen == offset + local, weights, 0.0), axis=-1)  # [S]
        y = y + weight[:, None] * swiglu(x, w1g[local], w1u[local], w2[local])
    return y


def shared_part(x, p):
    return jax.nn.sigmoid(x @ p["shared_gate"]["kernel"]) * mlp(x, p["shared"])  # ASSUMED: the scalar gate


def expert_layer(x, p, model: dict):
    """One sequence ``[S, D]`` -> ``(y, counts [E], balance)``."""
    experts, k = model["num_experts_published"], model["num_experts_per_tok"]
    probs, chosen, weights = route(x, p, model)
    y = shared_part(x, p) + routed_part(x, p, chosen, weights, model["num_experts"], model["expert_offset"])
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts), axis=(0, 1)).astype(jnp.float32)
    balance = jnp.sum(counts * (experts / (k * x.shape[0])) * jnp.mean(probs, axis=0))
    return y, counts, balance


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5))
def _layer(h, p, kind, heads, sparse, model_items):
    model = _unfrozen(model_items)
    eps = model["rms_norm_eps"]
    h = h + attention(norm(h, p["attn_norm"], eps), p["GatedSelfAttentionBlock_0"], kind, heads, model)
    x = norm(h, p["ffn_norm"], eps)
    if not sparse:
        return h + mlp(x, p["GatedFFBlock_0"]), None, 0.0
    y, counts, balance = expert_layer(x, p["moe"], model)
    return h + y, counts, balance


def layer(h, p, layer_index: int, model: dict):
    """Published layer ``layer_index``: its kind of attention, its number of
    query heads and its kind of FFN are the config's own per-layer lists'."""
    return _layer(
        h, p, model["layer_types"][layer_index], model["num_attention_heads_per_layer"][layer_index],
        model["mlp_layer_types"][layer_index] == "sparse", _static(model),
    )


def _static(model: dict) -> tuple:
    """The sizes the equations read, hashable for ``jax.checkpoint``."""
    keys = (
        "num_key_value_heads", "head_dim", "rms_norm_eps", "sliding_window", "num_experts",
        "num_experts_published", "expert_offset", "num_experts_per_tok", "moe_routed_scaling_factor",
    )
    rope = tuple((kind, tuple(sorted(group.items()))) for kind, group in sorted(model["rope_parameters"].items()))
    return tuple((key, model[key]) for key in keys) + (("rope_parameters", rope),)


def _unfrozen(model_items: tuple) -> dict:
    model = dict(model_items)
    model["rope_parameters"] = {kind: dict(group) for kind, group in model["rope_parameters"]}
    return model


@jax.checkpoint
def head_cross_entropy(w_head, h, targets):
    logits = h @ w_head
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def sparse_layers(model: dict) -> list:
    return [i for i in range(model["num_layers"]) if model["mlp_layer_types"][i] == "sparse"]


def hidden_states(params, inputs, model: dict):
    """``(the stack's output before the final norm, balance summed over the
    expert layers, counts [expert layers, E])`` of one sequence of ids ``[S]``."""
    h = params["embed"]["embedding"][inputs]
    counts, balance = [], 0.0
    for i in range(model["num_layers"]):
        h, c, b = layer(h, params[f"layer_{i}"], i, model)
        if c is not None:
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
    """``fn(params, tokens [B, S + 1]) -> (loss, gradient, counts [expert
    layers, E])``, one sequence at a time."""

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
        acc_counts = jnp.zeros((len(sparse_layers(model)), model["num_experts_published"]), jnp.float32)
        for row in tokens:
            acc_loss, acc_grad, acc_counts = block(params, acc_loss, acc_grad, acc_counts, row, tokens.shape[0])
        return acc_loss, acc_grad, acc_counts

    return loss_and_grad


def check_layout(params, config: dict) -> None:
    """The parameter tree has the layout this file reads and the sizes the
    configuration's file states: the cut's layers with their kinds and head
    counts, the experts held."""
    d, kv_heads, dim = config["hidden_size"], config["num_key_value_heads"], config["head_dim"]
    held, width, depth = config["num_experts"], config["moe_intermediate_size"], config["num_layers"]
    sparse = sparse_layers(config)
    dense = [i for i in range(depth) if i not in sparse]
    block = lambda i: params[f"layer_{i}"]["GatedSelfAttentionBlock_0"]
    moe, ffn = params[f"layer_{sparse[0]}"]["moe"], params[f"layer_{dense[0]}"]["GatedFFBlock_0"]
    found = {
        "embedding": tuple(params["embed"]["embedding"].shape),
        "q": [tuple(block(i)["to_qkv"]["q"]["kernel"].shape) for i in range(depth)],
        "k": [tuple(block(i)["to_qkv"]["k"]["kernel"].shape) for i in range(depth)],
        "v": [tuple(block(i)["to_qkv"]["v"]["kernel"].shape) for i in range(depth)],
        "gate": [tuple(block(i)["to_qkv"]["gate"]["kernel"].shape) for i in range(depth)],
        "q_norm": [tuple(block(i)["to_qkv"]["q_norm"]["scale"].shape) for i in range(depth)],
        "k_norm": [tuple(block(i)["to_qkv"]["k_norm"]["scale"].shape) for i in range(depth)],
        "attn_out": [tuple(block(i)["to_out"]["kernel"].shape) for i in range(depth)],
        "dense_gate_up": tuple(ffn["fc1"]["gate"]["kernel"].shape),
        "router": tuple(moe["route"]["kernel"].shape),
        "routed_gate": tuple(moe["experts"]["fc1"]["gate_experts_w1"].shape),
        "routed_down": tuple(moe["experts"]["fc2"]["experts_w2"].shape),
        "shared_gate_up": tuple(moe["shared"]["fc1"]["gate"]["kernel"].shape),
        "shared_gate": tuple(moe["shared_gate"]["kernel"].shape),
        "head": tuple(params["lm_head"]["kernel"].shape),
        "layers": sorted(k for k in params if k.startswith("layer_")),
        "routed_layers": sorted(k for k in params if k.startswith("layer_") and "moe" in params[k]),
    }
    heads = config["num_attention_heads_per_layer"][:depth]
    stated = {
        "embedding": (config["vocab_size"], d),
        "q": [(d, h * dim) for h in heads],
        "k": [(d, kv_heads * dim)] * depth,
        "v": [(d, kv_heads * dim)] * depth,
        "gate": [(d, h) for h in heads],
        "q_norm": [(dim,)] * depth,
        "k_norm": [(dim,)] * depth,
        "attn_out": [(h, dim, d) for h in heads],
        "dense_gate_up": (d, config["intermediate_size"]),
        "router": (d, config["num_experts_published"]),
        "routed_gate": (held, d, width),
        "routed_down": (held, width, d),
        "shared_gate_up": (d, config["shared_expert_intermediate_size"]),
        "shared_gate": (d, 1),
        "head": (d, config["vocab_size"]),
        "layers": sorted(f"layer_{i}" for i in range(depth)),
        "routed_layers": sorted(f"layer_{i}" for i in sparse),
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


def follow_steps(params, batches, hp: dict, model: dict):
    """Follow the first ``len(batches)`` updates from ``params``. CONSUMES
    ``params``: the first update donates them.

    ``batches`` is a list of token arrays ``[B, S + 1]``; ``hp`` holds the
    recipe's numbers under the names of ``reference/vit.py``'s
    ``learning_rate``; ``model`` the configuration's file (sizes and
    ``recipe``). Returns each step's loss, the first gradient as the
    optimizer's moments get it (after the clip), the parameters' change after
    the last update (the last two as lists of host arrays in the tree's
    order) and each step's routing counts ``[expert layers, E]``. Memory as
    ``reference/ling.py::follow_steps`` (12.98 GB of state here): the update
    donates all four trees, the seeded parameters and the moments wait on the
    host, the zero moments are made on the device, and the change is
    subtracted on the device a leaf at a time."""
    loss_and_grad = make_loss_and_grad(model)
    update = jax.jit(
        functools.partial(
            adamw_update, weight_decay=hp["weight_decay"], clip_grad_norm=hp["clip_grad_norm"]
        ),
        donate_argnums=(0, 1, 2, 3),
    )
    trim_heap()
    start = [np.asarray(leaf) for leaf in jax.tree.leaves(params)]
    mu = nu = None
    losses, step_counts, first_grad, last = [], [], None, len(batches) - 1
    for count, tokens in enumerate(batches):
        loss, grads, counts = loss_and_grad(params, tokens)
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
    return {"losses": losses, "first_grad": first_grad, "change": change, "counts": step_counts}
