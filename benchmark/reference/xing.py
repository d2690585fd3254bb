"""Plain float32 reference for the Xing4.0-29B-A4B cell: forward, loss,
gradient, AdamW and the selection-bias update.

Written from the model's public ``config.json`` (https://huggingface.co/
XingChen-AGI/Xing4.0-29B-A4B) and the papers whose methods its keys name:
manifold-constrained hyper-connections (arXiv:2512.24880 section 4 on
arXiv:2409.19606), latent attention, the expert layer and multi-token
prediction (arXiv:2412.19437 sections 2.1.1, 2.1.2, 2.2), YaRN
(arXiv:2309.00071). Plain ``jax.numpy``, float32, traced under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no gather of
routed rows; Sinkhorn-Knopp is its iterations written out. It imports nothing
of the program and is handed nothing the program made: the benchmark draws the
weights and the token batches from the seed and gives the same arrays to both
sides. What is the same mathematics as ``reference/joyai.py`` is imported from
it (RMSNorm, SwiGLU, ``route``, the expert layer's loop over the experts held
with its balance loss, the blocked head, ``stepped_bias``); the optimizer is
``reference/vit.py``'s AdamW.

Model. A token's residual state is ``X`` in ``R^{n x d}`` (``n`` =
``hc_mult``), here ``[n, S, d]`` a sequence. ``X^0`` is the embedding copied
to the ``n`` streams. A layer is two sublayers, latent attention and then the
FFN (SwiGLU in the first ``first_k_dense_replace`` layers, the expert layer
after them), each through its own hyper-connection (leaves ``kernel`` = Phi
``[n d, 2n + n^2]``, ``scale`` = the three gates alpha, ``bias`` = b)::

    xhat  = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)    # stream-major, one RMS over n d
    m     = xhat Phi = [m_pre (n) | m_post (n) | m_res (n^2)]
    h_pre = sigmoid(alpha_pre m_pre + b_pre);  h_post = 2 sigmoid(alpha_post m_post + b_post)
    Ht    = clamp(alpha_res mat(m_res) + b_res, clamp_min, clamp_max)
    M = exp(Ht); hc_sinkhorn_iters times: M = M / (colsum(M) + hc_eps); M = M / (rowsum(M) + hc_eps)
    u  = sum_i h_pre[i] X_i;   y = F(RMSNorm(u))
    X'_i = sum_j M[i, j] X_j + h_post[i] y

The final norm reads ``sum_i X_i``; the untied head follows.

- Latent attention: ``reference/joyai.py``'s equations with YaRN on the
  rotary part: pair ``i`` of the ``rope`` lanes turns at ``theta ** (-2i /
  rope)``, kept where that is ``beta_fast`` or more turns over
  ``original_max_position_embeddings``, divided by ``factor`` where it is
  ``beta_slow`` or fewer, blended linearly in ``i`` between the two pair
  indices (floor and ceiling); cos and sin times ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)``; the softmax scale ``(nope + rope) ** -0.5``
  times ``mscale(factor, mscale_all_dim) ** 2``, with ``mscale(f, m) = 0.1 m
  ln f + 1``. Causal, the explicit mask, a block of queries at a time.
- The expert layer, the selection bias ``b`` and its update, the balance loss:
  ``reference/joyai.py``'s (a loop over the experts HELD; what the absent
  experts would add is left out, as in the program).
- Multi-token prediction, where ``num_nextn_predict_layers`` is 1: ``h'_i =
  W_eh [RMSNorm(h_i) ; RMSNorm(E[t_{i+1}])]`` with ``h_i = sum of the main
  stack's streams`` (before the final norm), copied to ``n`` streams, one
  expert layer of its own, the streams' sum, its own final norm, the model's
  head; scored on ``t_{i+2}``. At 0 there is no module and no term.

Loss: ``mean CE_main [+ lambda mean CE_mtp] + alpha sum_layers mean_seq
balance``.

What the config and the papers leave open is set as the program sets it and
listed under ``assumed`` in ``benchmark/configs/xing4_29b_a4b.json``.
Departure from the published recipe: Adam's second-moment decay is the
program's 0.999.

Memory (12.15 GB of state at the cell's cut, against a chip of 17.18e9 bytes).
A batch goes through one sequence at a time; around each layer application,
each block of queries and each head stands a ``jax.checkpoint``, which changes
no arithmetic. The seeded parameters (for the change after the last update)
and Adam's moments wait on the host between the steps, and the update donates
all four trees, the parameters it was handed too: ``follow_steps`` consumes
its ``params``. The host counts too (a one-chip machine has 40 GiB and the
driver holds the program's first gradient and change meanwhile): the zero
moments are made on the device, the last step's moments are never fetched,
and the change is subtracted on the device a leaf at a time, so the host
holds at most four trees of this file's (12 GB). Those arrays are each a
mapping of their own and cannot reuse the pages the C heap has freed and kept
(5 GiB after the step's compilation; measured on the host of a described
v5e), so ``follow_steps`` hands such pages back to the system on entry and
after its first update (:func:`_trim_heap`).
"""

from __future__ import annotations

import ctypes
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai import (
    expert_layer,
    head_cross_entropy,
    initial_bias,
    mlp,
    rms_norm,
    stepped_bias,
)
from benchmark.reference.vit import adamw_update, learning_rate

QUERY_BLOCK = 1024  # rows of the dense causal logits alive at a time


# ------------------------------------------------------------------ rotary


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(rope: int, theta: float, scaling: dict):
    """``[rope / 2]`` angular frequencies of the rotary pairs under YaRN."""
    original = scaling["original_max_position_embeddings"]

    def pair_index(turns: float) -> float:
        # the pair that makes ``turns`` turns over the original context
        return rope * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_index(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_index(scaling["beta_slow"])), rope - 1)
    pair = jnp.arange(rope // 2, dtype=jnp.float32)
    blend = jnp.clip((pair - low) / max(high - low, 0.001), 0.0, 1.0)  # 0: kept, 1: divided by factor
    plain = theta ** (-2.0 * pair / rope)
    return plain * (1.0 - blend) + plain / scaling["factor"] * blend


def rotate_pairs(x, theta: float, scaling: dict):
    """Rotary position embedding on ``[S, ..., R]``: lane ``2i`` is paired
    with lane ``2i + 1``, at :func:`yarn_frequencies`."""
    s, r = x.shape[0], x.shape[-1]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_frequencies(r, theta, scaling)[None, :]
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    amplitude = yarn_mscale(scaling["factor"], scaling["mscale"]) / yarn_mscale(
        scaling["factor"], scaling["mscale_all_dim"]
    )
    cos, sin = amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


# --------------------------------------------------------------- attention


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _attend(q_rows, k, v, rows, scale):
    """One block of queries against every key: the dense logits, the causal
    mask, softmax, the weighted sum."""
    scores = jnp.einsum("qhe,khe->hqk", q_rows, k) * scale
    visible = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khe->qhe", probs, v)


def latent_attention(x, p, model: dict):
    """Causal multi-head latent self-attention on one sequence ``[S, D]``."""
    heads, nope = model["num_attention_heads"], model["qk_nope_head_dim"]
    rope, vdim, rank = model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"]
    theta, eps, scaling = float(model["rope_theta"]), model["rms_norm_eps"], model["rope_scaling"]
    qkv, s = p["to_qkv"], x.shape[0]
    c_q = rms_norm(x @ qkv["q_a"]["kernel"], qkv["q_norm"], eps)
    q = (c_q @ qkv["q_b"]["kernel"]).reshape(s, heads, nope + rope)
    kv = x @ qkv["kv_a"]["kernel"]
    c_kv, k_rope = rms_norm(kv[:, :rank], qkv["kv_norm"], eps), kv[:, rank:]
    kv = (c_kv @ qkv["kv_b"]["kernel"]).reshape(s, heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta, scaling)], axis=-1)
    k_rope = rotate_pairs(k_rope, theta, scaling)  # [S, rope]: one head, shared
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, None, :], (s, heads, rope))], axis=-1)
    scale = (nope + rope) ** -0.5 * yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = jnp.arange(start, min(start + QUERY_BLOCK, s))
        out.append(_attend(q[rows], k, v, rows, scale))
    return jnp.einsum("qhe,hed->qd", jnp.concatenate(out), p["to_out"]["kernel"])


# ------------------------------------------------------- hyper-connections


def sinkhorn_knopp(logits, iters: int, eps: float):
    """``[S, n, n]`` -> doubly stochastic: ``exp``, then ``iters`` times the
    columns and then the rows divided by their sums plus ``eps``."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)  # a column's sum runs over the rows
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    return m


def connection_maps(streams, p, model: dict):
    """``[n, S, d]`` -> ``(h_pre [S, n], h_post [S, n], H_res [S, n, n])``."""
    n, s, d = streams.shape
    flat = jnp.moveaxis(streams, 0, 1).reshape(s, n * d)  # vec(X), stream-major
    xhat = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + model["rms_norm_eps"])
    m = xhat @ p["kernel"]
    alpha, b = p["scale"], p["bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    logits = jnp.clip(
        alpha[2] * m[:, 2 * n:] + b[2 * n:], model["mhc_h_res_clamp_min"], model["mhc_h_res_clamp_max"]
    ).reshape(s, n, n)
    return h_pre, h_post, sinkhorn_knopp(logits, model["hc_sinkhorn_iters"], model["hc_eps"])


def connected(streams, p, sublayer, model: dict):
    """One sublayer through its hyper-connection: ``(X', what the sublayer
    returned beside y)``; ``sublayer(u) -> (y, extras)``."""
    h_pre, h_post, h_res = connection_maps(streams, p, model)
    u = jnp.einsum("si,isd->sd", h_pre, streams)
    y, extras = sublayer(u)
    mixed = jnp.einsum("sij,jsd->isd", h_res, streams)
    return mixed + h_post.T[:, :, None] * y[None, :, :], extras


# ------------------------------------------------------------------- layers


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _layer(streams, p, bias, model_items):
    model = _model_of(model_items)
    eps = model["rms_norm_eps"]
    streams, _ = connected(
        streams, p["hc_attn"],
        lambda u: (latent_attention(rms_norm(u, p["attn_norm"], eps), p["LatentSelfAttentionBlock_0"], model), None),
        model,
    )

    def ffn(u):
        x = rms_norm(u, p["ffn_norm"], eps)
        if "moe" not in p:
            return mlp(x, p["GatedFFBlock_0"]), (None, None)
        y, counts, balance = expert_layer(x, p["moe"], bias, model)
        return y, (counts, balance)

    streams, (counts, balance) = connected(streams, p["hc_ffn"], ffn, model)
    return streams, counts, balance


def layer(streams, p, bias, model: dict):
    """``[n, S, d]`` -> ``(X', counts [E], balance)``, the last two ``None``
    for a dense layer."""
    return _layer(streams, p, bias, _static(model))


_SIZES = (
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "rope_theta", "rms_norm_eps", "n_routed_experts", "n_routed_experts_published",
    "expert_offset", "num_experts_per_tok", "routed_scaling_factor",
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
)


def _static(model: dict) -> tuple:
    """The sizes the equations read, hashable for ``jax.checkpoint``."""
    return tuple((key, model[key]) for key in _SIZES) + (
        ("rope_scaling", tuple(sorted(model["rope_scaling"].items()))),
    )


def _model_of(model_items: tuple) -> dict:
    model = dict(model_items)
    model["rope_scaling"] = dict(model["rope_scaling"])
    return model


def fan_out(h, model: dict):
    return jnp.broadcast_to(h[None], (model["hc_mult"],) + h.shape)


def sequence_terms(params, bias, tokens, model: dict):
    """One sequence of ``S + 1`` ids -> ``(ce [S], ce_mtp [S - 1] or None,
    balance summed over the routed layers, counts [R, E])``; ``bias`` is
    ``[R, E]``, a row a routed layer and the module's, if there is one, last."""
    eps, depth = model["rms_norm_eps"], model["num_layers"]
    inputs, targets = tokens[:-1], tokens[1:]
    table = params["embed"]["embedding"]
    streams, row = fan_out(table[inputs], model), 0
    counts, balance = [], 0.0
    for i in range(depth):
        routed = "moe" in params[f"layer_{i}"]
        streams, c, b = layer(streams, params[f"layer_{i}"], bias[row] if routed else None, model)
        if routed:
            counts.append(c)
            balance, row = balance + b, row + 1
    h = jnp.sum(streams, axis=0)
    w_head = params["lm_head"]["kernel"]
    ce = head_cross_entropy(w_head, rms_norm(h, params["final_norm"], eps), targets)
    if not model["num_nextn_predict_layers"]:
        return ce, None, balance, jnp.stack(counts)
    # The module: position i reads h_i (the streams' sum, before the final
    # norm) and the embedding of t_{i+1}, and is scored on t_{i+2}; the last
    # position of the S has no t_{i+2} and is left out.
    mtp = params["mtp"]
    both = jnp.concatenate(
        [rms_norm(h, mtp["h_norm"], eps), rms_norm(table[targets], mtp["e_norm"], eps)],
        axis=-1,
    )
    streams, c, b = layer(fan_out(both @ mtp["eh_proj"]["kernel"], model), mtp["layer"], bias[row], model)
    counts.append(c)
    x = rms_norm(jnp.sum(streams, axis=0), mtp["final_norm"], eps)
    ce_mtp = head_cross_entropy(w_head, x[:-1], targets[1:])
    return ce, ce_mtp, balance + b, jnp.stack(counts)


def sequence_loss(params, bias, tokens, model: dict, sequences: int):
    """This sequence's part of the batch's loss (the parts add up to it)."""
    recipe = model["recipe"]
    ce, ce_mtp, balance, counts = sequence_terms(params, bias, tokens, model)
    loss = jnp.mean(ce) + recipe["balance_alpha"] * balance
    if ce_mtp is not None:
        loss = loss + recipe["mtp_lambda"] * jnp.mean(ce_mtp)
    return loss / sequences, counts


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
    configuration's file states: the cut's layers, the experts held, the
    hyper-connections' leaves, the module or none."""
    d, heads, n = config["hidden_size"], config["num_attention_heads"], config["hc_mult"]
    nope, rope, vdim = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    held, width = config["n_routed_experts"], config["moe_intermediate_size"]
    first = config["first_k_dense_replace"]
    routed = params[f"layer_{first}"]
    attn, moe = routed["LatentSelfAttentionBlock_0"], routed["moe"]
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
        "hc": sorted((k, tuple(v.shape)) for k, v in routed["hc_ffn"].items()),
        "hc_sublayers": sorted(k for k in routed if k.startswith("hc_")),
        "mtp": sorted(params["mtp"]) if "mtp" in params else None,
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
        "hc": [("bias", (2 * n + n * n,)), ("kernel", (n * d, 2 * n + n * n)), ("scale", (3,))],
        "hc_sublayers": ["hc_attn", "hc_ffn"],
        "mtp": (
            ["e_norm", "eh_proj", "final_norm", "h_norm", "layer"]
            if config["num_nextn_predict_layers"] else None
        ),
        "head": (d, config["vocab_size"]),
        "layers": sorted(f"layer_{i}" for i in range(config["num_layers"])),
        "routed_layers": sorted(f"layer_{i}" for i in range(first, config["num_layers"])),
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


def _trim_heap() -> None:
    """Hand the pages the C heap has freed back to the system (glibc's
    ``malloc_trim``; nothing where there is none)."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def follow_steps(params, batches, hp: dict, model: dict):
    """Follow the first ``len(batches)`` updates from ``params`` and a zero
    selection bias. CONSUMES ``params``: the first update donates them.

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
        donate_argnums=(0, 1, 2, 3),
    )
    _trim_heap()
    start = [np.asarray(leaf) for leaf in jax.tree.leaves(params)]
    bias = initial_bias(model)
    mu = nu = None
    losses, first_grad, last = [], None, len(batches) - 1
    for count, tokens in enumerate(batches):
        loss, grads, counts = loss_and_grad(params, bias, tokens)
        bias = stepped_bias(bias, counts, model["recipe"]["bias_update_rate"])
        if mu is None:  # the zero moments are made on the device, where the first update consumes them
            mu, nu = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params)
        params, mu, nu, clipped = update(
            params, grads, mu, nu, jnp.float32(learning_rate(count, hp)), jnp.float32(count + 1)
        )
        losses.append(float(loss))
        if first_grad is None:
            first_grad = [np.asarray(g) for g in jax.tree.leaves(clipped)]
            _trim_heap()  # both of this file's programs are compiled by now
        del grads, clipped
        # The moments wait on the host while the next step's gradient is taken; after the last nobody reads them.
        mu, nu = jax.device_get((mu, nu)) if count < last else (None, None)
    # The change leaf by leaf, subtracted on the device: the host holds one copy of each tree and no more.
    after, change = jax.tree.leaves(params), []
    del params
    for index in range(len(after)):
        change.append(np.asarray(after[index] - jnp.asarray(start[index])))
        after[index] = start[index] = None
    return {"losses": losses, "first_grad": first_grad, "change": change, "select_bias": np.asarray(bias)}
