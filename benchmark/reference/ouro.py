"""Plain float32 reference for the Ouro cells: forward, loss, gradient, AdamW.

Written from the paper ("Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741) and the sizes of the model's public
``config.json``, in plain ``jax.numpy``, float32, traced under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program and is handed nothing the program made: the benchmark draws the
weights (``benchmark/weights.py``) and the token batches
(``benchmark/drivers/train_tokens_fit.py``) from the seed and gives the
same arrays to both sides. The optimizer is ``reference/vit.py``'s AdamW,
imported.

Model. ``h = E[tokens]``; for pass ``t = 1..T`` the same ``N`` layers, then a
final RMSNorm whose output is both the pass's result ``h_t`` and the next
pass's input; logits ``z_t = h_t W_head`` (untied); exit gate ``lambda_t =
sigmoid(h_t . w_g + b_g)``. A layer is sandwich-normed: ``h += RMSNorm(Attn(
RMSNorm(h)))``, ``h += RMSNorm(MLP(RMSNorm(h)))``. ``Attn``: Q, K, V, out
without bias, rotary on the whole head (rotate-halves pairing, base
``rope_theta``), logits scaled by ``head_dim ** -0.5``, causal softmax by an
explicit ``[S, S]`` mask. ``MLP(x) = (silu(x W_gate) * x W_up) W_down``.

Loss: the mean over the predicted positions of ``sum_t p_t CE(z_t, next
token) - beta H(p)``, with ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for
``t < T`` and ``p_T = prod_{j<T}(1 - lambda_j)``: the entropy-regularised
objective of the paper's pre-training. Every pass runs on every token.

What the paper and the config leave open, set as the program sets it and
listed under ``assumed`` in ``benchmark/configs/ouro_2.6b.json``: the final
norm runs after every pass and its output enters the next pass; the gate
reads the normed ``h_t``; ``beta``; the recipe. Departures from the
published recipe: Adam's second-moment decay is the program's 0.999.

Memory. The four passes are unrolled (four calls of the same functions on
the same tree). A batch goes through one sequence at a time; around each
layer application and each pass's head stands a ``jax.checkpoint``, which
changes no arithmetic: at 4,096 positions the dense causal logits are 1.07
GB a layer application and a pass's logits 0.8 GB, and sixteen and four of
them would otherwise be alive at once. Adam's moments wait on the host
between the steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.vit import adamw_update, learning_rate


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rotate(x, theta):
    """Rotary position embedding on ``[S, H, Dh]``: lane ``i`` is paired
    with lane ``i + Dh/2``, at angular frequency ``theta ** (-2i / Dh)``."""
    s, _, dh = x.shape
    freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]  # [S, Dh/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, p, theta):
    """Causal multi-head self-attention on one sequence ``[S, D]``."""
    wqkv, wo = p["to_qkv"]["kernel"], p["to_out"]["kernel"]  # [D, 3, H, Dh], [H, Dh, D]
    dh = wqkv.shape[-1]
    q = rotate(jnp.einsum("sd,dhe->she", x, wqkv[:, 0]), theta)
    k = rotate(jnp.einsum("sd,dhe->she", x, wqkv[:, 1]), theta)
    v = jnp.einsum("sd,dhe->she", x, wqkv[:, 2])
    scores = jnp.einsum("qhe,khe->hqk", q, k) * dh**-0.5
    s = x.shape[0]
    visible = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khe,hed->qd", probs, v, wo)


def mlp(x, p):
    gate = x @ p["fc1"]["gate"]["kernel"]
    up = x @ p["fc1"]["up"]["kernel"]
    return (jax.nn.silu(gate) * up) @ p["fc2"]["kernel"]


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def layer(h, p, theta, eps):
    a = attention(rms_norm(h, p["attn_norm_in"], eps), p["SelfAttentionBlock_0"], theta)
    h = h + rms_norm(a, p["attn_norm_out"], eps)
    m = mlp(rms_norm(h, p["mlp_norm_in"], eps), p["GatedFFBlock_0"])
    return h + rms_norm(m, p["mlp_norm_out"], eps)


def passes(params, tokens, model: dict):
    """One sequence of ids ``[S]`` -> the normed hidden state ``[S, D]`` of
    each of the ``T`` passes."""
    stack = params["ut_loop"]
    depth = sum(1 for name in stack if name.startswith("layer_"))
    h = params["embed"]["embedding"][tokens]
    out = []
    for _ in range(model["total_ut_steps"]):
        for i in range(depth):
            h = layer(h, stack[f"layer_{i}"], float(model["rope_theta"]), model["rms_norm_eps"])
        h = rms_norm(h, stack["final_norm"], model["rms_norm_eps"])
        out.append(h)
    return out


def head_logits(params, h):
    return h @ params["lm_head"]["kernel"]


def gate_probability(params, h):
    g = params["exit_gate"]
    return jax.nn.sigmoid(h @ g["kernel"][:, 0] + g["bias"][0])


def forward(params, tokens, model: dict):
    """One sequence ``[S]`` -> ``(logits [T, S, V], lambda [T, S])``."""
    hs = passes(params, tokens, model)
    return (
        jnp.stack([head_logits(params, h) for h in hs]),
        jnp.stack([gate_probability(params, h) for h in hs]),
    )


def exit_distribution(lam):
    """``lambda [T, ...]`` -> ``p [T, ...]``: leave at pass ``t`` having
    stayed through the passes before it; the last pass takes what is left."""
    stayed, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    p.append(stayed)
    return jnp.stack(p)


@jax.checkpoint
def _pass_cross_entropy(w_head, h, targets):
    logits = h @ w_head
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]


def sequence_loss_sum(params, tokens, model: dict, beta: float):
    """Sum over the ``S`` predicted positions of one sequence ``[S + 1]``."""
    inputs, targets = tokens[:-1], tokens[1:]
    hs = passes(params, inputs, model)
    ce = jnp.stack([_pass_cross_entropy(params["lm_head"]["kernel"], h, targets) for h in hs])
    p = exit_distribution(jnp.stack([gate_probability(params, h) for h in hs]))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.sum(jnp.sum(p * ce, axis=0) - beta * entropy)


def make_loss_and_grad(model: dict, beta: float):
    """``fn(params, tokens [B, S + 1]) -> (mean loss, gradient)``, one
    sequence at a time."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def block(params, acc_loss, acc_grad, tokens):
        with jax.default_matmul_precision("highest"):
            loss, grad = jax.value_and_grad(sequence_loss_sum)(params, tokens, model, beta)
        return acc_loss + loss, jax.tree.map(jnp.add, acc_grad, grad)

    def loss_and_grad(params, tokens):
        acc_loss = jnp.zeros((), jnp.float32)
        acc_grad = jax.tree.map(jnp.zeros_like, params)
        for row in tokens:
            acc_loss, acc_grad = block(params, acc_loss, acc_grad, row)
        n = tokens.shape[0] * (tokens.shape[1] - 1)
        return acc_loss / n, jax.tree.map(lambda g: g / n, acc_grad)

    return loss_and_grad


def make_forward(model: dict):
    """``fn(params, tokens [B, S]) -> (logits [B, T, S, V], lambda [B, T, S])``."""

    @jax.jit
    def one(params, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(params, tokens, model)

    def batch(params, tokens):
        logits, lam = zip(*(one(params, row) for row in tokens))
        return jnp.stack(logits), jnp.stack(lam)

    return batch


def check_layout(params, config: dict) -> None:
    """The parameter tree has the layout this file reads, the sizes the
    configuration's file states, and each looped layer once."""
    d, heads, dh = config["hidden_size"], config["num_attention_heads"], config["head_dim"]
    stack = params["ut_loop"]
    first = stack["layer_0"]
    found = {
        "embedding": tuple(params["embed"]["embedding"].shape),
        "to_qkv": tuple(first["SelfAttentionBlock_0"]["to_qkv"]["kernel"].shape),
        "to_out": tuple(first["SelfAttentionBlock_0"]["to_out"]["kernel"].shape),
        "gate": tuple(first["GatedFFBlock_0"]["fc1"]["gate"]["kernel"].shape),
        "down": tuple(first["GatedFFBlock_0"]["fc2"]["kernel"].shape),
        "head": tuple(params["lm_head"]["kernel"].shape),
        "layers": sorted(k for k in stack if k.startswith("layer_")),
    }
    stated = {
        "embedding": (config["vocab_size"], d),
        "to_qkv": (d, 3, heads, dh),
        "to_out": (heads, dh, d),
        "gate": (d, config["intermediate_size"]),
        "down": (config["intermediate_size"], d),
        "head": (d, config["vocab_size"]),
        "layers": sorted(f"layer_{i}" for i in range(config["num_layers"])),
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


def follow_steps(params, batches, hp: dict, model: dict):
    """Follow the first ``len(batches)`` updates from ``params``.

    ``batches`` is a list of token arrays ``[B, S + 1]``; ``hp`` holds the
    recipe's numbers under the names of ``reference/vit.py``'s
    ``learning_rate`` plus ``entropy_weight``; ``model`` the configuration's
    sizes. Returns each step's loss, the first gradient as the optimizer's
    moments get it (after the clip) and the parameters' change after the
    last update, the last two as lists of host arrays in the tree's order."""
    loss_and_grad = make_loss_and_grad(model, hp["entropy_weight"])
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
        loss, grads = loss_and_grad(params, tokens)
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
