"""Plain float32 reference for the ViT / DeiT cells: forward, loss, gradient, AdamW.

Written from the papers, in plain ``jax.numpy``, float32, traced under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes). It imports nothing of the program and is
handed nothing the program made: the benchmark draws the weights
(``benchmark/weights.py``) and the batches (``benchmark/drivers``) from the
seed and gives the same arrays to both sides.

Model (Dosovitskiy et al., arXiv:2010.11929, sec. 3.1 and eq. 1-4; DeiT,
arXiv:2012.12877, uses the same trunk): non-overlapping patches projected by
one matrix, a class token, a learned position table, pre-LayerNorm blocks of
softmax self-attention and a GELU MLP, a final LayerNorm, a linear head on
the class token. The loss is the label-smoothed cross-entropy of DeiT's
recipe (Table 9); the optimizer is AdamW (Loshchilov & Hutter,
arXiv:1711.05101) behind a global-norm clip, as the program's recipe chains
them.

Departures from the papers, each because the program under test does so and
the reference has to compute the same function:

- no bias on the Q/K/V and output projections (the papers' have one);
- GELU in its tanh form (the papers' is the erf form);
- LayerNorm epsilon 1e-6;
- weight decay skips every vector, the class token and the position table;
- the learning rate of update ``t`` is the schedule at ``t - 1``, and the
  schedule starts at 0: the first update moves nothing.

The parameter tree is read by path; ``benchmark/weights.py`` documents the
layout. Shapes: patch kernel ``[p, p, 3, D]``, qkv kernel ``[D, 3, H, Dh]``,
output kernel ``[H, Dh, D]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# ImageNet channel statistics on the 0..255 scale (torchvision's
# 0.485/0.456/0.406 and 0.229/0.224/0.225 times 255).
MEAN_RGB = (0.485 * 255, 0.456 * 255, 0.406 * 255)
STDDEV_RGB = (0.229 * 255, 0.224 * 255, 0.225 * 255)
LN_EPS = 1e-6


def normalize(images_u8):
    x = images_u8.astype(jnp.float32)
    mean = jnp.asarray(MEAN_RGB, jnp.float32)
    std = jnp.asarray(STDDEV_RGB, jnp.float32)
    return (x - mean) / std


def layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def attention(x, p):
    """Multi-head softmax self-attention, eq. 5-8 of the ViT paper."""
    wqkv, wo = p["to_qkv"]["kernel"], p["to_out"]["kernel"]
    head_dim = wqkv.shape[-1]
    q = jnp.einsum("bld,dhe->bhle", x, wqkv[:, 0])
    k = jnp.einsum("bld,dhe->bhle", x, wqkv[:, 1])
    v = jnp.einsum("bld,dhe->bhle", x, wqkv[:, 2])
    scores = jnp.einsum("bhqe,bhke->bhqk", q, k) * head_dim**-0.5
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhke->bhqe", probs, v)
    return jnp.einsum("bhle,hed->bld", out, wo)


def mlp(x, p):
    h = x @ p["fc1"]["kernel"] + p["fc1"]["bias"]
    h = jax.nn.gelu(h, approximate=True)
    return h @ p["fc2"]["kernel"] + p["fc2"]["bias"]


def features(params, images_u8):
    """uint8 ``[B, S, S, 3]`` -> class-token features ``[B, D]`` after the final LayerNorm."""
    x = normalize(images_u8)
    kernel = params["PatchEmbedBlock_0"]["proj"]["kernel"]
    p, _, c, d = kernel.shape
    b, s = x.shape[0], x.shape[1]
    g = s // p
    patches = x.reshape(b, g, p, g, p, c).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, g * g, p * p * c)
    tokens = patches @ kernel.reshape(p * p * c, d)
    tokens = tokens + params["PatchEmbedBlock_0"]["proj"]["bias"]
    cls = jnp.broadcast_to(params["cls"], (b, 1, d))
    enc = params["Encoder_0"]
    x = jnp.concatenate([cls, tokens], axis=1) + enc["AddAbsPosEmbed_0"]["pos_embed"]
    depth = sum(1 for name in enc if name.startswith("block_"))
    for i in range(depth):
        blk = enc[f"block_{i}"]
        x = x + attention(layer_norm(x, blk["LayerNorm_0"]), blk["SelfAttentionBlock_0"])
        x = x + mlp(layer_norm(x, blk["LayerNorm_1"]), blk["FFBlock_0"])
    return layer_norm(x, enc["LayerNorm_0"])[:, 0]


def forward(params, images_u8):
    """uint8 ``[B, S, S, 3]`` -> float32 logits ``[B, classes]``."""
    feats = features(params, images_u8)
    return feats @ params["head"]["kernel"] + params["head"]["bias"]


def smoothed_cross_entropy_sum(logits, labels, label_smoothing):
    """Sum over rows of the label-smoothed cross-entropy."""
    classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, classes, dtype=jnp.float32)
    target = onehot * (1.0 - label_smoothing) + label_smoothing / classes
    return -jnp.sum(target * jax.nn.log_softmax(logits, axis=-1))


def _block_loss_and_grad(params, images, labels, label_smoothing):
    def loss_sum(p):
        return smoothed_cross_entropy_sum(forward(p, images), labels, label_smoothing)

    return jax.value_and_grad(loss_sum)(params)


def make_loss_and_grad(label_smoothing: float, rows_per_block: int, row_sharding=None):
    """``fn(params, images_u8, labels) -> (mean loss, gradient)`` over a whole
    batch, computed in blocks of rows so that float32 activations of a few
    rows, not of the batch, are alive at once. Where ``row_sharding`` is
    given, a block's rows are laid over its devices (the compiler then sums
    the gradient across them): the cell on four chips follows four times the
    rows in the time of one."""

    @jax.jit
    def block(params, acc_loss, acc_grad, images, labels):
        with jax.default_matmul_precision("highest"):
            loss, grad = _block_loss_and_grad(params, images, labels, label_smoothing)
        return acc_loss + loss, jax.tree.map(jnp.add, acc_grad, grad)

    def loss_and_grad(params, images_u8, labels):
        n = images_u8.shape[0]
        if n % rows_per_block:
            raise ValueError(f"batch {n} is not a multiple of the block {rows_per_block}")
        acc_loss = jnp.zeros((), jnp.float32)
        if row_sharding is not None:
            everywhere = jax.sharding.NamedSharding(row_sharding.mesh, jax.sharding.PartitionSpec())
            acc_loss = jax.device_put(acc_loss, everywhere)
        acc_grad = jax.tree.map(jnp.zeros_like, params)
        for start in range(0, n, rows_per_block):
            rows = slice(start, start + rows_per_block)
            images, targets = images_u8[rows], labels[rows]
            if row_sharding is not None:
                images, targets = jax.device_put((images, targets), row_sharding)
            acc_loss, acc_grad = block(params, acc_loss, acc_grad, images, targets)
        return acc_loss / n, jax.tree.map(lambda g: g / n, acc_grad)

    return loss_and_grad


def make_forward(rows_per_block: int):
    """``fn(params, images_u8) -> logits`` in blocks of rows."""

    @jax.jit
    def block(params, images):
        with jax.default_matmul_precision("highest"):
            return forward(params, images)

    def logits(params, images_u8):
        n = images_u8.shape[0]
        return jnp.concatenate([
            block(params, images_u8[s:s + rows_per_block])
            for s in range(0, n, rows_per_block)
        ])

    return logits


def check_layout(params, config: dict) -> None:
    """The parameter tree has the layout this file reads and the sizes the
    configuration's file states."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    length = (config["image_size"] // config["patch_size"]) ** 2 + 1
    enc = params["Encoder_0"]
    depth = sum(1 for k in enc if k.startswith("block_"))
    blk = enc["block_0"]
    found = {
        "pos_embed": tuple(enc["AddAbsPosEmbed_0"]["pos_embed"].shape),
        "to_qkv": tuple(blk["SelfAttentionBlock_0"]["to_qkv"]["kernel"].shape),
        "fc1": tuple(blk["FFBlock_0"]["fc1"]["kernel"].shape),
        "head": tuple(params["head"]["kernel"].shape),
        "depth": depth,
    }
    stated = {
        "pos_embed": (1, length, d),
        "to_qkv": (d, 3, heads, config["head_dim"]),
        "fc1": (d, config["intermediate_size"]),
        "head": (d, config["num_classes"]),
        "depth": config["num_hidden_layers"],
    }
    if found != stated:
        raise ValueError(f"the program's model {found} is not the configuration's {stated}")


# ------------------------------------------------------------------ optimizer


def learning_rate(count: int, hp: dict) -> float:
    """Linear warm-up from 0 to the peak, then a cosine to ``end_lr``;
    ``count`` is the number of updates already made."""
    peak = hp["base_lr"] * hp["global_batch_size"] / hp["lr_scaling_divisor"]
    steps_per_epoch = hp["num_train_images"] // hp["global_batch_size"]
    warmup = max(1, hp["warmup_epochs"] * steps_per_epoch)
    total = max(warmup + 1, hp["num_epochs"] * steps_per_epoch)
    if count < warmup:
        return peak * count / warmup
    frac = min(count - warmup, total - warmup) / (total - warmup)
    alpha = hp["end_lr"] / peak
    return peak * ((1.0 - alpha) * 0.5 * (1.0 + math.cos(math.pi * frac)) + alpha)


def decays(path: str, leaf) -> bool:
    """Weight decay applies to matrices, not to vectors, the class token or
    the position table."""
    return leaf.ndim >= 2 and "pos_embed" not in path and "cls" not in path


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_update(params, grads, mu, nu, lr, t, *, weight_decay, clip_grad_norm):
    """Update number ``t`` (from 1) at learning rate ``lr``: clip, Adam
    moments with bias correction, decoupled weight decay. Returns
    ``(params, mu, nu, clipped gradient)``."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    if clip_grad_norm is not None:
        grads = clip_by_global_norm(grads, clip_grad_norm)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def new_param(key_path, p, m, v):
        step = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps)
        if decays("/".join(str(getattr(k, "key", k)) for k in key_path), p):
            step = step + weight_decay * p
        return p - lr * step

    params = jax.tree_util.tree_map_with_path(new_param, params, mu, nu)
    return params, mu, nu, grads


def follow_steps(params, batches, hp: dict, rows_per_block: int, row_sharding=None):
    """Follow the first ``len(batches)`` updates from ``params``.

    ``batches`` is a list of ``(images_u8 [B, S, S, 3], labels [B])`` and
    ``hp`` the recipe's numbers under the names of ``learning_rate``.
    Returns each step's loss, the first gradient as the optimizer's moments
    get it (after the clip), and the parameters after the last update."""
    loss_and_grad = make_loss_and_grad(hp["label_smoothing"], rows_per_block, row_sharding)
    update = jax.jit(functools.partial(
        adamw_update,
        weight_decay=hp["weight_decay"],
        clip_grad_norm=hp["clip_grad_norm"],
    ))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for count, (images, labels) in enumerate(batches):
        loss, grads = loss_and_grad(params, images, labels)
        params, mu, nu, clipped = update(
            params, grads, mu, nu,
            jnp.float32(learning_rate(count, hp)), jnp.float32(count + 1),
        )
        losses.append(float(loss))
        if first_grad is None:
            first_grad = clipped
    return {"losses": losses, "first_grad": first_grad, "params": params}
