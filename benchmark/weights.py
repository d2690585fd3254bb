"""Seeded weights and batches: the arrays both sides are given.

The benchmark makes them, on the device, in one jitted call each, from
``--seed``: neither the program's initialiser nor a checkpoint is read, so
the reference takes nothing the program made. Only the *layout* comes from
the program (the tree of paths and shapes, as ``jax.eval_shape`` gives it).

Every leaf is drawn, the head included: the program's own initialiser
leaves the head and the class token at zero, and with a zero head every
logit is zero and any comparison passes.

Scales (so that activations keep unit order of magnitude through the
depth): a matrix is normal with standard deviation ``fan_in ** -0.5``; a
LayerNorm scale is ``1 + 0.1 * normal``; every other vector, the class
token and the position table are ``0.02 * normal``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole number a driver may pass: seeds run a little
    past 2**31, more than a signed 32-bit key seed holds."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def path_of(key_path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in key_path)


def _fan_in(path: str, shape) -> int:
    if path.endswith("to_qkv/kernel"):  # [D, 3, H, Dh]
        return shape[0]
    if path.endswith("to_out/kernel"):  # [H, Dh, D]
        return shape[0] * shape[1]
    return math.prod(shape[:-1])  # dense [in, out], conv [p, p, c, out]


def _draw(key, path: str, shape, dtype):
    noise = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("kernel"):
        out = noise * _fan_in(path, shape) ** -0.5
    elif path.endswith("scale"):
        out = 1.0 + 0.1 * noise
    else:
        out = 0.02 * noise
    return out.astype(dtype)


def draw_params(abstract_params, seed: int, shardings=None):
    """A parameter tree shaped like ``abstract_params``, every leaf drawn
    from ``seed``; placed by ``shardings`` (a matching tree) where given."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)

    def draw(key):
        leaves = [
            _draw(jax.random.fold_in(key, i), path_of(p), leaf.shape, leaf.dtype)
            for i, (p, leaf) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw, out_shardings=shardings)(seed_key(seed))


def draw_batches(seed: int, count: int, batch: int, image_size: int, classes: int):
    """``count`` batches of ``batch`` distinct uint8 images ``[B, S, S, 3]``
    with labels ``[B]``, on the device, in one call."""

    def draw(key):
        ki, kl = jax.random.split(jax.random.fold_in(key, 0x6261))
        images = jax.random.bits(ki, (count, batch, image_size, image_size, 3), jnp.uint8)
        labels = jax.random.randint(kl, (count, batch), 0, classes, jnp.int32)
        return images, labels

    images, labels = jax.jit(draw)(seed_key(seed))
    return [(images[i], labels[i]) for i in range(count)]
