"""Manifold-constrained hyper-connections: the residual path of a sublayer
over ``n`` streams (arXiv:2512.24880 section 4 on arXiv:2409.19606).

The residual state of a token is ``X`` in ``R^{n x d}``, here a tuple of ``n``
arrays ``[B, S, d]`` (a stream is a whole array, so every pass below is
elementwise over whole arrays and the compiler may fuse the ``n`` results of
one pass into one read of the streams). For a sublayer ``F`` with its own
``Phi [n d, 2n + n^2]`` (``kernel``), three scalar gates ``alpha`` (``scale``)
and a base ``b [2n + n^2]`` (``bias``), all float32::

    xhat  = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)        # one RMS over all n d entries
    m     = xhat Phi = [m_pre (n) | m_post (n) | m_res (n^2)]
    h_pre = sigmoid(alpha_pre m_pre + b_pre);  h_post = 2 sigmoid(alpha_post m_post + b_post)
    Ht    = clamp(alpha_res mat(m_res) + b_res, lo, hi)
    H_res = SinkhornKnopp(Ht): M = exp(Ht); iters times: M /= colsum(M) + eps; M /= rowsum(M) + eps
    u     = sum_i h_pre[i] X_i                              # what the sublayer reads
    X'_i  = sum_j H_res[i, j] X_j + h_post[i] F(RMSNorm(u))

The streams stay in the compute dtype; the maps (24 numbers a token at
``n`` 4) are float32 from the projection's accumulator on. ``vec(X)`` is
stream-major: row ``i d + c`` of ``Phi`` reads channel ``c`` of stream ``i``.

Scopes, for the readers of a trace: ``hc/pre`` (the RMS, the projection, the
gates, the weighted stream sum), ``hc/sinkhorn``, ``hc/post``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

Dtype = Any


def sinkhorn_knopp(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``[..., n, n]`` -> its projection onto the doubly stochastic matrices:
    ``exp``, then ``iters`` times the columns and then the rows divided by
    their sums plus ``eps``. Rows are the second-last axis (``H[i, j]`` takes
    stream ``j`` into stream ``i``)."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def fan_out(x: jax.Array, streams: int):
    """The residual state a stack starts from: ``x`` copied to every stream
    (``x`` itself where there is one)."""
    return x if streams == 1 else (x,) * streams


def fan_in(state) -> jax.Array:
    """What a final norm reads: the streams' sum."""
    return state if not isinstance(state, tuple) else sum(state[1:], start=state[0])


class HyperConnection(nn.Module):
    """One sublayer's residual path. ``(u, merge)``: what the sublayer reads,
    and ``merge(y) -> (state', stats)`` that takes the sublayer's result back
    into the state. At ``streams`` 1 the state is one array, ``u`` is it,
    ``merge(y)`` is ``x + y`` and there are no parameters and no ``stats``;
    else ``stats`` is ``(largest |row or column sum of H_res - 1|, norm of
    H_res X over norm of X)``, two float32 scalars no gradient flows to."""

    streams: int
    sinkhorn_iters: int = 20
    sinkhorn_eps: float = 1e-6
    res_clamp: tuple = (-30.0, 30.0)
    norm_eps: float = 1e-6
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, state):
        n = self.streams
        if n == 1:
            return state, lambda y: (state + y, None)
        dim = state[0].shape[-1]
        maps = 2 * n + n * n
        # Zero ``Phi`` with small gates is the static start of arXiv:2409.19606:
        # mean of the streams in, the result added to every stream, the
        # streams left apart (an identity ``H_res`` after Sinkhorn).
        phi = self.param("kernel", nn.initializers.zeros, (n * dim, maps))
        alpha = self.param("scale", nn.initializers.constant(0.01), (3,))
        base = self.param("bias", _static_start(n), (maps,))

        with jax.named_scope("hc/pre"):
            squares = sum(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1) for x in state)  # [B, S]
            inv_rms = jax.lax.rsqrt(squares / (n * dim) + self.norm_eps)
            phi = phi.astype(self.dtype).reshape(n, dim, maps)
            m = sum(
                jnp.einsum("bsd,dk->bsk", x, phi[i], preferred_element_type=jnp.float32)
                for i, x in enumerate(state)
            ) * inv_rms[..., None]
            h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + base[:n])
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + base[n:2 * n])
            logits = jnp.clip(alpha[2] * m[..., 2 * n:] + base[2 * n:], *self.res_clamp)
        with jax.named_scope("hc/sinkhorn"):
            h_res = sinkhorn_knopp(
                logits.reshape(*logits.shape[:-1], n, n), self.sinkhorn_iters, self.sinkhorn_eps
            )
        # Tagged for a caller's remat policy: 24 float32 a token that spare
        # the backward pass a pass over the streams and the twenty iterations.
        h_pre, h_post, h_res = (checkpoint_name(t, "hc_maps") for t in (h_pre, h_post, h_res))
        with jax.named_scope("hc/pre"):
            u = sum(h_pre[..., i, None] * x.astype(jnp.float32) for i, x in enumerate(state))

        def merge(y):
            with jax.named_scope("hc/post"):
                y32 = y.astype(jnp.float32)
                mixed = tuple(
                    sum(h_res[..., i, j, None] * x.astype(jnp.float32) for j, x in enumerate(state))
                    for i in range(n)
                )
                merged = tuple((mixed[i] + h_post[..., i, None] * y32).astype(self.dtype) for i in range(n))
                sums = jnp.stack([jnp.sum(h_res, axis=-1), jnp.sum(h_res, axis=-2)])
                gain = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in mixed) / jnp.sum(squares))
                stats = jax.lax.stop_gradient((jnp.max(jnp.abs(sums - 1.0)), gain))
            return merged, stats

        return u.astype(self.dtype), merge


def _static_start(n: int):
    """``b`` at which the maps, with ``Phi`` zero, are ``h_pre`` 1/n,
    ``h_post`` 1 and ``H_res`` the identity to ``exp(-8)``."""
    def init(key, shape, dtype=jnp.float32):
        del key
        pre = jnp.full((n,), -jnp.log(n - 1.0), dtype)  # sigmoid = 1 / n
        post = jnp.zeros((n,), dtype)
        res = jnp.where(jnp.eye(n, dtype=bool), 0.0, -8.0).reshape(-1).astype(dtype)
        return jnp.concatenate([pre, post, res]).reshape(shape)

    return init
