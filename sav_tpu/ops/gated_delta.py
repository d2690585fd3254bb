"""The gated delta rule: a linear-attention recurrence over the sequence.

Per value head, with a state ``S`` of shape ``[d_k, d_v]`` (keys by values)
that starts at zero (arXiv:2412.06464, eq. 10)::

    S'  = exp(g_t) S_{t-1}                          # decay, g_t <= 0
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T      # the delta rule's write
    o_t = S_t^T q_t

:func:`gated_delta_rule_recurrent` is that, a token at a time, in float32:
what the tests hold the chunked form to.

:func:`gated_delta_rule` computes the same in chunks of ``C`` tokens so that
the work is matrix products. With ``gamma_i`` the running sum of ``g`` inside
a chunk and ``S`` the state the chunk starts from::

    A  = strict_lower(beta_i (k_i . k_j) exp(gamma_i - gamma_j))
    T  = (I + A)^-1
    V' = T beta (V - e^gamma (K S))             # = U - W S with W = T (beta e^gamma K), U = T (beta V)
    O  = e^gamma (Q S) + lower(Q K^T exp(gamma_i - gamma_j)) V'
    S_next = e^{gamma_C} S + K^T (e^{gamma_C - gamma} V')

``W`` and ``U`` are never formed: ``V'`` is linear in ``V - e^gamma K S``, so
``T`` (its columns scaled by ``beta``) is applied once, to that difference,
and the decays scale rows of float32 results (``Q S``, ``K S``, ``V'``)
instead of rows of q and k. What a chunk keeps for its turn is then ``T
beta`` and the masked ``Q K^T`` (``C x C`` a head) beside its own rows of q,
k and v: at 4 x 4,096 tokens and 32 value heads 0.34 GB a layer, against
0.74 GB with ``W``, ``U`` and q, k scaled and repeated a value head
(compiled for a v5e the training step held 15.8 GB that way).

Everything that does not read ``S`` (``A``, ``T``, the masked ``Q K^T``) is
computed for all chunks at once; a ``lax.scan`` over the chunks carries ``S``
through the last three lines. Every exponent is a difference ``gamma_i -
gamma_j`` with ``i >= j`` (or ``gamma_i`` itself), so nothing overflows
however fast the state decays; the upper triangle is masked before ``exp``.

Precision: the running sums, ``exp``, the triangular system and the carried
state are float32; q, k, ``T beta``, the masked ``Q K^T``, ``V - e^gamma K
S``, ``V'`` and the state enter the matrix products in the operands' dtype
(the model's compute dtype) and are summed in float32. The backward pass is
JAX's transpose of this program (the scan's included); a caller bounds what
it keeps with a remat policy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

CHUNK = 64  # the published kernels' chunk


@jax.custom_vjp
def _unit_lower_inverse(lower: jax.Array) -> jax.Array:
    """``(I + lower)^-1`` for strictly lower triangular ``lower [..., n, n]``
    (float32), exactly, by doubling: with ``X`` the inverse of the diagonal
    blocks of size ``s`` and ``L`` the entries of ``lower`` that join two
    such blocks into one of ``2 s`` (row in the upper half of a pair, column
    in its lower half), ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1,
    D^-1]]`` is ``X - X L X`` for all pairs at once, from ``X = I`` at ``s =
    1``: every step is two products of whole ``[n, n]`` matrices on the MXU,
    in the order the operands lie in, and every entry of ``lower`` enters at
    one step (that of the highest bit in which its row and column differ). No
    power of ``lower`` is formed: the series ``sum (-lower)^k`` cancels
    catastrophically where many entries are near 1. Its derivative is the
    inverse's own, ``d lower = -T^T dT T^T``, from ``T`` alone.

    A substitution a row at a time (the textbook's, and the published
    kernels' inside their 16 x 16 blocks) computes the same and was measured
    first: as XLA's program its hundreds of row slices each cross the whole
    array, 26 ms a call at 8,192 systems of 64 on a v5e (PERF.md section 6,
    PR 37)."""
    return _doubled_inverse(lower)


def _unit_lower_inverse_fwd(lower):
    solved = _doubled_inverse(lower)
    return solved, solved


def _unit_lower_inverse_bwd(solved, g):
    back = -jnp.einsum(
        "...ji,...jk,...lk->...il", solved, g, solved, precision=jax.lax.Precision.HIGHEST
    )
    n = solved.shape[-1]
    return (jnp.where(jnp.arange(n)[:, None] > jnp.arange(n)[None, :], back, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _doubled_inverse(lower: jax.Array) -> jax.Array:
    n = lower.shape[-1]
    row, col = jnp.arange(n)[:, None], jnp.arange(n)[None, :]

    def joined(size):  # the entries that join two blocks of ``size`` into one
        pair = row // (2 * size) == col // (2 * size)
        return jnp.where(pair & (row % (2 * size) >= size) & (col % (2 * size) < size), lower, 0.0)

    solved = jnp.eye(n, dtype=lower.dtype) - joined(1)  # blocks of 2: [[1, 0], [b, 1]]^-1
    size = 2
    while size < n:
        solved = solved - jnp.einsum(
            "...ij,...jk,...kl->...il", solved, joined(size), solved, precision=jax.lax.Precision.HIGHEST
        )
        size *= 2
    return solved


def _by_chunk(x: jax.Array, chunks: int, chunk: int) -> jax.Array:
    """``[B, L, H, ...]`` -> ``[N, B, H, C, ...]``."""
    batch = x.shape[0]
    x = x.reshape((batch, chunks, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)  # [N, B, C, H, ...] -> [N, B, H, C, ...]


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _prepare(q, k, g, beta, group: int):
    """Everything of a chunk that does not read the state, for all chunks at
    once, on ``[N, B, H, C, ...]`` operands: ``(T beta, the masked Q K^T,
    gamma)``, the first two ``[N, B, H, C, C]`` in q's dtype. Checkpointed:
    the backward pass computes it again from the four operands, after the
    scan's own backward is done with its residuals, and holds the float32 ``C
    x C`` tensors of one direction at a time."""
    chunk, dtype = q.shape[3], q.dtype
    gamma = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(chunk)
    visible = rows[:, None] >= rows[None, :]
    # exp of a masked difference: the upper triangle would overflow.
    decay = jnp.exp(jnp.where(visible, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))

    def pairs(a, b):  # [N, B, H_k, C, C] float32, then a key head's value heads
        dots = jnp.einsum("nbhid,nbhjd->nbhij", a, b, preferred_element_type=jnp.float32)
        return jnp.repeat(dots, group, axis=2)

    strict = rows[:, None] > rows[None, :]
    system = jnp.where(strict, beta[..., :, None] * pairs(k, k) * decay, 0.0)
    solved = (_unit_lower_inverse(system) * beta[..., None, :]).astype(dtype)  # T beta
    inside = (pairs(q, k) * decay).astype(dtype)  # lower(Q K^T exp(.)), diagonal included
    return solved, inside, gamma


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked gated delta rule.

    Args:
      q, k: ``[B, L, H_k, d_k]`` (L2-normalised and scaled by the caller);
        ``H_k`` divides ``H``, and key head ``j`` feeds value heads
        ``j H / H_k .. (j + 1) H / H_k - 1``.
      v: ``[B, L, H, d_v]``.
      g: ``[B, L, H]`` float32, the log of the decay (``<= 0``).
      beta: ``[B, L, H]`` float32, the write strength in ``[0, 1]``.
      chunk: tokens a chunk; ``L`` is padded to whole chunks with rows
        ``g = 0, beta = 0, k = 0`` that leave the state as it is.

    Returns:
      ``(o [B, L, H, d_v]`` in ``v``'s dtype, the final state ``[B, H, d_k,
      d_v]`` float32``)``.
    """
    batch, length, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    if heads % key_heads or k.shape != q.shape or g.shape != v.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            f"gated delta rule: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape}"
        )
    group, dtype = heads // key_heads, v.dtype
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta)
        )
    chunks = (length + pad) // chunk
    q, k, v = (_by_chunk(x, chunks, chunk) for x in (q, k, v))  # [N, B, H, C, d]
    g = _by_chunk(g.astype(jnp.float32), chunks, chunk)  # [N, B, H, C]
    beta = _by_chunk(beta.astype(jnp.float32), chunks, chunk)

    solved, inside, gamma = _prepare(q, k, g, beta, group)
    # Tagged for a caller's remat policy: with these kept (134 MB a layer at 4
    # x 4,096 tokens) its recomputation of the layer runs the scan alone.
    solved, inside = (checkpoint_name(x, "gdn_solved") for x in (solved, inside))

    def by_key_head(x):  # [B, H, ...] -> [B, H_k, group, ...]
        return x.reshape((batch, key_heads, group) + x.shape[2:])

    # A step's backward computes its products again from the state it started
    # with: the scan keeps that state a chunk and nothing else.
    @jax.checkpoint
    def step(state, xs):
        q, k, v, solved, inside, gamma = xs  # q, k at the key heads
        held = by_key_head(state.astype(dtype))
        grown = jnp.exp(gamma)[..., None]  # e^gamma, a row
        read = jnp.einsum("bjck,bjrkv->bjrcv", k, held, preferred_element_type=jnp.float32)
        unread = (v.astype(jnp.float32) - grown * read.reshape(v.shape)).astype(dtype)  # V - e^gamma K S
        fresh = jnp.einsum("bhij,bhjv->bhiv", solved, unread, preferred_element_type=jnp.float32)  # V'
        out = grown * jnp.einsum(
            "bjck,bjrkv->bjrcv", q, held, preferred_element_type=jnp.float32
        ).reshape(v.shape)
        out = out + jnp.einsum("bhij,bhjv->bhiv", inside, fresh.astype(dtype), preferred_element_type=jnp.float32)
        left = by_key_head((jnp.exp(gamma[..., -1:] - gamma)[..., None] * fresh).astype(dtype))
        written = jnp.einsum("bjck,bjrcv->bjrkv", k, left, preferred_element_type=jnp.float32)
        state = jnp.exp(gamma[..., -1])[..., None, None] * state + written.reshape(state.shape)
        return state, out.astype(dtype)

    start = jnp.zeros((batch, heads, dk, dv), jnp.float32)
    final, out = jax.lax.scan(step, start, (q, k, v, solved, inside, gamma))
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1)  # [B, N, C, H, d_v]
    return out.reshape(batch, chunks * chunk, heads, dv)[:, :length], final


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The rule as its equations state it, a token at a time in float32 (the
    module docstring's three lines); shapes and results as
    :func:`gated_delta_rule`'s, the output float32."""
    batch, _, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    group = heads // key_heads
    q, k = (jnp.repeat(x.astype(jnp.float32), group, axis=2) for x in (q, k))
    v, g, beta = (x.astype(jnp.float32) for x in (v, g, beta))

    def step(state, xs):
        q, k, v, g, beta = xs  # [B, H, d], [B, H]
        state = jnp.exp(g)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k, precision=jax.lax.Precision.HIGHEST)
        write = beta[..., None] * (v - read)
        state = state + k[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q, precision=jax.lax.Precision.HIGHEST)

    start = jnp.zeros((batch, heads, dk, dv), jnp.float32)
    final, out = jax.lax.scan(step, start, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), final
