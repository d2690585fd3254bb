"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context capability the reference lacked entirely (SURVEY.md §5
'Long-context / sequence parallelism: none'). Sequences are sharded over the
``seq`` mesh axis; each device holds a Q shard and streams K/V shards around
the ring with ``jax.lax.ppermute`` (XLA collective permute → ICI
neighbor-to-neighbor traffic), accumulating exact softmax attention with the
same online (m, l, acc) statistics the flash kernel uses. Communication
overlaps compute: the K/V rotation for step i+1 is issued while block i is
being contracted, and XLA pipelines the ppermute over ICI.

Memory per device: O(L_local · L_local) logits per block instead of O(L²) —
sequence length scales linearly with the ring size.

Differentiable (ppermute has a transpose rule); numerics cross-checked
against the dense XLA core in ``tests/test_ring_attention.py``.
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from sav_tpu.parallel.mesh import SEQ_AXIS

# importlib: `import ... as` and `from ... import` both resolve the
# attribute `flash_attention`, which ops/__init__ rebinds to the same-named
# function; sys.modules holds the real submodule.
_fa = importlib.import_module("sav_tpu.ops.flash_attention")

_NEG_INF = float("-inf")


def _mask_key_block(s, origin, blk_len: int, valid_len: int):
    """Force logits at global key positions ``>= valid_len`` to −inf.

    Each K/V block travels with its origin shard index (rotated along with
    the block) so global positions stay recoverable after any number of
    ppermutes."""
    key_pos = origin * blk_len + jax.lax.iota(jnp.int32, blk_len)
    return jnp.where(key_pos[None, None, None, :] < valid_len, s, _NEG_INF)


def _online_softmax_update(m, l, s, masked: bool):
    """One block's contribution to the running (max, denominator).

    Returns ``(m_new, l_new, alpha, p)``: the updated statistics, the
    rescale factor for existing accumulators, and the block's unnormalized
    probabilities — the same (m, l, acc) algebra the flash kernel uses."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    if masked:
        # A fully-masked block leaves m at -inf; exp(-inf - -inf) = nan,
        # so guard the shift (the block contributes exactly zero mass).
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), _NEG_INF, m - m_safe))
        p = jnp.exp(s - m_safe)
    else:
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
    return m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True), alpha, p


def _ring_loop(k, v, origin, state, block_fn, *, axis_name: str,
               axis_size: int):
    """Rotate K/V (and the origin index, when masking) around the ring,
    folding each block into ``state`` via ``block_fn(state, k, v, origin)``."""
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for step in range(axis_size):
        state = block_fn(state, k, v, origin)
        if step + 1 < axis_size:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
            if origin is not None:
                origin = jax.lax.ppermute(origin, axis_name, perm)
    return state


def _guard_zero_denominator(l):
    # Defensive NaN guard. Masking is key-side only, so every query row
    # (padded or not) always attends to >= 1 valid key and l > 0 holds —
    # this should be unreachable. Kept so that a future mask variant that
    # can zero a full row degrades to zeros, not 0/0 NaNs that would
    # poison reductions run over the raw output.
    return jnp.where(l == 0.0, 1.0, l)


def _ring_shard_fn(q, k, v, *, axis_name: str, axis_size: int, scale: float,
                   valid_len: Optional[int] = None):
    """Per-shard body. q/k/v: ``[B, L_loc, H, D]`` (local shards).

    ``valid_len`` (static) masks global key positions ``>= valid_len`` out
    of every softmax — the pad-and-mask path :mod:`sav_tpu.parallel.seq_parallel`
    uses for CLS-odd model sequence lengths. ``None`` compiles to the
    unmasked loop (no extra ops).
    """
    batch, q_len, heads, dim = q.shape
    m = jnp.full((batch, heads, q_len, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((batch, heads, q_len, 1), jnp.float32)
    acc = jnp.zeros((batch, q_len, heads, dim), jnp.float32)
    masked = valid_len is not None
    origin = jax.lax.axis_index(axis_name) if masked else None

    def one_block(state, k_blk, v_blk, origin):
        m, l, acc = state
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = _mask_key_block(s, origin, k_blk.shape[1], valid_len)
        m_new, l_new, alpha, p = _online_softmax_update(m, l, s, masked)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        # alpha: [B,H,Lq,1] → broadcast over the [B,Lq,H,D] accumulator.
        alpha_q = jnp.transpose(alpha, (0, 2, 1, 3))
        return m_new, l_new, acc * alpha_q + pv

    m, l, acc = _ring_loop(
        k, v, origin, (m, l, acc), one_block,
        axis_name=axis_name, axis_size=axis_size,
    )
    if masked:
        l = _guard_zero_denominator(l)
    out = acc / jnp.transpose(l, (0, 2, 1, 3))
    return out.astype(q.dtype)


def _ring_talking_heads_shard_fn(
    q, k, v, w_pre, w_post, *, axis_name: str, axis_size: int, scale: float,
    valid_len: Optional[int] = None,
):
    """Ring attention with CaiT's pre/post-softmax head mixing — exact, one
    rotation (the seam that unlocks SP for talking-heads trunks).

    Head mixing couples heads across the softmax, which breaks the per-head
    online accumulator of :func:`_ring_shard_fn`: the post-mix probability
    ``pm_j = Σ_i Wpost[i,j] p_i`` pairs source-head-``i`` probabilities with
    head-``j`` *values*, so the output does not decompose into per-head
    attention outputs. It does decompose into head-*pair* accumulators::

        out[q,j] = Σ_i Wpost[i,j] · (Σ_k p_i,qk · v_k,j) / l_i,q
                 = Σ_i Wpost[i,j] · A[i,j,q] / l_i,q

    where ``A[i,j] = Σ_k exp(s̃_i,qk − m_i,q) v_k,j`` accumulates online
    with source-head-``i`` statistics (running max ``m_i``, denominator
    ``l_i``) exactly like flash — per-device memory is O(H²·L_loc·D), still
    no L² term, at H× the PV FLOPs (H is 4-16 for the model zoo). The
    pre-softmax mix ``s̃ = Wpreᵀ s`` is block-local and rides unchanged;
    key-side masking applies after it (padded columns forced to −inf, so
    they carry zero mass regardless of what the mix wrote there).
    """
    batch, q_len, heads, dim = q.shape
    m = jnp.full((batch, heads, q_len, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((batch, heads, q_len, 1), jnp.float32)
    # Head-pair accumulator: [B, src_head i, val_head j, Lq, D].
    acc = jnp.zeros((batch, heads, heads, q_len, dim), jnp.float32)
    masked = valid_len is not None
    origin = jax.lax.axis_index(axis_name) if masked else None
    w_pre32 = w_pre.astype(jnp.float32)

    def one_block(state, k_blk, v_blk, origin):
        m, l, acc = state
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        ) * scale
        # Pre-softmax mix (TalkingHeadsBlock convention: out_i = Σ_h W[h,i] x_h).
        s = jnp.einsum("hi,bhqk->biqk", w_pre32, s)
        if masked:
            s = _mask_key_block(s, origin, k_blk.shape[1], valid_len)
        m_new, l_new, alpha, p = _online_softmax_update(m, l, s, masked)
        # [B,i,Lq,K] × [B,K,j,D] → [B,i,j,Lq,D]
        pv = jnp.einsum(
            "biqk,bkjd->bijqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        # alpha: [B,i,Lq,1] → broadcast over (j, D) in [B,i,j,Lq,D].
        return m_new, l_new, acc * alpha[:, :, None, :, :] + pv

    m, l, acc = _ring_loop(
        k, v, origin, (m, l, acc), one_block,
        axis_name=axis_name, axis_size=axis_size,
    )
    if masked:
        l = _guard_zero_denominator(l)
    # out[b,q,j,d] = Σ_i Wpost[i,j] · acc[b,i,j,q,d] / l[b,i,q]
    normed = acc / l[:, :, None, :, :]
    out = jnp.einsum("ij,bijqd->bqjd", w_post.astype(jnp.float32), normed)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-mode ring: each ring step runs the fused Pallas kernel on the local
# (Q, K_blk, V_blk) pair and the normalized partials are merged online with
# their logsumexps — per-device memory stays O(L_loc·D + H·L_loc), never
# O(L_loc²), in BOTH directions:
#
#   forward   o = Σ_i softmax-partial_i merged by lse_i (exact)
#   backward  re-stream the ring with the GLOBAL lse: p_blk = exp(s − lse)
#             is the globally-normalized probability block, so the blocked
#             backward kernels yield dq partials (summed locally) and
#             dk/dv partials that ride the ring home in carried f32
#             accumulators (one full rotation returns them to their owner).
#
# Autodiff of the dense ring loop would instead save every per-step
# [B,H,L_loc,L_loc] probability block — O(L_loc·L) per device. The
# custom_vjp contains the ppermutes, so it composes with shard_map.
# ---------------------------------------------------------------------------


def _flash_ring_forward_steps(q, k, v, *, axis_name, axis_size, scale,
                              block_q, block_kv, interpret):

    batch, q_len, heads, dim = q.shape
    acc = jnp.zeros((batch, q_len, heads, dim), jnp.float32)
    m = jnp.full((batch, heads, q_len), _NEG_INF, jnp.float32)
    denom = jnp.zeros((batch, heads, q_len), jnp.float32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for step in range(axis_size):
        o_blk, lse_pad = _fa._flash_forward(
            q, k, v, None, scale, block_q, block_kv, interpret, with_lse=True
        )
        lse_blk = lse_pad[:, :q_len, 0].reshape(batch, heads, q_len)
        m_new = jnp.maximum(m, lse_blk)
        w_old = jnp.exp(m - m_new)
        w_blk = jnp.exp(lse_blk - m_new)
        # weights are [B,H,Lq] → broadcast over the [B,Lq,H,D] partials.
        to_q = lambda x: jnp.transpose(x, (0, 2, 1))[..., None]
        acc = acc * to_q(w_old) + o_blk.astype(jnp.float32) * to_q(w_blk)
        denom = denom * w_old + w_blk
        m = m_new
        if step + 1 < axis_size:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)

    out = (acc / jnp.transpose(denom, (0, 2, 1))[..., None]).astype(q.dtype)
    lse_global = m + jnp.log(denom)  # [B, H, Lq] f32
    return out, lse_global


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, axis_size, scale, block_q, block_kv,
                interpret):
    out, _ = _flash_ring_forward_steps(
        q, k, v, axis_name=axis_name, axis_size=axis_size, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out


def _ring_flash_fwd(q, k, v, axis_name, axis_size, scale, block_q, block_kv,
                    interpret):
    out, lse = _flash_ring_forward_steps(
        q, k, v, axis_name=axis_name, axis_size=axis_size, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, axis_size, scale, block_q, block_kv,
                    interpret, residuals, g):

    q, k, v, out, lse = residuals
    lse_pad = _fa.lse_padded_layout(lse, q.shape[1], block_q)

    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for _ in range(axis_size):
        dq_p, dk_b, dv_b = _fa._flash_backward_pallas(
            q, k, v, out, lse_pad, g, scale, block_q, block_kv, interpret
        )
        dq = dq + dq_p.astype(jnp.float32)
        dk = dk + dk_b.astype(jnp.float32)
        dv = dv + dv_b.astype(jnp.float32)
        # Rotate K/V together with their gradient accumulators: after the
        # full loop (axis_size rotations) each lands back on its owner.
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        dk = jax.lax.ppermute(dk, axis_name, perm)
        dv = jax.lax.ppermute(dv, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    mesh: Mesh,
    seq_axis: str = SEQ_AXIS,
    batch_axis: Optional[str] = None,
    scale: Optional[float] = None,
    backend: str = "xla",
    block_q: int = 512,
    block_kv: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Exact attention over sequence-sharded inputs.

    Args:
      query/key/value: global ``[B, L, H, D]`` arrays; ``L`` must divide by
        the ``seq_axis`` mesh size. Under jit the arrays should already be
        sharded ``P(batch_axis, seq_axis, None, None)``; calling it on
        unsharded host arrays also works (shard_map partitions them).
      mesh: mesh containing ``seq_axis`` (and optionally ``batch_axis``).
      scale: logits scale, default ``D ** -0.5``.
      backend: ``'xla'`` — dense per-block logits (numerics reference);
        ``'pallas'`` — each ring step runs the fused flash kernel and the
        blocked backward re-streams the ring, so nothing O(L_loc²) exists
        on any device in either direction (the configuration for truly
        long contexts; see module comment).

    Returns:
      ``[B, L, H, D]``, sharded like the query.
    """
    if scale is None:
        scale = query.shape[-1] ** -0.5
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown ring attention backend: {backend!r}")
    axis_size = mesh.shape[seq_axis]
    if query.shape[1] % axis_size:
        raise ValueError(
            f"sequence length {query.shape[1]} not divisible by "
            f"{seq_axis}={axis_size}"
        )
    spec = P(batch_axis, seq_axis, None, None)
    if backend == "pallas":
        # positional args only: custom_vjp's nondiff_argnums handling
        # rejects keywords.
        fscale = float(scale)

        def shard_fn(q, k, v):
            return _ring_flash(
                q, k, v, seq_axis, axis_size, fscale, block_q, block_kv,
                interpret,
            )
    else:
        shard_fn = functools.partial(
            _ring_shard_fn,
            axis_name=seq_axis,
            axis_size=axis_size,
            scale=float(scale),
        )
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(query, key, value)
