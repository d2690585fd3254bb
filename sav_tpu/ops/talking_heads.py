"""Fused talking-heads attention (CaiT trunk) — Pallas TPU kernel.

Talking-heads attention (reference: /root/reference/models/layers/attentions/
talking_heads.py:5-14 applied at attention.py:44-52) mixes attention *logits*
across heads before the softmax and mixes the *probabilities* after it:

    s'_i = Σ_h W_pre[h, i] · s_h        (pre-softmax head mix)
    p_i  = softmax(s'_i)
    p'_i = Σ_h W_post[h, i] · p_h       (post-softmax head mix)
    out_i = p'_i · V_i

The head coupling breaks the per-head independence the generic flash kernel
relies on, so this kernel keeps **all heads of one batch element in a single
grid cell** and mixes them in VMEM. CaiT's talking-heads trunk runs at short
sequence lengths by design (196 tokens for the named CaiT configs), so the
whole K/V fits one block and the softmax is exact row-wise — no online
accumulation needed. The ``[B, H, L, L]`` logits never exist in HBM in
either direction: the backward is also a blocked Pallas kernel
(:func:`_th_bwd_kernel`) that recomputes S/P/P' in VMEM and resolves the
4-way head-mix coupling with elementwise tile reductions for the ``[H, H]``
gradients (no extra matmuls). Shapes beyond its VMEM budget
(:func:`fused_bwd_eligible`) fall back to a dense XLA recompute with
autodiff-identical numerics.

The ``[H, H]`` mixing matrices ride in SMEM and are read as scalars.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sav_tpu.ops import _backend

_NEG_INF = float("-inf")

# Soft cap on the kernel's VMEM working set. The dominant terms per grid
# cell are the per-head logits+probs tiles (2 · H · block_q · kv_len_p · 4 B
# live at once) plus the whole K/V (2 · H · kv_len_p · dim_p · 2 B); the
# budget leaves headroom under the ~16 MB/core VMEM.
VMEM_BUDGET_BYTES = 10 << 20
_DEFAULT_BLOCK_Q = 256


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def fused_eligible(heads: int, kv_len: int, dim: int,
                   block_q: int = _DEFAULT_BLOCK_Q) -> bool:
    """Whether the all-heads-in-cell kernel fits the VMEM budget.

    Used by the ``'auto'`` dispatch so ineligible shapes (many heads ×
    long kv) fall back to XLA instead of failing Mosaic VMEM allocation."""
    kv_len_p = _round_up(kv_len, 128)
    dim_p = _round_up(dim, 128)
    block_q = min(block_q, _round_up(kv_len, 16))
    logits = 2 * heads * block_q * kv_len_p * 4
    kv = 2 * heads * kv_len_p * dim_p * 2
    qo = 2 * heads * block_q * dim_p * 2
    return logits + kv + qo <= VMEM_BUDGET_BYTES


def _th_kernel(q_ref, k_ref, v_ref, wpre_ref, wpost_ref, o_ref, *,
               heads: int, scale: float, kv_len: int, kv_len_p: int):
    """One grid cell = all heads of one batch element × one q block."""
    logits = []
    for h in range(heads):
        s = jax.lax.dot_general(
            q_ref[0, h], k_ref[0, h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        logits.append(s * scale)

    col = jax.lax.broadcasted_iota(jnp.int32, logits[0].shape, 1)
    probs = []
    for i in range(heads):
        # Pre-softmax mix. Padded kv columns hold Σ_h w·0 = 0 garbage —
        # masked to −inf *after* the mix, exactly where the reference's
        # dense mask would sit.
        mixed = logits[0] * wpre_ref[0, i]
        for h in range(1, heads):
            mixed += logits[h] * wpre_ref[h, i]
        if kv_len != kv_len_p:
            mixed = jnp.where(col < kv_len, mixed, _NEG_INF)
        m = jnp.max(mixed, axis=-1, keepdims=True)
        p = jnp.exp(mixed - m)
        probs.append(p / jnp.sum(p, axis=-1, keepdims=True))

    for i in range(heads):
        post = probs[0] * wpost_ref[0, i]
        for h in range(1, heads):
            post += probs[h] * wpost_ref[h, i]
        v = v_ref[0, i]
        o_ref[0, i] = jax.lax.dot_general(
            post.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def _th_forward(q, k, v, w_pre, w_post, scale, block_q, interpret):
    """q/k/v ``[B, L, H, D]``; w_pre/w_post ``[H, H]`` float32."""
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    if interpret is None:
        interpret = _backend.default_interpret()

    def to_bhld(x):
        return jnp.transpose(x, (0, 2, 1, 3))  # [B, H, L, D]

    dim_p = _round_up(dim, 128)
    block_q = min(block_q, _round_up(q_len, 16))
    q_len_p = _round_up(q_len, block_q)
    kv_len_p = _round_up(kv_len, 128)

    def pad4(x, lp):
        return jnp.pad(
            x, ((0, 0), (0, 0), (0, lp - x.shape[2]), (0, dim_p - x.shape[3]))
        )

    qf = pad4(to_bhld(q), q_len_p)
    kf = pad4(to_bhld(k), kv_len_p)
    vf = pad4(to_bhld(v), kv_len_p)

    kernel = functools.partial(
        _th_kernel,
        heads=heads,
        scale=scale,
        kv_len=kv_len,
        kv_len_p=kv_len_p,
    )
    out = pl.pallas_call(
        kernel,
        grid=(batch, q_len_p // block_q),
        in_specs=[
            pl.BlockSpec(
                (1, heads, block_q, dim_p), lambda b, i: (b, 0, i, 0)
            ),
            pl.BlockSpec(
                (1, heads, kv_len_p, dim_p), lambda b, i: (b, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, heads, kv_len_p, dim_p), lambda b, i: (b, 0, 0, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, heads, block_q, dim_p), lambda b, i: (b, 0, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, heads, q_len_p, dim_p), q.dtype
        ),
        interpret=interpret,
    )(qf, kf, vf, w_pre.astype(jnp.float32), w_post.astype(jnp.float32))
    out = out[:, :, :q_len, :dim]
    return jnp.transpose(out, (0, 2, 1, 3))


def fused_bwd_eligible(heads: int, q_len: int, kv_len: int, dim: int,
                       block_q: int = _DEFAULT_BLOCK_Q) -> bool:
    """Whether the blocked backward's larger VMEM working set fits.

    The backward keeps ~6 per-head f32 logit-sized tiles live at once
    (S, P, P', dP', dS', dS) plus Q/K/V/dO and the dk/dv accumulators —
    stricter than the forward's 2. ``block_q`` is capped by ``q_len``
    exactly as :func:`_th_backward` caps it, so the estimate tracks the
    kernel's real tile size (a single-query class-attention call is far
    cheaper than a square trunk call). Used by the backward dispatch so
    shapes beyond the budget recompute on the XLA path instead."""
    kv_len_p = _round_up(kv_len, 128)
    dim_p = _round_up(dim, 128)
    block_q = min(block_q, _round_up(q_len, 16))
    logit_tiles = 6 * heads * block_q * kv_len_p * 4
    qkv = 4 * heads * kv_len_p * dim_p * 2
    accum = 2 * heads * kv_len_p * dim_p * 4
    return logit_tiles + qkv + accum <= VMEM_BUDGET_BYTES


def _th_bwd_kernel(q_ref, k_ref, v_ref, g_ref, wpre_ref, wpost_ref,
                   dq_ref, dk_ref, dv_ref, dwpre_ref, dwpost_ref, *,
                   heads: int, scale: float, kv_len: int, kv_len_p: int):
    """Blocked talking-heads backward; one cell = all heads of one batch
    element × one q block. No ``[B, H, L, L]`` tensor ever reaches HBM.

    Recomputes S/P/P' flash-style from the q/k residuals, then:

      dP'_i = dO_i·V_iᵀ                 dV_i += P'_iᵀ·dO_i
      dWpost[h,i] += ⟨P_h, dP'_i⟩       dP_h = Σ_i Wpost[h,i]·dP'_i
      dS'_i = P_i ⊙ (dP_i − rowsum(P_i⊙dP_i))
      dWpre[h,i] += ⟨S_h, dS'_i⟩        dS_h = Σ_i Wpre[h,i]·dS'_i
      dQ_h = scale·dS_h·K_h             dK_h += scale·dS_hᵀ·Q_h

    The ⟨·,·⟩ head-mix gradients are elementwise VPU reductions (no
    matmul), and every matmul runs storage-dtype-in / f32-accumulate on
    the MXU. dk/dv/dW accumulate in their output blocks across the
    (sequential, innermost) q-block grid axis.

    Mosaic cannot store rank-0 values to VMEM, so the per-(h, i) scalar
    mix-weight gradients are scattered into an ``[H, H]`` register tile
    via iota masks and written with one full-block store per cell."""
    qi = pl.program_id(1)
    mix_rows = jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 0)
    mix_cols = jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 1)

    def at_cell(h, i, val):
        # rank-0 `val` broadcast into the (h, i) slot of an [H, H] tile.
        return jnp.where((mix_rows == h) & (mix_cols == i), val, 0.0)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        dwpre_ref[...] = jnp.zeros_like(dwpre_ref)
        dwpost_ref[...] = jnp.zeros_like(dwpost_ref)

    col = None
    # Recompute per-head raw logits (padded kv columns give exact 0 —
    # K is zero-padded — matching the forward's pre-mix values).
    s = []
    for h in range(heads):
        sh = jax.lax.dot_general(
            q_ref[0, h], k_ref[0, h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s.append(sh)
    if kv_len != kv_len_p:
        col = jax.lax.broadcasted_iota(jnp.int32, s[0].shape, 1)

    probs = []
    for i in range(heads):
        mixed = s[0] * wpre_ref[0, i]
        for h in range(1, heads):
            mixed += s[h] * wpre_ref[h, i]
        if col is not None:
            mixed = jnp.where(col < kv_len, mixed, _NEG_INF)
        m = jnp.max(mixed, axis=-1, keepdims=True)
        p = jnp.exp(mixed - m)
        probs.append(p / jnp.sum(p, axis=-1, keepdims=True))

    # dP' and dV per output head; dWpost from direct tile reductions.
    dpost = []
    dwpost_acc = jnp.zeros((heads, heads), jnp.float32)
    for i in range(heads):
        g = g_ref[0, i]
        vi = v_ref[0, i]
        dpi = jax.lax.dot_general(  # dO_i · V_iᵀ : [bq, Lkv]
            g, vi, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpost.append(dpi)
        post = probs[0] * wpost_ref[0, i]
        for h in range(1, heads):
            post += probs[h] * wpost_ref[h, i]
        dv_ref[0, i] += jax.lax.dot_general(  # P'_iᵀ · dO_i : [Lkv, D]
            post.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for h in range(heads):
            dwpost_acc += at_cell(h, i, jnp.sum(probs[h] * dpi))
    dwpost_ref[0] += dwpost_acc

    # Softmax backward per head, then the pre-mix couplings.
    ds_mixed = []
    dwpre_acc = jnp.zeros((heads, heads), jnp.float32)
    for i in range(heads):
        dp = dpost[0] * wpost_ref[i, 0]
        for j in range(1, heads):
            dp += dpost[j] * wpost_ref[i, j]
        pi = probs[i]
        ds = pi * (dp - jnp.sum(pi * dp, axis=-1, keepdims=True))
        ds_mixed.append(ds)
        for h in range(heads):
            dwpre_acc += at_cell(h, i, jnp.sum(s[h] * ds))
    dwpre_ref[0] += dwpre_acc

    for h in range(heads):
        dsh = ds_mixed[0] * wpre_ref[h, 0]
        for i in range(1, heads):
            dsh += ds_mixed[i] * wpre_ref[h, i]
        dsh_lo = dsh.astype(k_ref.dtype)
        dq_ref[0, h] = (
            jax.lax.dot_general(
                dsh_lo, k_ref[0, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
        ).astype(dq_ref.dtype)
        dk_ref[0, h] += (
            jax.lax.dot_general(
                dsh_lo, q_ref[0, h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
        )


def _th_backward(q, k, v, w_pre, w_post, g, scale, block_q, interpret):
    """Pallas-call wrapper for the blocked backward. Layouts as forward."""
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    if interpret is None:
        interpret = _backend.default_interpret()

    def to_bhld(x):
        return jnp.transpose(x, (0, 2, 1, 3))

    dim_p = _round_up(dim, 128)
    block_q = min(block_q, _round_up(q_len, 16))
    q_len_p = _round_up(q_len, block_q)
    kv_len_p = _round_up(kv_len, 128)

    def pad4(x, lp):
        return jnp.pad(
            x, ((0, 0), (0, 0), (0, lp - x.shape[2]), (0, dim_p - x.shape[3]))
        )

    qf = pad4(to_bhld(q), q_len_p)
    kf = pad4(to_bhld(k), kv_len_p)
    vf = pad4(to_bhld(v), kv_len_p)
    # Zero-padded cotangent rows make the padded q rows contribute exact
    # zeros to dk/dv/dW (their dP' and dS' rows vanish).
    gf = pad4(to_bhld(g.astype(q.dtype)), q_len_p)

    num_q_blocks = q_len_p // block_q
    kernel = functools.partial(
        _th_bwd_kernel,
        heads=heads,
        scale=scale,
        kv_len=kv_len,
        kv_len_p=kv_len_p,
    )
    whole = lambda b, i: (b, 0, 0, 0)
    dq, dk, dv, dwpre, dwpost = pl.pallas_call(
        kernel,
        grid=(batch, num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, dim_p), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, heads, kv_len_p, dim_p), whole),
            pl.BlockSpec((1, heads, kv_len_p, dim_p), whole),
            pl.BlockSpec((1, heads, block_q, dim_p), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, block_q, dim_p), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, heads, kv_len_p, dim_p), whole),
            pl.BlockSpec((1, heads, kv_len_p, dim_p), whole),
            pl.BlockSpec((1, heads, heads), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, heads, heads), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, q_len_p, dim_p), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, kv_len_p, dim_p), jnp.float32),
            jax.ShapeDtypeStruct((batch, heads, kv_len_p, dim_p), jnp.float32),
            jax.ShapeDtypeStruct((batch, heads, heads), jnp.float32),
            jax.ShapeDtypeStruct((batch, heads, heads), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, w_pre.astype(jnp.float32), w_post.astype(jnp.float32))

    def from_bhld(x, l):
        return jnp.transpose(x[:, :, :l, :dim], (0, 2, 1, 3))

    dq = from_bhld(dq, q_len)
    dk = from_bhld(dk, kv_len).astype(k.dtype)
    dv = from_bhld(dv, kv_len).astype(v.dtype)
    dwpre = jnp.sum(dwpre, axis=0).astype(w_pre.dtype)
    dwpost = jnp.sum(dwpost, axis=0).astype(w_post.dtype)
    return dq, dk, dv, dwpre, dwpost


def _th_dense_reference(q, k, v, w_pre, w_post, scale):
    """Dense XLA talking-heads attention (backward recompute + numerics
    cross-check). Mirrors sav_tpu.models.layers.attention.talking_heads_attention."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q * jnp.asarray(scale, q.dtype), k,
        preferred_element_type=jnp.float32,
    )
    s = jnp.einsum("hi,bhqk->biqk", w_pre.astype(jnp.float32), s)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.einsum("hi,bhqk->biqk", w_post.astype(jnp.float32), p)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _th(q, k, v, w_pre, w_post, scale, block_q, interpret):
    return _th_forward(q, k, v, w_pre, w_post, scale, block_q, interpret)


def _th_fwd(q, k, v, w_pre, w_post, scale, block_q, interpret):
    out = _th_forward(q, k, v, w_pre, w_post, scale, block_q, interpret)
    return out, (q, k, v, w_pre, w_post)


def _th_bwd(scale, block_q, interpret, residuals, g):
    q, k, v, w_pre, w_post = residuals
    heads, dim = q.shape[2], q.shape[3]
    if fused_bwd_eligible(heads, q.shape[1], k.shape[1], dim, block_q):
        return _th_backward(q, k, v, w_pre, w_post, g, scale, block_q, interpret)
    # Shapes beyond the backward's VMEM budget: dense XLA recompute
    # (numerics identical to autodiff; the [B,H,L,L] cost returns, but
    # only where the blocked kernel cannot run).
    _, vjp = jax.vjp(
        lambda q, k, v, wp, wq: _th_dense_reference(q, k, v, wp, wq, scale),
        q, k, v, w_pre, w_post,
    )
    return vjp(g)


_th.defvjp(_th_fwd, _th_bwd)


def flash_talking_heads_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    w_pre: jax.Array,
    w_post: jax.Array,
    *,
    scale: Optional[float] = None,
    block_q: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused talking-heads attention. See module docstring.

    Args:
      query/key/value: ``[B, L, H, D]``.
      w_pre / w_post: ``[H, H]`` learned head-mixing matrices
        (``mixed_i = Σ_h W[h, i] · head_h``, the reference's einsum
        ``'h i, b h ... -> b i ...'``).
      scale: logit scale, default ``D ** -0.5``.

    Raises:
      ValueError: shape beyond the VMEM budget (whole-K/V-in-VMEM design;
        talking-heads models run short trunks — use the XLA path otherwise).
    """
    if query.ndim != 4:
        raise ValueError(f"expected [B, L, H, D] inputs, got {query.shape}")
    _, kv_len, heads, dim = key.shape
    if not fused_eligible(heads, kv_len, dim, block_q):
        raise ValueError(
            f"fused talking-heads holds all heads' K/V and logits in VMEM; "
            f"heads={heads}, kv_len={kv_len}, dim={dim} exceeds the "
            f"{VMEM_BUDGET_BYTES >> 20} MB budget — use the XLA path"
        )
    if scale is None:
        scale = query.shape[-1] ** -0.5
    return _th(query, key, value, w_pre, w_post, float(scale), block_q, interpret)
