"""Single-pass fused attention for sequences that fit one KV block in VMEM.

The model zoo's headline shapes (DeiT/ViT L=197, CaiT L=197, TNT outer
L=785) hold the whole key/value sequence in one VMEM block, so the
attention core needs no online-softmax carry: QK → scale/bias → plain
softmax (the whole row is resident) → PV, bf16-in/f32-accumulate, and the
``[B, H, Lq, Lk]`` logits/probabilities never exist in HBM in either
direction. On the dense XLA path those tensors were half of DeiT-S's
train step on the v5e (PERF.md §6, PR 25).

Layout: the kernel reads and writes the model's own ``[B, L, H, D]``
arrays in place, seen as ``[B, L, H·D]`` (a free reshape). One grid cell
holds ``block_b`` batch elements × one q block × the WHOLE kv sequence,
all heads; a head is a static lane slice of width D. Nothing is padded,
transposed or copied outside the kernel: L and D are padded to the
hardware tiles only inside VMEM, by the compiler.

Differentiation: ``fused_attention`` is a ``jax.custom_vjp``. Without a
bias the backward is a SINGLE fused Pallas kernel: the forward saves only
the per-row logsumexp, compact as ``[B, H, Lq]`` float32; the backward
recomputes the probabilities from it in VMEM, computes ``delta =
Σ_d dO·O`` itself, and emits dq, dk and dv directly (dk/dv through VMEM
accumulators when there are several q blocks). With a bias that requires
a gradient the backward falls back to the XLA flash-style recompute
shared with :mod:`sav_tpu.ops.flash_attention` (the dense ``ds`` is
unavoidable for a dense dbias).

Block configs (``block_q``, ``block_b``) default to the static heuristics
below; the measured per-shape winners come from the tune cache via the
``auto`` dispatcher (:mod:`sav_tpu.ops.attn_tuning`).

On non-TPU backends the kernels run in Pallas interpreter mode, so the
same code path is testable on the CPU mesh (tests/test_fused_attention.py
cross-checks fwd + grads against ``xla_attention``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sav_tpu.ops import _backend

from sav_tpu.ops.flash_attention import _dense_recompute_bwd, _round_up

# Default q tile; a shorter sequence is one block of its own length.
DEFAULT_BLOCK_Q = 256

# Per-grid-cell VMEM working-set budget for eligibility/auto block_b
# selection, and the scoped limit the kernels are compiled under (a v5e
# core has 128 MiB of VMEM; Mosaic's default scoped limit is 16 MiB). The
# estimator counts the double-buffered blocks and one head's temporaries
# and leaves the rest of the limit to what it cannot see. The dispatcher's
# "fits one KV block" band is defined as: the *backward* working set (the
# larger of the two passes) of one batch element fits the budget.
FUSED_VMEM_BUDGET = 14 * 2**20
_VMEM_LIMIT = 32 * 2**20

# The logsumexp rides one 128-lane tile with a head on each lane.
_MAX_HEADS = 128


def _clamp_block_q(q_len: int, block_q: int) -> int:
    """One block of the sequence's own length when it fits, else a
    multiple of 128 (the compact logsumexp's lane tile)."""
    return q_len if q_len <= block_q else _round_up(block_q, 128)


def fused_vmem_bytes(
    q_len: int,
    kv_len: int,
    dim: int,
    *,
    heads: int = 1,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = 1,
    itemsize: int = 2,
) -> int:
    """Estimated per-grid-cell VMEM working set of the fused *backward*
    (the larger pass): q/o/do in and dq out at the q block, k/v in and
    dk/dv out at the whole kv sequence, every block double-buffered and
    ``heads·dim`` wide; the f32 dk/dv accumulators when there are several
    q blocks; and one head's f32 logits-tile temporaries. Intentionally
    conservative: real Mosaic allocation is the arbiter on chip."""
    width = _round_up(heads * dim, 128)
    bq = _round_up(_clamp_block_q(q_len, block_q), 16)
    kv = _round_up(kv_len, 16)
    total = 2 * block_b * width * itemsize * (4 * bq + 4 * kv)
    if q_len > block_q:
        total += 2 * block_b * kv * width * 4  # dk/dv f32 accumulators
    total += 4 * bq * _round_up(kv, 128) * 4  # s, p, dp, ds of one head
    return total


def fused_eligible(
    q_len: int,
    kv_len: int,
    dim: int,
    *,
    heads: int = 1,
    block_q: int = DEFAULT_BLOCK_Q,
    itemsize: int = 2,
    budget: int = FUSED_VMEM_BUDGET,
) -> bool:
    """True when the whole KV sequence of one batch element (all
    ``heads``) fits one VMEM block under the budget at block_b=1."""
    return heads <= _MAX_HEADS and (
        fused_vmem_bytes(
            q_len, kv_len, dim,
            heads=heads, block_q=block_q, block_b=1, itemsize=itemsize,
        )
        <= budget
    )


def _pick_block_b(
    batch: int, q_len: int, kv_len: int, heads: int, dim: int, *,
    block_q: int, itemsize: int,
) -> int:
    """Largest of (8, 4, 2, 1) dividing the batch whose working set stays
    under the VMEM budget. Several batch elements per grid cell amortize
    the grid-cell issue overhead and lengthen the DMAs."""
    for bb in (8, 4, 2):
        if batch % bb == 0 and (
            fused_vmem_bytes(
                q_len, kv_len, dim, heads=heads,
                block_q=block_q, block_b=bb, itemsize=itemsize,
            )
            <= FUSED_VMEM_BUDGET
        ):
            return bb
    return 1


def _each_batch_element(block_b: int, body) -> None:
    """Run ``body(bi)`` for every batch element of the grid cell: inline
    for one, a device loop for several (the body holds an unrolled loop
    over heads already)."""
    if block_b == 1:
        body(0)
        return

    def step(bi, carry):
        body(bi)
        return carry

    jax.lax.fori_loop(0, block_b, step, 0)


def _scaled(q, scale: float):
    """q·scale, rounded once to q's dtype (the MXU's operand)."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _fused_kernel(
    q_ref,
    k_ref,
    v_ref,
    *rest,
    bias_dims: Optional[tuple],
    with_lse: bool,
    scale: float,
    heads: int,
    dim: int,
    block_b: int,
):
    """One grid cell = ``block_b`` batch elements × one q block × the
    WHOLE kv sequence, every head: plain (single-pass) softmax, no online
    statistics, no scratch carry, no finalize pass. ``bias_dims`` says
    which of the bias block's two leading dims (batch, head) are real and
    which broadcast (size 1)."""
    bias_ref = rest[0] if bias_dims else None
    rest = rest[1 if bias_dims else 0 :]
    if with_lse:
        o_ref, lse_ref, lse_scr = rest
    else:
        (o_ref,) = rest
    block_q = q_ref.shape[1]

    def one(bi):
        if with_lse:
            lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, 128), 1)
            lse = jnp.zeros((block_q, 128), jnp.float32)
        for h in range(heads):
            cols = slice(h * dim, (h + 1) * dim)
            s = jax.lax.dot_general(
                _scaled(q_ref[bi, :, cols], scale), k_ref[bi, :, cols],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )  # [block_q, kv_len]
            if bias_dims:
                s = s + bias_ref[
                    bi if bias_dims[0] else 0, h if bias_dims[1] else 0
                ].astype(jnp.float32)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[bi, :, cols],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            o_ref[bi, :, cols] = (acc * (1.0 / l)).astype(o_ref.dtype)
            if with_lse:
                lse = jnp.where(lane == h, m + jnp.log(l), lse)
        if with_lse:
            # Rows on sublanes, heads on lanes -> heads on sublanes, rows
            # on lanes: the compact [H, Lq] residual, through an aligned
            # scratch tile the transpose unit takes.
            lse_scr[0:block_q, :] = lse
            lse_ref[bi] = lse_scr[...].T[0:heads, 0:block_q]

    _each_batch_element(block_b, one)


def _bias_spec(bias: jax.Array, batch: int, heads: int, block_b: int,
               block_q: int):
    """A bias is read at its OWN broadcast rank — (1,1), (1,H), (B,1)
    biases are never materialized to the full [B, H, Lq, Lk] (that tensor
    is the HBM tax this kernel exists to avoid). Returns the BlockSpec of
    the 4-D bias and which of its two leading dims are real."""
    if bias.shape[0] not in (1, batch) or bias.shape[1] not in (1, heads):
        raise ValueError(
            f"bias {bias.shape} does not broadcast to [{batch}, {heads}, Lq, Lk]"
        )
    dims = (bias.shape[0] != 1, bias.shape[1] != 1)
    spec = pl.BlockSpec(
        (block_b if dims[0] else 1, bias.shape[1], block_q, bias.shape[3]),
        lambda b, i: (b if dims[0] else 0, 0, i, 0),
    )
    return spec, dims


def _geometry(q, k, block_q: int, block_b: Optional[int]):
    """(block_q, block_b, number of q blocks) both passes run with: one
    source, so the backward reads the residual in the forward's blocks."""
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    block_q = _clamp_block_q(q_len, block_q)
    if block_b is None:
        block_b = _pick_block_b(
            batch, q_len, kv_len, heads, dim,
            block_q=block_q, itemsize=q.dtype.itemsize,
        )
    elif batch % block_b != 0:
        block_b = 1
    return block_q, block_b, pl.cdiv(q_len, block_q)


def _fused_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array],
    scale: float,
    block_q: int,
    block_b: Optional[int],
    interpret: Optional[bool],
    with_lse: bool = False,
):
    """Layout in/out ``[B, L, H, D]``, read in place as ``[B, L, H·D]``.
    With ``with_lse`` also returns the ``[B, H, Lq]`` float32 logsumexp."""
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    if interpret is None:
        interpret = _backend.default_interpret()
    block_q, block_b, num_q_blocks = _geometry(q, k, block_q, block_b)
    width = heads * dim

    qspec = pl.BlockSpec((block_b, block_q, width), lambda b, i: (b, i, 0))
    kspec = pl.BlockSpec((block_b, kv_len, width), lambda b, i: (b, 0, 0))
    in_specs = [qspec, kspec, kspec]
    args = [
        q.reshape(batch, q_len, width),
        k.reshape(batch, kv_len, width),
        v.reshape(batch, kv_len, width),
    ]
    bias_dims = None
    if bias is not None:
        bias = jnp.broadcast_to(bias, bias.shape[:-2] + (q_len, kv_len))
        bias_spec, bias_dims = _bias_spec(bias, batch, heads, block_b, block_q)
        in_specs.append(bias_spec)
        args.append(bias)

    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct((batch, q_len, width), q.dtype)]
    scratch_shapes = []
    if with_lse:
        out_specs.append(
            pl.BlockSpec((block_b, heads, block_q), lambda b, i: (b, 0, i))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((batch, heads, q_len), jnp.float32)
        )
        scratch_shapes.append(
            pltpu.VMEM((_round_up(block_q, 128), 128), jnp.float32)
        )

    outs = pl.pallas_call(
        functools.partial(
            _fused_kernel,
            bias_dims=bias_dims,
            with_lse=with_lse,
            scale=scale,
            heads=heads,
            dim=dim,
            block_b=block_b,
        ),
        grid=(batch // block_b, num_q_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*args)

    out = outs[0].reshape(batch, q_len, heads, dim)
    if with_lse:
        return out, outs[1]
    return out


def _fused_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, lse_scr, *acc, scale: float,
                      q_len: int, heads: int, dim: int, block_b: int,
                      num_q_blocks: int):
    """SINGLE fused backward: with the whole kv sequence resident, each
    grid cell recomputes its probability tile from the lse residual and
    emits dq directly (no kv-block sweep to accumulate over); dk/dv are
    written directly when the q sequence is one block, and accumulate
    across q blocks in VMEM scratch otherwise — one kernel, not the dq +
    dk/dv pair the multi-block flash backward needs."""
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    if acc:
        dk_acc, dv_acc = acc

        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    ragged = q_len % block_q != 0
    if ragged:
        # The last q block reaches past the sequence: its rows are whatever
        # the buffer held. They write nothing back (dq is clipped) but must
        # add nothing to dk/dv: zero rows of q, o, dO and lse give s = 0,
        # p = 1, dp = delta = 0, so ds = 0 and p^T dO = 0.
        valid = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        ) < q_len

    def rows(x):
        return jnp.where(valid, x, jnp.zeros_like(x)) if ragged else x

    def one(bi):
        # The compact [H, block_q] residual back to rows on sublanes,
        # heads on lanes: column h broadcasts along a head's logits rows.
        lse_scr[0:heads, 0:block_q] = lse_ref[bi]
        lse = rows(lse_scr[...].T[0:block_q, :])
        for h in range(heads):
            cols = slice(h * dim, (h + 1) * dim)
            q = rows(_scaled(q_ref[bi, :, cols], scale))
            k, v = k_ref[bi, :, cols], v_ref[bi, :, cols]
            o, do = rows(o_ref[bi, :, cols]), rows(do_ref[bi, :, cols])
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            p = jnp.exp(s - lse[:, h : h + 1])
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            delta = jnp.sum(
                do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1, keepdims=True,
            )
            ds = p * (dp - delta)
            dq_ref[bi, :, cols] = (
                jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            ).astype(dq_ref.dtype)
            dv = jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk = jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # q carries the scale already
            if acc:
                dv_acc[bi, :, cols] += dv
                dk_acc[bi, :, cols] += dk
            else:
                dv_ref[bi, :, cols] = dv.astype(dv_ref.dtype)
                dk_ref[bi, :, cols] = dk.astype(dk_ref.dtype)

    _each_batch_element(block_b, one)

    if acc:
        @pl.when(qi == num_q_blocks - 1)
        def _write():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _fused_backward(q, k, v, out, lse, g, scale, block_q, block_b,
                    interpret):
    """q/k/v/out/g ``[B, L, H, D]``; lse the forward's ``[B, H, Lq]``."""
    if interpret is None:
        interpret = _backend.default_interpret()
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    block_q, block_b, num_q_blocks = _geometry(q, k, block_q, block_b)
    width = heads * dim

    qspec = pl.BlockSpec((block_b, block_q, width), lambda b, i: (b, i, 0))
    kspec = pl.BlockSpec((block_b, kv_len, width), lambda b, i: (b, 0, 0))
    lspec = pl.BlockSpec((block_b, heads, block_q), lambda b, i: (b, 0, i))
    scratch_shapes = [pltpu.VMEM((128, _round_up(block_q, 128)), jnp.float32)]
    if num_q_blocks > 1:
        scratch_shapes += [
            pltpu.VMEM((block_b, kv_len, width), jnp.float32),
            pltpu.VMEM((block_b, kv_len, width), jnp.float32),
        ]

    def flat(x):
        return x.reshape(batch, x.shape[1], width)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel,
            scale=scale,
            q_len=q_len,
            heads=heads,
            dim=dim,
            block_b=block_b,
            num_q_blocks=num_q_blocks,
        ),
        grid=(batch // block_b, num_q_blocks),
        in_specs=[qspec, kspec, kspec, qspec, qspec, lspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((batch, q_len, width), q.dtype),
            jax.ShapeDtypeStruct((batch, kv_len, width), k.dtype),
            jax.ShapeDtypeStruct((batch, kv_len, width), v.dtype),
        ],
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(flat(q), flat(k), flat(v), flat(out), flat(g), lse)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused(q, k, v, bias, scale, block_q, block_b, interpret):
    return _fused_forward(q, k, v, bias, scale, block_q, block_b, interpret)


def _fused_fwd(q, k, v, bias, scale, block_q, block_b, interpret):
    if bias is None:
        out, lse = _fused_forward(
            q, k, v, bias, scale, block_q, block_b, interpret, with_lse=True
        )
        return out, (q, k, v, bias, out, lse)
    out = _fused_forward(q, k, v, bias, scale, block_q, block_b, interpret)
    return out, (q, k, v, bias, None, None)


def _fused_vjp_bwd(scale, block_q, block_b, interpret, residuals, g):
    """No bias → the single fused Pallas backward. A bias gradient needs
    the dense ``ds`` (its own size is O(L²) by construction), so that path
    shares flash_attention's XLA recompute."""
    q, k, v, bias, out, lse = residuals
    if bias is None:
        dq, dk, dv = _fused_backward(
            q, k, v, out, lse, g, scale, block_q, block_b, interpret
        )
        return dq, dk, dv, None
    return _dense_recompute_bwd(q, k, v, bias, g, scale)


_fused.defvjp(_fused_fwd, _fused_vjp_bwd)


def fused_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused single-pass short-sequence attention.

    Args:
      query: ``[B, q_len, heads, head_dim]``.
      key, value: ``[B, kv_len, heads, head_dim]``. The whole kv sequence
        of a batch element must fit one VMEM block (:func:`fused_eligible`).
      bias: optional additive logits bias broadcastable to
        ``[B, heads, q_len, kv_len]``.
      scale: logit scale, default ``head_dim ** -0.5``.
      block_q: q tile for sequences longer than it (rounded up to a
        multiple of 128); a shorter sequence is one block. Per-shape
        measured winners come from the tune cache via the ``auto``
        dispatcher.
      block_b: batch elements (all their heads) per grid cell; None =
        largest of (8, 4, 2, 1) under the VMEM budget.
      interpret: force Pallas interpreter mode; default = auto (on for
        non-TPU backends).

    Returns:
      ``[B, q_len, heads, head_dim]`` in the query dtype.
    """
    if query.ndim != 4 or key.ndim != 4 or value.ndim != 4:
        raise ValueError(
            f"fused attention expects [B, L, H, D] inputs, got "
            f"{query.shape}/{key.shape}/{value.shape}"
        )
    if bias is not None and bias.ndim != 4:
        raise ValueError(f"bias must be 4-D broadcastable, got {bias.shape}")
    q_len, kv_len = query.shape[1], key.shape[1]
    heads, dim = query.shape[2:]
    fits = dict(heads=heads, block_q=block_q, itemsize=query.dtype.itemsize)
    if not fused_eligible(q_len, kv_len, dim, **fits):
        raise ValueError(
            f"kv_len={kv_len} (heads={heads}, dim={dim}) does not fit the "
            f"fused kernel's single-KV-block VMEM budget "
            f"({FUSED_VMEM_BUDGET} bytes, estimate "
            f"{fused_vmem_bytes(q_len, kv_len, dim, **fits)}); "
            "use the flash kernel (backend='pallas') or XLA"
        )
    if scale is None:
        scale = query.shape[-1] ** -0.5
    return _fused(
        query, key, value, bias, float(scale), block_q, block_b, interpret
    )
