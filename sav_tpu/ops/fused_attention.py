"""Single-pass fused attention for sequences that fit one KV block in VMEM.

The model zoo's headline shapes (DeiT/ViT L=197, CaiT L=197, TNT outer
L=785) are exactly where PERF.md §5 measured the online-softmax flash
kernel *losing* to XLA: with L_kv inside a single VMEM block the
multi-pass (max, sum, acc) carry, the per-kv-block grid cells, and the
cross-block finalize are pure overhead. This kernel keeps the flash
*memory* shape — the ``[B, H, Lq, Lk]`` logits/probabilities never exist
in HBM in either direction, which is the 67-of-112 ms HBM tax the dense
XLA path pays at DeiT-S/16 — but computes each ``block_b`` batch·head
slice in ONE grid cell: QK → scale/bias → plain softmax (the whole row is
resident, no running max/sum) → PV, bf16-in/f32-accumulate.

Differentiation: ``fused_attention`` is a ``jax.custom_vjp``. Without a
bias the backward is a SINGLE fused Pallas kernel per (bh slice, q block):
the forward saves only the per-row logsumexp, the backward recomputes the
probabilities from it in VMEM and emits dq directly plus dk/dv through
VMEM accumulators swept over q blocks — no dense logits rematerialized in
HBM. With a bias that requires a gradient the backward falls back to the
XLA flash-style recompute shared with :mod:`sav_tpu.ops.flash_attention`
(the dense ``ds`` is unavoidable for a dense dbias).

Block configs (``block_q``, ``block_b``) default to the static heuristics
below; the measured per-shape winners come from ``tools/attn_tune.py``'s
cache via the ``auto`` dispatcher (:mod:`sav_tpu.ops.attn_tuning`).

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

from sav_tpu.ops.flash_attention import (
    _bwd_prep,
    _dense_recompute_bwd,
    _lanes,
    _round_up,
)

_NEG_INF = float("-inf")

# Default q tile; clamped to round_up(q_len, 16) for short sequences
# (mirrors flash_attention's clamping so padding geometry is shared).
DEFAULT_BLOCK_Q = 256

# Per-grid-cell VMEM working-set budget for eligibility/auto block_b
# selection. v5e-class cores have ~16 MiB of VMEM; Mosaic rejected flash
# configs already at ~half of it (the block_b 16/32 failures, PERF.md §5),
# so the estimator budgets conservatively — 8 MiB — and the dispatcher's
# "fits one KV block" band is defined as: some (block_q, block_b=1)
# config's *backward* working set (the larger of the two passes) fits.
FUSED_VMEM_BUDGET = 8 * 2**20


def _kv_pad(kv_len: int) -> int:
    """The single KV block width: the whole (padded) key/value sequence."""
    return _round_up(kv_len, 16)


def fused_vmem_bytes(
    q_len: int,
    kv_len: int,
    dim: int,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = 1,
    itemsize: int = 2,
) -> int:
    """Estimated per-grid-cell VMEM working set of the fused *backward*
    (the larger pass — q/k/v/do in, dq out, f32 dk/dv accumulators, and the
    f32 logits-tile temporaries the unrolled block_b loop keeps live).
    Intentionally conservative: real Mosaic allocation is the arbiter on
    chip (tools/attn_tune.py records its failures as infeasible)."""
    dim_p = _round_up(dim, 128)
    block_q = min(block_q, _round_up(q_len, 16))
    kv_p = _kv_pad(kv_len)
    tensors = block_b * (block_q + 2 * kv_p) * dim_p * itemsize  # q, k, v
    tensors += block_b * block_q * dim_p * itemsize  # do
    tensors += block_b * block_q * dim_p * itemsize  # dq out
    tensors += 2 * block_b * kv_p * dim_p * 4  # dk/dv f32 accumulators
    tensors += 2 * block_b * block_q * 128 * 4  # lse + delta rows
    tensors += 3 * block_b * block_q * kv_p * 4  # s/p/ds f32 temporaries
    return tensors


def fused_eligible(
    q_len: int,
    kv_len: int,
    dim: int,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    itemsize: int = 2,
    budget: int = FUSED_VMEM_BUDGET,
) -> bool:
    """True when the whole KV sequence fits one VMEM block under the
    budget at block_b=1 (larger block_b only shrinks under the budget by
    never being auto-picked)."""
    return (
        fused_vmem_bytes(
            q_len, kv_len, dim, block_q=block_q, block_b=1, itemsize=itemsize
        )
        <= budget
    )


def _pick_block_b(
    bh: int,
    q_len: int,
    kv_len: int,
    dim: int,
    *,
    block_q: int,
    itemsize: int,
    divisor_of: Optional[int] = None,
) -> int:
    """Largest of (8, 4, 2, 1) dividing bh (and ``divisor_of``, when a
    batch- or head-shared bias needs grid cells that don't straddle batch
    boundaries) whose working set stays under the VMEM budget. Several bh
    slices per grid cell amortize the ~µs grid-cell issue overhead that
    dominates short-L shapes (PERF.md §2)."""
    for bb in (8, 4, 2):
        if bh % bb != 0:
            continue
        if divisor_of is not None and divisor_of % bb != 0:
            continue
        if (
            fused_vmem_bytes(
                q_len, kv_len, dim,
                block_q=block_q, block_b=bb, itemsize=itemsize,
            )
            <= FUSED_VMEM_BUDGET
        ):
            return bb
    return 1


def _fused_kernel(
    q_ref,
    k_ref,
    v_ref,
    *rest,
    has_bias: bool,
    bias_per_slice: bool,
    with_lse: bool,
    scale: float,
    kv_len: int,
    kv_p: int,
    block_b: int,
):
    """One grid cell = ``block_b`` batch·head slices × one q block × the
    WHOLE kv sequence: plain (single-pass) softmax, no online statistics,
    no scratch carry, no finalize pass. ``bias_per_slice`` distinguishes a
    bias block carrying one row per bh slice from a single shared row
    (batch-shared / fully shared biases — see ``_prep_bias``)."""
    bias_ref = rest[0] if has_bias else None
    rest = rest[1 if has_bias else 0 :]
    if with_lse:
        o_ref, lse_ref = rest
    else:
        (o_ref,), lse_ref = rest, None

    for bi in range(block_b):
        q = q_ref[bi]  # [block_q, dim_p]
        k = k_ref[bi]  # [kv_p, dim_p]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if has_bias:
            s = s + bias_ref[bi if bias_per_slice else 0].astype(jnp.float32)
        if kv_p != kv_len:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < kv_len, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[bi], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[bi] = (acc / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # Broadcast across one 128-lane tile — the layout the blocked
            # backward reads with no relayout (same as flash_attention).
            lse_ref[bi] = jnp.broadcast_to(
                m + jnp.log(l), lse_ref.shape[1:]
            )


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
    """Layout in/out ``[B, L, H, D]``; internally ``[B·H, L, D]`` padded to
    the shared flash geometry (dim→128 lanes, q→block_q, kv→one block)."""
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    if interpret is None:
        interpret = _backend.default_interpret()

    def to_bhld(x):
        b, l, h, d = x.shape
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)

    dim_p = _round_up(dim, 128)
    block_q = min(block_q, _round_up(q_len, 16))
    q_len_p = _round_up(q_len, block_q)
    kv_p = _kv_pad(kv_len)

    def pad3(x, lp):
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, dim_p - x.shape[2])))

    qf = pad3(to_bhld(q), q_len_p)
    kf = pad3(to_bhld(k), kv_p)
    vf = pad3(to_bhld(v), kv_p)

    # Bias broadcast pattern. A bias is stored (and padded) at its OWN
    # broadcast rank — (1,1), (1,H), (B,1) biases are never materialized
    # to the full [B, H, Lq, Lk] (that tensor is the HBM tax this kernel
    # exists to avoid); the grid reads the compact form through an index
    # map instead. The head-ful patterns need grid cells that never
    # straddle a batch boundary, i.e. block_b | heads.
    bias_mode = None
    if bias is not None:
        bias = jnp.broadcast_to(bias, bias.shape[:-2] + (q_len, kv_len))
        shape2 = (bias.shape[0], bias.shape[1])
        # Order matters for the degenerate batch==1 / heads==1 cases: the
        # fully-shared and fully-indexed patterns subsume them, so the
        # modular modes below only ever see batch > 1 AND heads > 1.
        if shape2 == (1, 1):
            bias_mode = "single"
        elif shape2 == (batch, heads):
            bias_mode = "per_slice"
        elif shape2 == (1, heads):
            bias_mode = "per_head"
        elif shape2 == (batch, 1):
            bias_mode = "per_batch"
        else:
            bias = jnp.broadcast_to(bias, (batch, heads) + bias.shape[-2:])
            bias_mode = "per_slice"

    bh = batch * heads
    # The modular modes read the compact bias through index arithmetic that
    # only works when grid cells never straddle a batch boundary.
    needs_head_divisor = bias_mode in ("per_head", "per_batch")
    if block_b is None:
        block_b = _pick_block_b(
            bh, q_len, kv_len, dim,
            block_q=block_q, itemsize=q.dtype.itemsize,
            divisor_of=heads if needs_head_divisor else None,
        )
    elif bh % block_b != 0 or (needs_head_divisor and heads % block_b != 0):
        block_b = 1
    num_q_blocks = q_len_p // block_q
    grid = (bh // block_b, num_q_blocks)

    in_specs = [
        pl.BlockSpec((block_b, block_q, dim_p), lambda b, i: (b, i, 0)),
        pl.BlockSpec((block_b, kv_p, dim_p), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((block_b, kv_p, dim_p), lambda b, i: (b, 0, 0)),
    ]
    args = [qf, kf, vf]
    bias_per_slice = bias_mode in ("per_slice", "per_head")
    if bias is not None:
        # The bias is padded at its OWN broadcast rank — (1,1)/(1,H)/(B,1)
        # stay compact; the full [B·H, Lq, Lk] only exists when the caller
        # materialized it (the HBM tax this kernel exists to avoid).
        biasf = bias.reshape(-1, q_len, kv_len)
        biasf = jnp.pad(
            biasf, ((0, 0), (0, q_len_p - q_len), (0, kv_p - kv_len))
        )
        groups = heads // block_b  # cells per batch element (modular modes)
        if bias_mode == "per_slice":
            bias_spec = pl.BlockSpec(
                (block_b, block_q, kv_p), lambda b, i: (b, i, 0)
            )
        elif bias_mode == "per_head":
            # One bias row per head; cell b starts at head
            # (b·block_b) mod heads, i.e. row-block b mod groups.
            bias_spec = pl.BlockSpec(
                (block_b, block_q, kv_p), lambda b, i: (b % groups, i, 0)
            )
        elif bias_mode == "per_batch":
            # One shared row per batch element: cell b sits in batch
            # (b·block_b) // heads = b // groups.
            bias_spec = pl.BlockSpec(
                (1, block_q, kv_p), lambda b, i: (b // groups, i, 0)
            )
        else:  # 'single': one row for everyone, any block_b
            bias_spec = pl.BlockSpec(
                (1, block_q, kv_p), lambda b, i: (0, i, 0)
            )
        in_specs.append(bias_spec)
        args.append(biasf)

    kernel = functools.partial(
        _fused_kernel,
        has_bias=bias is not None,
        bias_per_slice=bias_per_slice,
        with_lse=with_lse,
        scale=scale,
        kv_len=kv_len,
        kv_p=kv_p,
        block_b=block_b,
    )
    out_specs = [
        pl.BlockSpec((block_b, block_q, dim_p), lambda b, i: (b, i, 0))
    ]
    out_shape = [jax.ShapeDtypeStruct((bh, q_len_p, dim_p), q.dtype)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((block_b, block_q, 128), lambda b, i: (b, i, 0))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((bh, q_len_p, 128), jnp.float32)
        )

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)

    out = outs[0][:, :q_len, :dim]
    out = out.reshape(batch, heads, q_len, dim)
    out = jnp.transpose(out, (0, 2, 1, 3))
    if with_lse:
        return out, outs[1]
    return out


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      scale: float, q_len: int, kv_len: int, kv_p: int,
                      block_b: int, block_q: int, num_q_blocks: int):
    """SINGLE fused backward: with the whole kv sequence resident, each
    grid cell recomputes its probability tile from the lse residual and
    emits dq directly (no kv-block sweep to accumulate over) while dk/dv
    accumulate across q blocks in VMEM scratch — one kernel, not the dq +
    dk/dv pair the multi-block flash backward needs."""
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    for bi in range(block_b):
        q, k, v, do = q_ref[bi], k_ref[bi], v_ref[bi], do_ref[bi]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.exp(s - _lanes(lse_ref[bi], s.shape[1]))
        if kv_p != kv_len:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(col < kv_len, p, 0.0)
        if q_len % block_q != 0:
            # Padded q rows carry a finite lse, so p is finite garbage —
            # zero it so the padded rows contribute nothing to dk/dv.
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            p = jnp.where(row < q_len, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - _lanes(delta_ref[bi], s.shape[1]))
        dq_ref[bi] = (
            jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        ).astype(dq_ref.dtype)
        dv_acc[bi] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[bi] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(qi == num_q_blocks - 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _fused_backward(q, k, v, out, lse, g, scale, block_q, block_b,
                    interpret):
    """q/k/v/out/g ``[B, L, H, D]``; lse is the padded ``[B·H, q_len_p,
    128]`` forward residual."""
    if interpret is None:
        interpret = _backend.default_interpret()

    kv_p = _kv_pad(k.shape[1])
    geom = _bwd_prep(q, k, v, out, g, block_q, kv_p)
    q_len, kv_len = geom.q_len, geom.kv_len
    block_q, dim_p = geom.block_q, geom.dim_p
    num_q_blocks = geom.q_len_p // block_q
    bh = geom.batch * geom.heads
    if block_b is None:
        block_b = _pick_block_b(
            bh, q_len, kv_len, geom.dim,
            block_q=block_q, itemsize=q.dtype.itemsize,
        )
    elif bh % block_b != 0:
        block_b = 1

    qspec = pl.BlockSpec((block_b, block_q, dim_p), lambda b, i: (b, i, 0))
    kspec = pl.BlockSpec((block_b, kv_p, dim_p), lambda b, i: (b, 0, 0))
    rowq = pl.BlockSpec((block_b, block_q, 128), lambda b, i: (b, i, 0))

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel,
            scale=scale,
            q_len=q_len,
            kv_len=kv_len,
            kv_p=kv_p,
            block_b=block_b,
            block_q=block_q,
            num_q_blocks=num_q_blocks,
        ),
        grid=(bh // block_b, num_q_blocks),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=[qspec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, geom.q_len_p, dim_p), q.dtype),
            jax.ShapeDtypeStruct((bh, kv_p, dim_p), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_p, dim_p), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, kv_p, dim_p), jnp.float32),
            pltpu.VMEM((block_b, kv_p, dim_p), jnp.float32),
        ],
        interpret=interpret,
    )(geom.qf, geom.kf, geom.vf, geom.dof, lse, geom.delta)

    return (
        geom.unprep(dq, q_len),
        geom.unprep(dk, kv_len),
        geom.unprep(dv, kv_len),
    )


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
      key, value: ``[B, kv_len, heads, head_dim]``. The whole (padded) kv
        sequence must fit one VMEM block (:func:`fused_eligible`).
      bias: optional additive logits bias broadcastable to
        ``[B, heads, q_len, kv_len]``.
      scale: logit scale, default ``head_dim ** -0.5``.
      block_q: q tile (clamped for short sequences). Per-shape measured
        winners come from the ``tools/attn_tune.py`` cache via the ``auto``
        dispatcher.
      block_b: batch·head slices per grid cell; None = largest of
        (8, 4, 2, 1) under the VMEM budget.
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
    dim = query.shape[-1]
    if not fused_eligible(
        q_len, kv_len, dim, block_q=block_q, itemsize=query.dtype.itemsize
    ):
        raise ValueError(
            f"kv_len={kv_len} (dim={dim}) does not fit the fused kernel's "
            f"single-KV-block VMEM budget ({FUSED_VMEM_BUDGET} bytes, "
            f"estimate {fused_vmem_bytes(q_len, kv_len, dim, block_q=block_q, itemsize=query.dtype.itemsize)}); "
            "use the flash kernel (backend='pallas') or XLA"
        )
    if scale is None:
        scale = query.shape[-1] ** -0.5
    return _fused(
        query, key, value, bias, float(scale), block_q, block_b, interpret
    )
