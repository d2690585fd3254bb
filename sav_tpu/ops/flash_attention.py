"""Fused Pallas TPU flash attention.

The TPU execution backend for every attention family in the layer zoo
(SURVEY.md §2.1, BASELINE.json north star): a blockwise online-softmax kernel
that streams K/V tiles through VMEM, keeps the running ``(max, sum, acc)``
statistics in scratch, and never materializes the ``[B, H, Lq, Lk]`` logits
in HBM. An optional additive bias input carries 2-D relative-position logits
(BoTNet) or masks through the fused softmax. ``causal=True`` (decoder
self-attention) needs no bias: a block wholly above the diagonal is neither
fetched nor computed, a block the diagonal crosses is masked in VMEM by an
iota comparison, forward and backward. ``window`` beside it is a band
(``i - window < j <= i``, sliding-window layers; the mask is
:func:`band_keep`), and the shapes say which kernels run it
(:func:`band_form`, logged as ``band``). ``resident``: a kernel pair of its
own whose grid IS the band, one cell a q block and key/value head, the
group's query heads and the kv blocks the q block sees inside it, each
head's softmax finished in the cell, dk and dv summed over the group and
the cells in a VMEM ring, operands and results all ``[B, H·D, L]`` (the
section before :func:`_band_forward`). It takes an unbiased call whose
heads are whole 128-lane tiles, whose q and kv blocks are equal whole lane
tiles that divide the sequence, and whose cell fits the one-kernel
backward's VMEM budget and a bound on the unrolled program (a window of a
few blocks). ``skipped_cells``: everything else (a head of 64, a ragged
length, a bias, unequal blocks, a window of many blocks) runs as an arm of
the causal kernels below, on the causal grid: the blocks wholly behind the
window are skipped as those above the diagonal are, and the block the far
edge crosses is masked by the same comparison.

Layout: where every block can be one head's rows of the caller's own
arrays, the unbiased kernels read and write those in place
(:func:`layout_form`: heads of whole 128-lane tiles, sequences of whole
blocks, one slice a grid cell, the one-kernel backward; a rule on what the
call can see, nothing a caller sets). q, k, v and dO are read as
``[B, H·D, L]``, the order in which XLA holds what a projection's matmul and
the rotary write (the sequence on the lanes), so the transpose to it is
answered with a layout; out, dq, dk and dv are written ``[B, L, H·D]``,
which the next matmul reads as it is. The logsumexp leaves the forward as
one float32 a row, the backward computes its tiles transposed so that the
row and ``delta`` (computed in the kernel from the dO and output blocks it
holds) broadcast down a tile, and XLA transposes, pads and broadcasts
nothing around the calls. Everything else (a 64- or 192-lane head, ragged
lengths, a bias, several slices a cell, the two-kernel backward, and
:mod:`sav_tpu.parallel.ring_attention`'s direct calls) runs the same tiles
on padded head-major ``[B·H, L_p, D_p]`` copies.

Differentiation: ``flash_attention`` is a ``jax.custom_vjp``. Without a
bias, the backward is fully blocked Pallas too: the forward saves only the
per-row logsumexp (one float32 a row between the passes; the head-major
kernels write and read it broadcast across one 128-lane tile),
and ONE kernel recomputes a ``(q block, kv block)`` pair's probabilities
once and feeds dq, dk and dv from them: a q-innermost grid sums dk/dv over
the q sweep of a kv block, and the float32 dq of a whole batch·head cell
stays in VMEM from the cell's first grid step to its last (3 MiB at L 4096
and a 192-lane head), under a VMEM limit of its own. Where that dq does not
fit beside the tiles (:func:`backward_form`: a rule on the padded sizes,
the blocks and ``block_b``, nothing a caller sets), two kernels run, dq
(kv-innermost grid) and dk/dv (q-innermost grid), each rebuilding the
probabilities: seven matmuls a pair of tiles for five. The ``[B, H, Lq,
Lk]`` probability matrix never exists in HBM in either direction. With an
additive bias that requires a gradient, the backward falls back to an XLA
flash-style recompute (the dbias reduction needs the dense ``ds``).

Numerics: logits/softmax/accumulation in float32 regardless of input dtype;
the P·V matmul runs in the value dtype on the MXU (bf16 in, f32 accumulate).
Cross-checked against :func:`sav_tpu.ops.attention.xla_attention` in
``tests/test_flash_attention.py``.

On non-TPU backends the kernel runs in Pallas interpreter mode, so the same
code path is testable on the CPU mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sav_tpu.ops import _backend

_NEG_INF = float("-inf")
# The default q and kv tile: the v5e block sweep (now tools/attn_tune.py,
# PERF.md §5) measured 256/256 ~1.6x faster than 128/128 at model-zoo shapes.
DEFAULT_BLOCK = 256


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _clamp_block(block: int, length: int) -> int:
    """A tile no longer than its sequence (rounded up to whole sublane
    tiles): the one clamp every driver and both rules apply."""
    return min(block, _round_up(length, 16))


def _pad_head(dim: int) -> int:
    """The head size the kernels see: a whole number of 128-lane tiles, but a
    head wider than one tile that is a multiple of 64 (latent attention's
    192) stays as it is. Mosaic takes a block whose last dimension is the
    array's own, and the half-filled second tile costs less than padding
    every query and key row in HBM: at ``[2, 4096, 32, 192 / 128]`` causal
    4.44 / 18.20 ms forward / forward + backward against 5.26 / 19.01 padded
    to 256 (PERF.md section 6, PR 30)."""
    if dim > 128 and dim % 64 == 0:
        return dim
    return _round_up(dim, 128)


def _pick_block_b(bh: int, *, force_one: bool = False) -> int:
    """Batch·head slices per grid cell. Grid-cell issue overhead on TPU is
    ~µs-scale, so short-sequence shapes (few kv blocks per cell) want several
    bh slices batched into one cell; 8 × block 256 stays well inside VMEM."""
    if force_one:
        return 1
    for bb in (8, 4, 2):
        if bh % bb == 0:
            return bb
    return 1


def band_keep(row, col, window: Optional[int] = None):
    """Where a query at position ``row`` may look: ``col <= row`` and, under
    a ``window``, ``col > row - window`` (itself and the ``window - 1``
    before it). The one place the mask is written: the kernels' element
    masks, the dense paths and the block tests below all call it."""
    keep = col <= row
    if window is not None:
        keep = jnp.logical_and(keep, col > row - window)
    return keep


def _causal_blocks(qi, ki, block_q: int, block_kv: int, window: Optional[int] = None):
    """For q block ``qi`` against kv block ``ki`` under the causal mask:
    ``(visible, crossed, far)``. ``visible``: some ``col <= row`` exists, the
    block has work. ``crossed``: the diagonal runs through it, so it needs
    the element mask (a visible block that is not crossed lies wholly below
    the diagonal). ``far``: the far edge of a ``window`` (``col == row -
    window``) runs through it (never, without one); ``visible`` then also
    wants a column inside some row's window. Takes Python integers (the
    static counts of :func:`band_blocks`) as well as traced ones."""
    first_row, last_row = qi * block_q, qi * block_q + block_q - 1
    first_col, last_col = ki * block_kv, ki * block_kv + block_kv - 1
    visible = first_col <= last_row
    crossed = visible & (last_col > first_row)
    if window is None:
        return visible, crossed, False
    visible = visible & (last_col > first_row - window)
    return visible, visible & crossed, visible & (first_col <= last_row - window)


def _causal_keep(qi, ki, block_q: int, block_kv: int, *, transposed: bool = False,
                 window: Optional[int] = None, edges: tuple = (True, True)):
    """``[block_q, block_kv]`` bool: :func:`band_keep` in global positions
    (``transposed``: of a ``[block_kv, block_q]`` tile, rows on its lanes).
    ``edges = (diagonal, far)`` names the comparisons a block needs: the one
    the diagonal crosses ``col <= row`` alone, the one the window's far edge
    crosses ``col > row - window`` alone."""
    shape = (block_kv, block_q) if transposed else (block_q, block_kv)
    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transposed else 0)
    col = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transposed else 1)
    diagonal, far = edges
    if window is None or not far:
        return col <= row
    if not diagonal:
        return col > row - window
    return band_keep(row, col, window)


def band_blocks(num_q_blocks: int, num_kv_blocks: int, block_q: int, block_kv: int,
                window: Optional[int]) -> dict:
    """Static counts of a causal grid: ``visited`` cells (those with work
    under ``window``; None = the causal mask alone), ``causal`` (those with
    work under the causal mask alone), and ``cases``, the ``(diagonal,
    far)`` pairs of :func:`_causal_blocks` that some visited cell meets, in
    a fixed order (a kernel builds one body a case)."""
    cases, visited, causal = set(), 0, 0
    for qi in range(num_q_blocks):
        for ki in range(num_kv_blocks):
            causal += _causal_blocks(qi, ki, block_q, block_kv)[0]
            visible, *edges = _causal_blocks(qi, ki, block_q, block_kv, window)
            if visible:
                visited += 1
                cases.add(tuple(edges))
    return {"visited": visited, "causal": causal, "cases": tuple(sorted(cases))}


def _band_statics(num_q_blocks: int, num_kv_blocks: int, block_q: int, block_kv: int,
                  window: Optional[int]) -> dict:
    """What a kernel is told of a window beside ``causal``: nothing without
    one (the causal kernels are built as they were), else the window and the
    grid's ``cases``."""
    if window is None:
        return {}
    return {"window": window, "cases": band_blocks(num_q_blocks, num_kv_blocks, block_q, block_kv, window)["cases"]}


def _for_causal_blocks(causal: bool, qi, ki, block_q: int, block_kv: int, body,
                       window: Optional[int] = None, cases: tuple = ()):
    """Run ``body(masked)`` for this grid cell: always and unmasked without
    ``causal``; else not at all above the diagonal, masked on it, unmasked
    below it. Under a ``window`` not at all behind its far edge either, and
    ``masked`` is the ``(diagonal, far)`` pair of edges that cross the cell
    (false where neither does): one body for each of ``cases``, the pairs
    the grid meets (:func:`band_blocks`)."""
    if not causal:
        body(False)
        return
    if window is None:
        visible, crossed, _ = _causal_blocks(qi, ki, block_q, block_kv)
        pl.when(crossed)(lambda: body((True, False)))
        pl.when(jnp.logical_and(visible, jnp.logical_not(crossed)))(lambda: body(False))
        return
    visible, diagonal, far = _causal_blocks(qi, ki, block_q, block_kv, window)
    for case in cases:
        met = visible
        for edge, wanted in zip((diagonal, far), case):
            met = jnp.logical_and(met, edge if wanted else jnp.logical_not(edge))
        pl.when(met)(lambda case=case: body(case if any(case) else False))


def _last_kv_block(qi, block_q: int, block_kv: int):
    """Index of the last kv block a causal q block can see."""
    return (qi * block_q + block_q - 1) // block_kv


def _first_kv_block(qi, block_q: int, block_kv: int, window: Optional[int]):
    """Index of the first kv block a q block can see: 0 without a window,
    else the block of its first row's farthest column."""
    if window is None:
        return 0
    return jnp.maximum(qi * block_q - window + 1, 0) // block_kv


def _first_q_block(ki, block_q: int, block_kv: int):
    """Index of the first q block that can see a causal kv block."""
    return (ki * block_kv) // block_q


def _last_q_block(ki, block_q: int, block_kv: int, num_q_blocks: int, window: Optional[int]):
    """Index of the last q block that can see a kv block: the grid's last
    without a window, else the block of the row ``window - 1`` past the kv
    block's last column."""
    if window is None:
        return num_q_blocks - 1
    return jnp.minimum((ki * block_kv + block_kv - 1 + window - 1) // block_q, num_q_blocks - 1)


def _visible_kv_block(qi, ki, block_q: int, block_kv: int, window: Optional[int]):
    """The kv block a forward (or dq) cell names: its own where it has work,
    else the nearest one its q block visits, so that nothing is fetched for
    a skipped cell at either edge (Pallas fetches a block only when its
    index changes)."""
    last = jnp.minimum(ki, _last_kv_block(qi, block_q, block_kv))
    return last if window is None else jnp.maximum(last, _first_kv_block(qi, block_q, block_kv, window))


def _visible_q_block(ki, qi, block_q: int, block_kv: int, num_q_blocks: int, window: Optional[int]):
    """The q block a dk/dv cell names: as :func:`_visible_kv_block`, for the
    q sweep of a kv block."""
    first = jnp.maximum(qi, _first_q_block(ki, block_q, block_kv))
    return first if window is None else jnp.minimum(first, _last_q_block(ki, block_q, block_kv, num_q_blocks, window))


def _resolve_block_b(block_b: Optional[int], bh: int, *, force_one: bool = False) -> int:
    """The caller's ``block_b`` where it gives one that divides ``bh``
    (a measured entry of the tune cache), else :func:`_pick_block_b`."""
    if force_one or block_b is None:
        return _pick_block_b(bh, force_one=force_one)
    if bh % block_b:
        raise ValueError(f"block_b {block_b} does not divide batch x heads {bh}")
    return block_b


def _finite_max(m_new, banded: bool):
    """The running max a block's exponentials are taken against. Under the
    causal mask alone a row's first block shows it column 0 and the max is
    finite from then on; under a window a q block's first kv block can hide
    every column of its later rows, whose max is still ``-inf``: 0 stands in
    for it (``exp(-inf - 0)`` is the 0 such a row's entries are)."""
    return jnp.where(m_new == _NEG_INF, 0.0, m_new) if banded else m_new


def _online_softmax_step(s, v, m_scr, l_scr, acc_scr, bi, banded: bool = False):
    """Fold one block's logits ``s`` of batch·head slice ``bi`` into the
    running (max, sum, acc) statistics."""
    m_prev = m_scr[bi, :, 0:1]
    l_prev = l_scr[bi, :, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    against = _finite_max(m_new, banded)
    alpha = jnp.exp(m_prev - against)
    p = jnp.exp(s - against)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[bi] = jnp.broadcast_to(m_new, m_scr.shape[1:])
    l_scr[bi] = jnp.broadcast_to(l_new, l_scr.shape[1:])
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[bi] = acc_scr[bi] * alpha + pv


def _write_output(o_ref, lse_ref, m_scr, l_scr, acc_scr, bi):
    """After the last kv block: the normalized output of slice ``bi`` and,
    when ``lse_ref`` is given, the per-row logsumexp the blocked backward
    needs."""
    o_ref[bi] = (acc_scr[bi] / l_scr[bi, :, 0:1]).astype(o_ref.dtype)
    if lse_ref is not None:
        # Combined logsumexp, broadcast across the lane tile so the
        # backward reads it with no relayout.
        lse_ref[bi] = m_scr[bi] + jnp.log(l_scr[bi])


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    *rest,
    has_bias: bool,
    with_lse: bool,
    scale: float,
    kv_len: int,
    block_b: int,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    causal: bool,
    window: Optional[int] = None,
    cases: tuple = (),
):
    """Online-softmax flash kernel;
    ``rest`` = ([bias_ref], o_ref, [lse_ref], m, l, acc).

    The leading grid axis carries ``block_b`` batch·head slices per cell
    (unrolled loop below): TPU grid-cell issue overhead is ~µs-scale, so at
    small sequence lengths a [B·H, 1, 1]-cell grid is overhead-bound — the
    dominant cost at DeiT shapes, measured on v5e."""
    bias_ref = rest[0] if has_bias else None
    rest = rest[1 if has_bias else 0 :]
    if with_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        lse_ref = None
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)

    def fold(masked: bool):
        for bi in range(block_b):
            q = q_ref[bi]  # [block_q, d]
            k = k_ref[bi]  # [block_kv, d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            s = s * scale
            if has_bias:
                s = s + bias_ref[bi].astype(jnp.float32)
            if kv_len % block_kv != 0:
                col = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(col < kv_len, s, _NEG_INF)
            if masked:
                # Block 0 comes first and shows every row its column 0, so
                # the running max is finite before a row meets a block that
                # hides all of its columns (under a window: _finite_max).
                keep = _causal_keep(qi, ki, block_q, block_kv, window=window, edges=masked)
                s = jnp.where(keep, s, _NEG_INF)
            _online_softmax_step(s, v_ref[bi], m_scr, l_scr, acc_scr, bi, window is not None)

    _for_causal_blocks(causal, qi, ki, block_q, block_kv, fold, window, cases)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        for bi in range(block_b):
            _write_output(o_ref, lse_ref, m_scr, l_scr, acc_scr, bi)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array],
    scale: float,
    block_q: int,
    block_kv: int,
    interpret: Optional[bool],
    with_lse: bool = False,
    *,
    causal: bool = False,
    block_b: Optional[int] = None,
    window: Optional[int] = None,
):
    """Run the kernel. Layout in/out: ``[B, L, H, D]``.

    With ``with_lse`` also returns the per-row logsumexp as
    ``[B·H, padded_q_len, 128]`` f32 (value broadcast across the lane dim) —
    the residual the blocked backward consumes as-is.
    """
    batch, q_len, heads, dim = q.shape
    kv_len, dim_v = k.shape[1], v.shape[-1]
    if interpret is None:
        interpret = _backend.default_interpret()

    # [B, L, H, D] -> [B*H, L, D]
    def to_bhld(x):
        b, l, h, d = x.shape
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)

    qf, kf, vf = to_bhld(q), to_bhld(k), to_bhld(v)

    # The query/key head and the value head are padded each to its own lane
    # multiple: a value head (and the output) narrower than the query's is
    # never widened to it.
    dim_p, dim_v_p = _pad_head(dim), _pad_head(dim_v)
    block_q = _clamp_block(block_q, q_len)
    block_kv = _clamp_block(block_kv, kv_len)
    q_len_p = _round_up(q_len, block_q)
    kv_len_p = _round_up(kv_len, block_kv)

    def pad3(x, lp, dp):
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, dp - x.shape[2])))

    qf, kf, vf = pad3(qf, q_len_p, dim_p), pad3(kf, kv_len_p, dim_p), pad3(vf, kv_len_p, dim_v_p)

    shared_bias = False
    if bias is not None:
        bias = jnp.broadcast_to(bias, bias.shape[:-2] + (q_len, kv_len))
        bb, bh = bias.shape[0], bias.shape[1]
        if (bb, bh) not in ((batch, heads), (1, 1)):
            bias = jnp.broadcast_to(bias, (batch, heads) + bias.shape[-2:])
            bb, bh = batch, heads
        shared_bias = bb * bh == 1

    if causal and q_len != kv_len:
        raise ValueError(f"causal attention is self-attention: q_len {q_len} != kv_len {kv_len}")
    block_b = _resolve_block_b(block_b, batch * heads, force_one=shared_bias)
    num_q_blocks = q_len_p // block_q
    num_kv_blocks = kv_len_p // block_kv
    grid = (batch * heads // block_b, num_q_blocks, num_kv_blocks)

    # A causal cell above the diagonal (or behind the window) names the
    # block a neighbour held: Pallas fetches a block only when its index
    # changes.
    if causal:
        kv_index = lambda b, i, j: (b, _visible_kv_block(i, j, block_q, block_kv, window), 0)
    else:
        kv_index = lambda b, i, j: (b, j, 0)
    in_specs = [
        pl.BlockSpec((block_b, block_q, dim_p), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((block_b, block_kv, dim_p), kv_index),
        pl.BlockSpec((block_b, block_kv, dim_v_p), kv_index),
    ]
    args = [qf, kf, vf]
    if bias is not None:
        biasf = bias.reshape(-1, q_len, kv_len)
        biasf = jnp.pad(
            biasf, ((0, 0), (0, q_len_p - q_len), (0, kv_len_p - kv_len))
        )
        if shared_bias:
            bias_index = lambda b, i, j: (0, i, j)
        else:
            bias_index = lambda b, i, j: (b, i, j)
        in_specs.append(pl.BlockSpec((block_b, block_q, block_kv), bias_index))
        args.append(biasf)

    kernel = functools.partial(
        _kernel,
        has_bias=bias is not None,
        with_lse=with_lse,
        scale=scale,
        kv_len=kv_len,
        block_b=block_b,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
        causal=causal,
        **_band_statics(num_q_blocks, num_kv_blocks, block_q, block_kv, window),
    )

    out_specs = [
        pl.BlockSpec((block_b, block_q, dim_v_p), lambda b, i, j: (b, i, 0))
    ]
    out_shape = [jax.ShapeDtypeStruct((batch * heads, q_len_p, dim_v_p), q.dtype)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((block_b, block_q, 128), lambda b, i, j: (b, i, 0))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((batch * heads, q_len_p, 128), jnp.float32)
        )

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_b, block_q, 128), jnp.float32),
            pltpu.VMEM((block_b, block_q, 128), jnp.float32),
            pltpu.VMEM((block_b, block_q, dim_v_p), jnp.float32),
        ],
        interpret=interpret,
    )(*args)

    out = outs[0][:, :q_len, :dim_v]
    out = out.reshape(batch, heads, q_len, dim_v)
    out = jnp.transpose(out, (0, 2, 1, 3))
    if with_lse:
        return out, outs[1]
    return out


# ---------------------------------------------------------------------------
# Blocked Pallas backward (no-bias path). Standard flash backward with the
# normalized-probability formulation: the forward saves lse = m + log(l),
# so p = exp(s − lse) is already normalized, and with
# delta_i = Σ_d dO_id · O_id the gradients are
#   ds = p ⊙ (dO·Vᵀ − delta),  dq = scale·ds·K,  dk = scale·dsᵀ·Q,
#   dv = pᵀ·dO.
# One kernel on a q-innermost grid produces all three where the whole float32
# dq of a batch·head cell fits VMEM (backward_form); else dq uses a
# kv-innermost grid (accumulator indexed by q block) and dk/dv a q-innermost
# one (accumulators indexed by kv block). All matmuls run bf16-in/
# f32-accumulate on the MXU — feeding fp32 operands to the MXU would run it
# at a fraction of peak for no accuracy gain (same policy as the XLA
# recompute path below).
# ---------------------------------------------------------------------------


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """Expand a [rows, 128] lane-broadcast tile to ``n`` lanes."""
    if n == 128:
        return x
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    return jnp.broadcast_to(x[:, 0:1], (x.shape[0], n))


class _BwdGeom(NamedTuple):
    """Shared padded operands + geometry for the blocked backward drivers."""

    qf: jax.Array
    kf: jax.Array
    vf: jax.Array
    dof: jax.Array
    delta: jax.Array
    batch: int
    heads: int
    q_len: int
    kv_len: int
    dim: int
    dim_p: int
    block_q: int
    block_kv: int
    q_len_p: int
    kv_len_p: int
    # The value head (v, dO, dv) where it differs from the query/key head.
    dim_v: int
    dim_v_p: int

    def unprep(self, x: jax.Array, l: int, dim: Optional[int] = None) -> jax.Array:
        """Padded ``[B·H, L_p, D_p]`` → ``[B, L, H, D]`` (``D`` the
        query/key head unless ``dim`` says the value head)."""
        dim = self.dim if dim is None else dim
        x = x[:, :l, :dim].reshape(self.batch, self.heads, l, dim)
        return jnp.transpose(x, (0, 2, 1, 3))


def lse_padded_layout(lse: jax.Array, q_len: int, block_q: int) -> jax.Array:
    """``[B, H, Lq]`` f32 logsumexp → the ``[B·H, q_len_p, 128]`` broadcast
    residual layout the blocked backward kernels read. Uses the same block
    clamping as :func:`_bwd_prep`, so external callers (e.g. the flash-mode
    ring backward) stay in sync with the drivers' padding geometry."""
    block_q = _clamp_block(block_q, q_len)
    q_len_p = _round_up(q_len, block_q)
    b, h, lq = lse.shape
    flat = lse.reshape(b * h, lq)
    flat = jnp.pad(flat, ((0, 0), (0, q_len_p - lq)))
    return jnp.broadcast_to(flat[:, :, None], flat.shape + (128,))


def _bwd_prep(q, k, v, out, g, block_q, block_kv) -> _BwdGeom:
    """``[B, L, H, D]`` operands → the padded ``[B·H, L_p, D_p]`` layout both
    blocked backward drivers consume, plus ``delta_i = Σ_d dO·O`` broadcast
    across one lane tile (same layout as lse, so kernels read both with no
    relayout). Single source for block clamping and padding geometry."""
    batch, q_len, heads, dim = q.shape
    kv_len, dim_v = k.shape[1], v.shape[-1]
    dim_p, dim_v_p = _pad_head(dim), _pad_head(dim_v)
    block_q = _clamp_block(block_q, q_len)
    block_kv = _clamp_block(block_kv, kv_len)
    q_len_p = _round_up(q_len, block_q)
    kv_len_p = _round_up(kv_len, block_kv)

    def to_bhld(x):
        b, l, h, d = x.shape
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)

    def pad3(x, lp, dp):
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, dp - x.shape[2])))

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.transpose(delta, (0, 2, 1)).reshape(batch * heads, q_len)
    delta = jnp.pad(delta, ((0, 0), (0, q_len_p - q_len)))
    delta = jnp.broadcast_to(delta[:, :, None], delta.shape + (128,))
    return _BwdGeom(
        qf=pad3(to_bhld(q), q_len_p, dim_p),
        kf=pad3(to_bhld(k), kv_len_p, dim_p),
        vf=pad3(to_bhld(v), kv_len_p, dim_v_p),
        dof=pad3(to_bhld(g), q_len_p, dim_v_p),
        delta=delta,
        batch=batch,
        heads=heads,
        q_len=q_len,
        kv_len=kv_len,
        dim=dim,
        dim_p=dim_p,
        block_q=block_q,
        block_kv=block_kv,
        q_len_p=q_len_p,
        kv_len_p=kv_len_p,
        dim_v=dim_v,
        dim_v_p=dim_v_p,
    )


def _bwd_tile(q, k, v, do, lse, delta, *, scale, qi, ki, q_len, kv_len,
              block_q, block_kv, masked, window=None):
    """One ``(q block, kv block)`` pair's ``(p, ds)``, float32: the
    probabilities rebuilt from the logsumexp, zero on padded rows and
    columns and above the diagonal, and ``ds = p (dO v^T - delta)``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [block_q, block_kv]
    p = jnp.exp(s - _lanes(lse, s.shape[1]))
    if kv_len % block_kv != 0:
        col = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.where(col < kv_len, p, 0.0)
    if q_len % block_q != 0:
        # Padded (zero) q rows carry a finite lse ≈ log(kv_len), so p is
        # finite garbage, not NaN: they must not reach the dk/dv sums, and
        # their dq rows (sliced off outside) cost nothing as zeros.
        row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        p = jnp.where(row < q_len, p, 0.0)
    if masked:
        p = jnp.where(_causal_keep(qi, ki, block_q, block_kv, window=window, edges=masked), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p, p * (dp - _lanes(delta, s.shape[1]))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale: float, q_len: int, kv_len: int,
                   block_b: int, block_q: int, block_kv: int,
                   num_kv_blocks: int, causal: bool,
                   window: Optional[int] = None, cases: tuple = ()):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def fold(masked: bool):
        for bi in range(block_b):
            k = k_ref[bi]
            _, ds = _bwd_tile(
                q_ref[bi], k, v_ref[bi], do_ref[bi], lse_ref[bi], delta_ref[bi],
                scale=scale, qi=qi, ki=ki, q_len=q_len, kv_len=kv_len,
                block_q=block_q, block_kv=block_kv, masked=masked, window=window,
            )
            dq_acc[bi] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    _for_causal_blocks(causal, qi, ki, block_q, block_kv, fold, window, cases)

    @pl.when(ki == num_kv_blocks - 1)
    def _write():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    with_dq: bool, scale: float, q_len: int, kv_len: int,
                    block_b: int, block_q: int, block_kv: int,
                    num_q_blocks: int, num_kv_blocks: int, causal: bool,
                    window: Optional[int] = None, cases: tuple = ()):
    """dk and dv of one kv block, summed over its q sweep (q innermost);
    ``rest`` = ([dq_ref], dk_ref, dv_ref, [dq_acc], dk_acc, dv_acc).

    ``with_dq``: dq too, from the same recomputation of a pair's logits. A q
    block is met once per kv block, so the float32 dq of the whole
    batch·head cell, ``[num_q_blocks, block_q, dim_p]`` a slice (a q block
    is named by its leading index), stays in VMEM from the cell's first
    grid step to its last."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    ki, qi = pl.program_id(1), pl.program_id(2)

    if with_dq:
        @pl.when(jnp.logical_and(ki == 0, qi == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def fold(masked: bool):
        for bi in range(block_b):
            q, k, do = q_ref[bi], k_ref[bi], do_ref[bi]
            p, ds = _bwd_tile(
                q, k, v_ref[bi], do, lse_ref[bi], delta_ref[bi], scale=scale,
                qi=qi, ki=ki, q_len=q_len, kv_len=kv_len, block_q=block_q,
                block_kv=block_kv, masked=masked, window=window,
            )
            dv_acc[bi] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = ds.astype(q.dtype)
            dk_acc[bi] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale
            if with_dq:
                dq_acc[bi, qi] += jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                ) * scale

    _for_causal_blocks(causal, qi, ki, block_q, block_kv, fold, window, cases)

    @pl.when(qi == num_q_blocks - 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    if with_dq:
        @pl.when(jnp.logical_and(ki == num_kv_blocks - 1, qi == num_q_blocks - 1))
        def _write_dq():
            for i in range(num_q_blocks):
                dq_ref[:, i * block_q:(i + 1) * block_q, :] = dq_acc[:, i].astype(dq_ref.dtype)


# The one-kernel backward keeps a batch·head cell's whole float32 dq in
# VMEM beside its tiles: over Mosaic's default 16 MiB of scoped VMEM at the
# token cells' shapes (a v5e core has 128 MiB). The call is compiled under
# the limit; the estimate has to fit the budget, and the rest of the limit
# is left to what the estimate cannot see.
ONE_KERNEL_VMEM_BUDGET = 48 * 2**20
_ONE_KERNEL_VMEM_LIMIT = 64 * 2**20


def one_kernel_backward_vmem_bytes(
    q_len_p: int, dim_p: int, dim_v_p: int, *, block_q: int, block_kv: int,
    block_b: int = 1, itemsize: int = 2,
) -> int:
    """Estimated per-grid-cell VMEM working set of :func:`_bwd_dkv_kernel`
    with dq:
    q, k, v, dO and the 128-lane lse and delta tiles in and dk, dv out at
    their blocks, dq out at the whole padded sequence, every block
    double-buffered; the float32 dk, dv and dq accumulators; and two
    float32 logits tiles of one slice (Mosaic keeps about one and a half of
    ``s``, ``p``, ``dp``, ``ds`` alive). Every term but the last holds
    ``block_b`` slices, and a 192-lane head fills two lane tiles in VMEM.
    Conservative: compiled for a v5e at head sizes 64 to 192, lengths 200
    to 16,384, tiles 128 to 2,048 and ``block_b`` 1 to 8, Mosaic reports
    using from half of this (a long sequence: it holds the dq block once)
    to 94% of it, and the budget leaves a quarter of the limit besides."""
    dim_p, dim_v_p = _round_up(dim_p, 128), _round_up(dim_v_p, 128)
    heads = dim_p + dim_v_p
    blocks = (block_q + 2 * block_kv) * heads * itemsize  # q, dO; k, v; dk, dv
    blocks += 2 * block_q * 128 * 4  # lse, delta
    blocks += q_len_p * dim_p * itemsize  # dq
    scratch = (block_kv * heads + q_len_p * dim_p) * 4
    return block_b * (2 * blocks + scratch) + 2 * block_q * block_kv * 4


def backward_form(q_len: int, kv_len: int, dim: int, dim_v: int, *,
                  batch_heads: int, block_q: int = DEFAULT_BLOCK,
                  block_kv: int = DEFAULT_BLOCK,
                  block_b: Optional[int] = None, itemsize: int = 2) -> str:
    """Which blocked backward an unbiased :func:`flash_attention` of these
    shapes and blocks runs: ``one_kernel`` where its working set (at the
    geometry :func:`_bwd_prep` pads to) fits the budget, else
    ``two_kernels`` (dq apart from dk/dv, each rebuilding the
    probabilities, neither holding more than its tiles). A ``window``
    changes neither this nor :func:`layout_form`: the causal kernels' banded
    arm keeps the causal grid, its blocks and the resident dq, and skips
    cells (the band's resident pair, :func:`band_form`, asks neither)."""
    block_q = _clamp_block(block_q, q_len)
    fits = one_kernel_backward_vmem_bytes(
        _round_up(q_len, block_q), _pad_head(dim), _pad_head(dim_v),
        block_q=block_q, block_kv=_clamp_block(block_kv, kv_len),
        block_b=_resolve_block_b(block_b, batch_heads), itemsize=itemsize,
    ) <= ONE_KERNEL_VMEM_BUDGET
    return "one_kernel" if fits else "two_kernels"


def layout_form(q_len: int, kv_len: int, dim: int, dim_v: int, *,
                batch_heads: int, biased: bool = False,
                block_q: int = DEFAULT_BLOCK, block_kv: int = DEFAULT_BLOCK,
                block_b: Optional[int] = None, itemsize: int = 2) -> str:
    """Where a :func:`flash_attention` of these shapes and blocks meets its
    operands in HBM. ``in_place``: the kernels' blocks are one head's rows
    of the caller's own ``[B, L, H, D]`` arrays (read with the sequence on
    the lanes, written with the heads' columns side by side: the section
    before :func:`_in_place_forward`), and XLA transposes, pads and
    broadcasts nothing around the calls. That takes an unbiased call whose
    heads are whole 128-lane tiles (out, dq, dk and dv leave as one head's
    columns of ``[B, L, H·D]``, and Mosaic takes no 192-lane block out of a
    wider array), whose sequences are whole numbers of the clamped
    blocks, themselves whole lane tiles (a block's rows lie on the lanes),
    one slice a grid cell, and the one-kernel backward. Everything else is
    ``head_major``: padded ``[B·H, L_p, D_p]`` copies, the form the ring
    path also calls by name.

    The in-place forward holds the backward's tiles and no resident dq, and
    both calls are compiled under the one-kernel backward's VMEM limit, so
    the backward's estimate (read through :func:`backward_form`) bounds
    both. What the form is worth depends on who feeds it: a model whose
    projections write the sequence on the lanes pays no copy at all, while
    a caller whose operands lie in their default order (a jit's own
    parameters: ``tools/attn_tune.py``, ``parallel/ulysses.py``,
    ``tools/flash_memory_win.py``) pays one transposing copy an operand, as
    the head-major form does: there the forward alone gains nothing, and
    the gain is the backward's (PERF.md section 6, PR 36)."""
    block_q = _clamp_block(block_q, q_len)
    block_kv = _clamp_block(block_kv, kv_len)
    whole = (
        dim % 128 == 0 and dim_v % 128 == 0 and block_q % 128 == 0 and block_kv % 128 == 0
        and q_len % block_q == 0 and kv_len % block_kv == 0
    )
    in_place = (
        not biased and whole and _resolve_block_b(block_b, batch_heads) == 1
        and backward_form(
            q_len, kv_len, dim, dim_v, batch_heads=batch_heads, block_q=block_q,
            block_kv=block_kv, block_b=block_b, itemsize=itemsize,
        ) == "one_kernel"
    )
    return "in_place" if in_place else "head_major"


def _flash_backward_pallas(q, k, v, out, lse, g, scale, block_q, block_kv,
                           interpret, *, causal: bool = False,
                           block_b: Optional[int] = None,
                           window: Optional[int] = None):
    """Blocked backward; q/k/v/out/g are ``[B, L, H, D]``, lse is the padded
    ``[B·H, q_len_p, 128]`` forward residual. One Mosaic call where
    :func:`backward_form` says its working set fits, else two."""
    if interpret is None:
        interpret = _backend.default_interpret()

    geom = _bwd_prep(q, k, v, out, g, block_q, block_kv)
    q_len, kv_len = geom.q_len, geom.kv_len
    dim_p, block_q, block_kv = geom.dim_p, geom.block_q, geom.block_kv
    dim_v_p = geom.dim_v_p
    q_len_p, kv_len_p = geom.q_len_p, geom.kv_len_p

    num_q_blocks = q_len_p // block_q
    num_kv_blocks = kv_len_p // block_kv
    bh = geom.batch * geom.heads
    block_b = _resolve_block_b(block_b, bh)
    operands = (geom.qf, geom.kf, geom.vf, geom.dof, lse, geom.delta)
    static = dict(
        scale=scale, q_len=q_len, kv_len=kv_len, block_b=block_b,
        block_q=block_q, block_kv=block_kv, causal=causal,
        **_band_statics(num_q_blocks, num_kv_blocks, block_q, block_kv, window),
    )

    # Under the causal mask a skipped cell names the block its neighbour
    # held, so nothing is fetched for it (see _flash_forward).
    if causal:
        kv_index = lambda b, i, j: (b, _visible_kv_block(i, j, block_q, block_kv, window), 0)
        q_index2 = lambda b, j, i: (b, _visible_q_block(j, i, block_q, block_kv, num_q_blocks, window), 0)
    else:
        kv_index = lambda b, i, j: (b, j, 0)
        q_index2 = lambda b, j, i: (b, i, 0)
    # q-innermost grid: block index 1 is the kv block, index 2 sweeps q
    # blocks into the dk/dv accumulators. q, k, dq, dk at the query/key
    # head; v, dO, dv at the value head.
    qspec2 = pl.BlockSpec((block_b, block_q, dim_p), q_index2)
    dospec2 = pl.BlockSpec((block_b, block_q, dim_v_p), q_index2)
    kspec2 = pl.BlockSpec((block_b, block_kv, dim_p), lambda b, j, i: (b, j, 0))
    vspec2 = pl.BlockSpec((block_b, block_kv, dim_v_p), lambda b, j, i: (b, j, 0))
    rowq2 = pl.BlockSpec((block_b, block_q, 128), q_index2)
    dkv = dict(
        grid=(bh // block_b, num_kv_blocks, num_q_blocks),
        in_specs=[qspec2, kspec2, vspec2, dospec2, rowq2, rowq2],
        out_specs=[kspec2, vspec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_len_p, dim_p), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_len_p, dim_v_p), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, block_kv, dim_p), jnp.float32),
            pltpu.VMEM((block_b, block_kv, dim_v_p), jnp.float32),
        ],
        interpret=interpret,
    )

    form = backward_form(
        q_len, kv_len, geom.dim, geom.dim_v, batch_heads=bh, block_q=block_q,
        block_kv=block_kv, block_b=block_b, itemsize=q.dtype.itemsize,
    )
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, num_q_blocks=num_q_blocks, num_kv_blocks=num_kv_blocks, **static
    )
    if form == "one_kernel":
        # dq's block is the batch·head cell's whole sequence: its map reads
        # the cell alone, and it is written back when the cell ends. Each
        # gradient takes the HBM buffer of the padded operand it is the
        # gradient of (the kernel has read a region for the last time
        # before it writes it: q's cell when the cell ends, k's and v's
        # block when its q sweep ends), so the three outputs of the one
        # call add nothing to the step's live bytes.
        one = dict(
            dkv,
            out_specs=[pl.BlockSpec((block_b, q_len_p, dim_p), lambda b, j, i: (b, 0, 0))] + dkv["out_specs"],
            out_shape=[jax.ShapeDtypeStruct((bh, q_len_p, dim_p), q.dtype)] + dkv["out_shape"],
            scratch_shapes=[pltpu.VMEM((block_b, num_q_blocks, block_q, dim_p), jnp.float32)]
            + dkv["scratch_shapes"],
        )
        dq, dk, dv = pl.pallas_call(
            functools.partial(dkv_kernel, with_dq=True),
            **one,
            input_output_aliases={0: 0, 1: 1, 2: 2},
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_ONE_KERNEL_VMEM_LIMIT),
        )(*operands)
    else:
        qspec = pl.BlockSpec((block_b, block_q, dim_p), lambda b, i, j: (b, i, 0))
        dospec = pl.BlockSpec((block_b, block_q, dim_v_p), lambda b, i, j: (b, i, 0))
        kspec = pl.BlockSpec((block_b, block_kv, dim_p), kv_index)
        vspec = pl.BlockSpec((block_b, block_kv, dim_v_p), kv_index)
        rowq = pl.BlockSpec((block_b, block_q, 128), lambda b, i, j: (b, i, 0))
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, num_kv_blocks=num_kv_blocks, **static),
            grid=(bh // block_b, num_q_blocks, num_kv_blocks),
            in_specs=[qspec, kspec, vspec, dospec, rowq, rowq],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((bh, q_len_p, dim_p), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_b, block_q, dim_p), jnp.float32)],
            interpret=interpret,
        )(*operands)
        dk, dv = pl.pallas_call(functools.partial(dkv_kernel, with_dq=False), **dkv)(*operands)

    return (
        geom.unprep(dq, q_len),
        geom.unprep(dk, kv_len),
        geom.unprep(dv, kv_len, geom.dim_v),
    )


# ---------------------------------------------------------------------------
# The in-place form (layout_form): the kernels address the operands where
# XLA lays them. A model's q, k, v and the cotangent of the output come out
# of a projection's matmul (and the rotary after it) with the sequence on
# the lanes: what XLA holds for a ``[B, L, H, D]`` array there is
# ``[B, H, D, L]``, so its transpose to that order moves nothing and a
# ``(1, D, block)`` block at ``(b, h, i)`` of the ``[B, H·D, L]`` view is one
# head's rows. The outputs (out, dq, dk, dv) are written ``[B, L, H·D]``,
# which reshapes to ``[B, L, H, D]`` for the matmul that takes them next.
# Same tiles, maps, accumulators and arithmetic as the head-major form, each
# direction a kernel of its own: the forward turns its q block once a kv
# sweep, the backward computes its tiles transposed, ``[block_kv, block_q]``,
# so that the logsumexp and ``delta`` are rows and no operand is turned per
# tile.
# ---------------------------------------------------------------------------


def _sequence_on_lanes(x: jax.Array) -> jax.Array:
    """``[B, L, H, D]`` -> ``[B, H·D, L]``."""
    batch, length, heads, dim = x.shape
    return jnp.transpose(x, (0, 2, 3, 1)).reshape(batch, heads * dim, length)


def _in_place_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, with_lse: bool, scale: float,
                         block_q: int, block_kv: int, num_kv_blocks: int, causal: bool,
                         window: Optional[int] = None, cases: tuple = ()):
    """The online softmax of :func:`_kernel` for one head's ``[d, block]``
    blocks of q, k and v; ``rest`` = ([lse_ref], m, l, acc, q_scr). The q
    block is turned to ``[block_q, d]`` once, when its kv sweep starts;
    ``q k`` and ``p v^T`` then take k and v as they lie, the output is
    summed as it is written, ``[block_q, d_v]``, and the logsumexp tile
    leaves as a row. (Tiles computed transposed, as the backward's are,
    need no turn of q or of the logsumexp but one of the k block every grid
    step: 5.8% slower on the chip, PERF.md section 6, PR 36.)"""
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr, q_scr = rest
    else:
        (m_scr, l_scr, acc_scr, q_scr), lse_ref = rest, None
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q_scr[...] = q_ref[0].T

    def fold(masked: bool):
        k, v = k_ref[0], v_ref[0]  # [d, block_kv], [d_v, block_kv]
        s = jax.lax.dot_general(
            q_scr[...], k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_kv]
        if masked:
            # As in :func:`_kernel`: under the causal mask alone a row's
            # first visited block always shows it a column.
            s = jnp.where(_causal_keep(qi, ki, block_q, block_kv, window=window, edges=masked), s, _NEG_INF)
        # The running max and sum are kept broadcast across a lane tile.
        m_prev, l_prev = m_scr[:, 0:1], l_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        against = _finite_max(m_new, window is not None)
        alpha = jnp.exp(m_prev - against)
        p = jnp.exp(s - against)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    _for_causal_blocks(causal, qi, ki, block_q, block_kv, fold, window, cases)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / l_scr[:, 0:1]).astype(o_ref.dtype)
        if lse_ref is not None:
            # The tile's rows go onto the lanes through the transpose unit,
            # and its first lane is the row.
            lse_ref[0, 0] = (m_scr[...] + jnp.log(l_scr[...])).T[0:1, :]


def _in_place_forward(q, k, v, scale, block_q, block_kv, interpret,
                      with_lse: bool = False, *, causal: bool = False,
                      window: Optional[int] = None):
    """The forward kernel on ``[B, L, H, D]`` operands where they lie (k and
    v may have fewer heads: query head ``h`` reads key/value head ``h //
    (H / H_kv)`` through the block index, and nothing is repeated in HBM); the
    output as the kernel writes it, ``[B, Lq, H·D_v]``. With ``with_lse``
    also the logsumexp, one float32 a row as ``[B, H, 1, Lq]`` (a block's
    last two dimensions are then one whole and one of lane tiles)."""
    batch, q_len, heads, dim = q.shape
    kv_len, dim_v = k.shape[1], v.shape[-1]
    group = heads // k.shape[2]  # query heads a key/value head serves
    if interpret is None:
        interpret = _backend.default_interpret()
    if causal and q_len != kv_len:
        raise ValueError(f"causal attention is self-attention: q_len {q_len} != kv_len {kv_len}")
    block_q, block_kv = _clamp_block(block_q, q_len), _clamp_block(block_kv, kv_len)
    num_kv_blocks = kv_len // block_kv

    if causal:
        kv_block = lambda i, j: _visible_kv_block(i, j, block_q, block_kv, window)
    else:
        kv_block = lambda i, j: j
    kv_head = (lambda h: h // group) if group > 1 else (lambda h: h)
    kv_index = lambda b, h, i, j: (b, kv_head(h), kv_block(i, j))
    out_specs = [pl.BlockSpec((1, block_q, dim_v), lambda b, h, i, j: (b, i, h))]
    out_shape = [jax.ShapeDtypeStruct((batch, q_len, heads * dim_v), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((batch, heads, 1, q_len), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _in_place_fwd_kernel, with_lse=with_lse, scale=scale, block_q=block_q,
            block_kv=block_kv, num_kv_blocks=num_kv_blocks, causal=causal,
            **_band_statics(q_len // block_q, num_kv_blocks, block_q, block_kv, window),
        ),
        grid=(batch, heads, q_len // block_q, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, dim, block_q), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, dim, block_kv), kv_index),
            pl.BlockSpec((1, dim_v, block_kv), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dim_v), jnp.float32),
            pltpu.VMEM((block_q, dim), q.dtype),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_ONE_KERNEL_VMEM_LIMIT),
        interpret=interpret,
    )(_sequence_on_lanes(q), _sequence_on_lanes(k), _sequence_on_lanes(v))
    return tuple(outs) if with_lse else outs[0]


def _in_place_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dq_ref, dk_ref, dv_ref,
                         dq_acc, dk_acc, dv_acc, k_scr, v_scr, delta_scr, *,
                         scale: float, block_q: int, block_kv: int,
                         num_q_blocks: int, num_kv_blocks: int, causal: bool,
                         window: Optional[int] = None, cases: tuple = ()):
    """dq, dk and dv of one batch·head cell, as :func:`_bwd_dkv_kernel`
    with ``with_dq`` computes them, on ``[d, block]`` blocks of q, k, v and
    dO. A pair of tiles is computed transposed, ``[block_kv, block_q]``:
    the q block's logsumexp and ``delta`` are then rows that broadcast down
    the tile, k and v are turned to ``[block_kv, d]`` once a kv block, and
    every matmul takes its operands as they are. ``delta = sum_d dO·O`` of a
    q block is computed when the cell first meets it (kv block 0, which
    every q block sees; under a window the first kv block the q block sees)
    and kept; the float32 dq of the cell is resident as
    ``[num_q_blocks, d, block_q]`` and turned as it is written out."""
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        k_scr[...] = k_ref[0].T
        v_scr[...] = v_ref[0].T

    @pl.when(ki == _first_kv_block(qi, block_q, block_kv, window))
    def _delta():
        delta_scr[qi] = jnp.sum(
            do_ref[0].astype(jnp.float32) * o_ref[0].T.astype(jnp.float32), axis=0, keepdims=True
        )

    def fold(masked: bool):
        q, do = q_ref[0], do_ref[0]  # [d, block_q], [d_v, block_q]
        s = jax.lax.dot_general(
            k_scr[...], q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_kv, block_q]
        p = jnp.exp(s - lse_ref[0, 0])
        if masked:
            keep = _causal_keep(qi, ki, block_q, block_kv, transposed=True, window=window, edges=masked)
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            v_scr[...], do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta_scr[qi])).astype(q.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale
        dq_acc[qi] += jax.lax.dot_general(
            k_ref[0], ds, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale

    _for_causal_blocks(causal, qi, ki, block_q, block_kv, fold, window, cases)

    @pl.when(qi == num_q_blocks - 1)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == num_kv_blocks - 1, qi == num_q_blocks - 1))
    def _write_dq():
        for i in range(num_q_blocks):
            dq_ref[0, i * block_q:(i + 1) * block_q, :] = dq_acc[i].T.astype(dq_ref.dtype)


def _in_place_backward(q, k, v, out, lse, g, scale, block_q, block_kv,
                       interpret, *, causal: bool = False,
                       window: Optional[int] = None):
    """The one-kernel backward on ``[B, L, H, D]`` operands where they lie;
    ``out`` and ``lse`` are the forward's ``[B, Lq, H·D_v]`` and
    ``[B, H, 1, Lq]``. dq, dk and dv are written as ``[B, L, H·D]`` arrays
    of their own (the operands' buffers hold another order). Where k and v
    have fewer heads than q, a cell reads its key/value head through the
    block index, writes its own query head's dk and dv, and the group's are
    summed after the call."""
    batch, q_len, heads, dim = q.shape
    kv_len, dim_v = k.shape[1], v.shape[-1]
    group = heads // k.shape[2]
    if interpret is None:
        interpret = _backend.default_interpret()
    block_q, block_kv = _clamp_block(block_q, q_len), _clamp_block(block_kv, kv_len)
    num_q_blocks, num_kv_blocks = q_len // block_q, kv_len // block_kv

    # Under the causal mask a skipped cell names the block its neighbour
    # held, so nothing is fetched for it; the output block is read while the
    # cell's first kv block computes ``delta`` and stays where it is after.
    # Under a window a q block's ``delta`` waits for the first kv block that
    # sees it: kv block ``j`` brings in the output blocks past the last one
    # kv block ``j - 1`` saw, up to the last it sees itself.
    if causal:
        q_block = lambda j, i: _visible_q_block(j, i, block_q, block_kv, num_q_blocks, window)
    else:
        q_block = lambda j, i: i
    if window is None:
        out_block = lambda j, i: jnp.where(j == 0, i, num_q_blocks - 1)
    else:
        last_seen = lambda j: _last_q_block(j, block_q, block_kv, num_q_blocks, window)
        out_block = lambda j, i: jnp.minimum(
            jnp.maximum(i, jnp.where(j == 0, 0, last_seen(j - 1) + 1)), last_seen(j)
        )
    q_index = lambda b, h, j, i: (b, h, q_block(j, i))
    kv_head = (lambda h: h // group) if group > 1 else (lambda h: h)
    kv_index = lambda b, h, j, i: (b, kv_head(h), j)
    dkv_index = lambda b, h, j, i: (b, j, h)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _in_place_bwd_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
            num_q_blocks=num_q_blocks, num_kv_blocks=num_kv_blocks, causal=causal,
            **_band_statics(num_q_blocks, num_kv_blocks, block_q, block_kv, window),
        ),
        grid=(batch, heads, num_kv_blocks, num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, dim, block_q), q_index),
            pl.BlockSpec((1, dim, block_kv), kv_index),
            pl.BlockSpec((1, dim_v, block_kv), kv_index),
            pl.BlockSpec((1, dim_v, block_q), q_index),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, j, i: (b, h, 0, q_block(j, i))),
            pl.BlockSpec((1, block_q, dim_v), lambda b, h, j, i: (b, out_block(j, i), h)),
        ],
        out_specs=[
            pl.BlockSpec((1, q_len, dim), lambda b, h, j, i: (b, 0, h)),
            pl.BlockSpec((1, block_kv, dim), dkv_index),
            pl.BlockSpec((1, block_kv, dim_v), dkv_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, q_len, heads * dim), q.dtype),
            # A group's dk and dv leave in float32: eight heads' are summed after.
            jax.ShapeDtypeStruct((batch, kv_len, heads * dim), k.dtype if group == 1 else jnp.float32),
            jax.ShapeDtypeStruct((batch, kv_len, heads * dim_v), v.dtype if group == 1 else jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((num_q_blocks, dim, block_q), jnp.float32),
            pltpu.VMEM((block_kv, dim), jnp.float32),
            pltpu.VMEM((block_kv, dim_v), jnp.float32),
            pltpu.VMEM((block_kv, dim), k.dtype),
            pltpu.VMEM((block_kv, dim_v), v.dtype),
            pltpu.VMEM((num_q_blocks, 1, block_q), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_ONE_KERNEL_VMEM_LIMIT),
        interpret=interpret,
    )(_sequence_on_lanes(q), _sequence_on_lanes(k), _sequence_on_lanes(v), _sequence_on_lanes(g), lse, out)
    return dq.reshape(q.shape), _sum_group(dk, k), _sum_group(dv, v)


def _sum_group(grad: jax.Array, like: jax.Array) -> jax.Array:
    """``grad [B, L, H·D]`` or ``[B, L, H, D]``, one a query head -> ``like``'s
    ``[B, L, H_kv, D]``: the sum over the query heads each key/value head
    serves, in float32 (a reshape alone where every head has its own)."""
    if grad.size == like.size:
        return grad.reshape(like.shape)
    batch, length, kv_heads, dim = like.shape
    grad = grad.reshape(batch, length, kv_heads, -1, dim)
    return jnp.sum(grad.astype(jnp.float32), axis=3).astype(like.dtype)


def _repeat_group(x: jax.Array, heads: int) -> jax.Array:
    """Each of ``x``'s heads repeated to the ``heads / H_kv`` query heads it
    serves: what the head-major form copies, since it copies anyway."""
    return x if x.shape[2] == heads else jnp.repeat(x, heads // x.shape[2], axis=2)


def _runs_in_place(q, k, v, bias, block_q, block_kv, block_b) -> bool:
    return layout_form(
        q.shape[1], k.shape[1], q.shape[-1], v.shape[-1],
        batch_heads=q.shape[0] * q.shape[2], biased=bias is not None,
        block_q=block_q, block_kv=block_kv, block_b=block_b,
        itemsize=q.dtype.itemsize,
    ) == "in_place"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, bias, scale, block_q, block_kv, interpret, causal, block_b, window):
    if _runs_in_place(q, k, v, bias, block_q, block_kv, block_b):
        out = _in_place_forward(q, k, v, scale, block_q, block_kv, interpret, causal=causal, window=window)
        return out.reshape(q.shape[:3] + v.shape[3:])
    return _flash_forward(
        q, _repeat_group(k, q.shape[2]), _repeat_group(v, q.shape[2]), bias, scale,
        block_q, block_kv, interpret, causal=causal, block_b=block_b, window=window,
    )


def _flash_fwd(q, k, v, bias, scale, block_q, block_kv, interpret, causal, block_b, window):
    if bias is not None:
        out = _flash_forward(
            q, _repeat_group(k, q.shape[2]), _repeat_group(v, q.shape[2]), bias, scale,
            block_q, block_kv, interpret, causal=causal, block_b=block_b, window=window,
        )
        return out, (q, k, v, bias, None, None)
    # The residuals are the output and one float32 a row of logsumexp. A
    # remat policy sees a custom_vjp's residuals only where the forward rule
    # names them: with these two kept (and the caller's q, k, v) the
    # backward pass runs no second forward kernel.
    if _runs_in_place(q, k, v, bias, block_q, block_kv, block_b):
        # Both as the kernel wrote them and as the backward kernel reads
        # them: a kept [B, L, H, D] would be laid out anew for every read.
        kept, lse_row = _in_place_forward(
            q, k, v, scale, block_q, block_kv, interpret, with_lse=True, causal=causal, window=window
        )
        kept = checkpoint_name(kept, "flash_out")
        out = kept.reshape(q.shape[:3] + v.shape[3:])
    else:
        # The head-major kernel writes a 128-lane tile a row, which its
        # backward rebuilds.
        out, lse = _flash_forward(
            q, _repeat_group(k, q.shape[2]), _repeat_group(v, q.shape[2]), bias, scale,
            block_q, block_kv, interpret, with_lse=True, causal=causal, block_b=block_b, window=window,
        )
        out = kept = checkpoint_name(out, "flash_out")
        lse_row = lse[..., 0]
    return out, (q, k, v, bias, kept, checkpoint_name(lse_row, "flash_lse"))


def _flash_bwd(scale, block_q, block_kv, interpret, causal, block_b, window, residuals, g):
    """Backward dispatch: blocked Pallas kernels when there is no bias, in
    the layout the forward ran in (the rule reads the same shapes); XLA
    flash-style recompute when a dbias is needed (the dense ``ds`` is
    unavoidable for the bias gradient)."""
    q, k, v, bias, out, lse_row = residuals
    if bias is None:
        if _runs_in_place(q, k, v, bias, block_q, block_kv, block_b):
            dq, dk, dv = _in_place_backward(
                q, k, v, out, lse_row, g, scale, block_q, block_kv, interpret, causal=causal, window=window
            )
            return dq, dk, dv, None
        lse = jnp.broadcast_to(lse_row[..., None], lse_row.shape + (128,))
        dq, dk, dv = _flash_backward_pallas(
            q, _repeat_group(k, q.shape[2]), _repeat_group(v, q.shape[2]), out, lse, g, scale,
            block_q, block_kv, interpret, causal=causal, block_b=block_b, window=window,
        )
        return dq, _sum_group(dk, k), _sum_group(dv, v), None
    del block_q, block_kv, interpret, block_b
    dq, dk, dv, dbias = _dense_recompute_bwd(
        q, _repeat_group(k, q.shape[2]), _repeat_group(v, q.shape[2]), bias, g, scale, causal=causal,
        window=window,
    )
    return dq, _sum_group(dk, k), _sum_group(dv, v), dbias


def _dense_recompute_bwd(q, k, v, bias, g, scale, *, causal: bool = False,
                         window: Optional[int] = None):
    """XLA flash-style recompute backward for the biased path — shared by
    this kernel and the fused short-sequence kernel
    (:mod:`sav_tpu.ops.fused_attention`): a dense dbias is O(L²) by
    construction, so the recompute materializes nothing the caller's bias
    gradient doesn't already require."""
    mm_dtype = q.dtype
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = jnp.where(_causal_keep(0, 0, *s.shape[-2:], window=window), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)  # [B, H, Lq, Lk] fp32
    p_mm = p.astype(mm_dtype)
    g_mm = g.astype(mm_dtype)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p_mm, g_mm, preferred_element_type=jnp.float32)
    dp = jnp.einsum(
        "bqhd,bkhd->bhqk", g_mm, v.astype(mm_dtype),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))  # fp32
    ds_mm = ds.astype(mm_dtype)
    dq = jnp.einsum(
        "bhqk,bkhd->bqhd", ds_mm, k.astype(mm_dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    dk = jnp.einsum(
        "bhqk,bqhd->bkhd", ds_mm, q.astype(mm_dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    if bias is not None:
        dbias = ds
        # Un-broadcast to the original bias shape.
        for axis in range(dbias.ndim):
            if bias.shape[axis] == 1 and dbias.shape[axis] != 1:
                dbias = jnp.sum(dbias, axis=axis, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    else:
        dbias = None
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# The band-resident pair (band_form): a sliding-window layer's kernels, whose
# grid IS the band. One grid cell is a q block and a key/value head. It holds
# the group's query heads (adjacent rows of the ``[B, H·D, L]`` view, so one
# ``(1, group·D, block)`` block) and the ``resident`` kv blocks the q block
# can see (its own and those before it: the same k array named ``resident``
# times, at ``i - t``), and finishes each head's softmax inside the cell: no
# statistics carried across grid steps, no accumulator rescaled, no cell
# without work, k and v fetched once a group and repeated nowhere. With equal
# q and kv blocks the geometry of the ``t``-th block behind the q block's own
# is the same in every cell: which edge crosses it is static
# (:func:`_band_tile_edges`), and only the first ``resident - 1`` cells of a
# sequence differ, by holding fewer blocks (a body a count).
#
# Operands AND results lie with the sequence on the lanes, ``[B, H·D, L]``:
# q, k, v and dO as the in-place causal kernels read them, and out, dq, dk
# and dv too (those kernels write theirs ``[B, L, H·D]``). What takes the
# results next in a decoder block is not a matmul alone: the output meets a
# head-wise gate, dq, dk and dv the rotary's and the norm's backward, all of
# which XLA runs in the order their forward left, the sequence on the lanes;
# written ``[B, L, H·D]``, each result was laid out anew in float32 before
# its first use (six q-sized copies a layer in the compiled step, one with
# the results where they are read: PERF.md section 6, PR 47).
#
# The forward stacks the group's heads on the rows, ``[group·block_q, d]``
# against k and v as they lie: one matmul a kv block streams the whole group
# past it (12% under a matmul a head on the chip, same section). The
# backward computes a head's tiles transposed, ``[block_kv, block_q]``, as
# the in-place causal backward does: the logsumexp and ``delta`` are rows, k
# and v are turned once a cell for the whole group, and o, dO, q and dq are
# used as they lie. It runs over the same cells: dq of a (head, q block) is
# whole inside its cell; dk and dv of a kv block gather from ``resident``
# consecutive cells and the group's heads in a float32 VMEM ring, and leave
# once, at the key/value heads and in the operands' dtype, when the last
# cell that sees the block has added its share (``resident - 1`` flush cells
# at a sequence's end write the ring's tail).
# ---------------------------------------------------------------------------

# The pair's bodies are unrolled over the group's heads and the resident
# blocks, one body a count of blocks held: ``group * resident * (resident + 1)
# / 2`` tiles of code a kernel. Past this many the banded arm of the causal
# kernels runs (a window of many blocks is nearer the triangle than the band).
BAND_MAX_UNROLLED_TILES = 192


def band_resident_blocks(q_len: int, block: int, window: int) -> int:
    """The kv blocks a q block of ``block`` rows can see under ``window``:
    its own and the ``ceil((window - 1) / block)`` before it (no more than
    the sequence has)."""
    return min(-(-(window - 1) // block) + 1, -(-q_len // block))


def _band_tile_edges(t: int, block: int, window: int) -> tuple:
    """``(diagonal, far)`` of the ``t``-th kv block behind a q block's own:
    the diagonal crosses the q block's own alone, the window's far edge
    (``col == row - window``) every block whose first column some row of the
    q block, ``t`` blocks on, no longer sees."""
    return t == 0, t * block + block - 1 >= window


def band_vmem_bytes(group: int, dim: int, dim_v: int, *, block: int, resident: int, itemsize: int = 2) -> dict:
    """Estimated VMEM working set of a cell of each kernel of the resident
    pair. ``backward``: the group's q, dO, o and dq blocks and one k, v
    block a resident block in, dk and dv out, every block double-buffered;
    a resident block's slot of the float32 ring, its partial sums in the
    cell and their sum under way, k and v turned; and six float32 logits
    tiles (two heads' ``s``, ``dp``, ``ds`` in flight). ``forward``: q and o,
    the logsumexp and the k, v blocks double-buffered, the group's q turned,
    and five bytes a logit of the whole group against every resident block
    (``s`` and what of ``p`` Mosaic keeps beside it). Compiled for a
    described v5e at groups of 1 to 9, blocks 128 to 1,024 and two to
    seventeen resident blocks, Mosaic reports using 44 to 94% of the
    forward's and 35 to 96% of the backward's (a group of one holds the
    least of it)."""
    heads, kv_blocks = dim + dim_v, resident * (dim + dim_v) * block * itemsize
    rows = _round_up(group, 8) * block * 4  # the logsumexp
    backward = 2 * (2 * group * heads * block * itemsize + rows + kv_blocks + heads * block * itemsize)
    backward += resident * heads * block * (4 + 4 + 4 + itemsize) + 6 * block * block * 4
    forward = 2 * (group * heads * block * itemsize + rows + kv_blocks) + group * dim * block * itemsize
    forward += 5 * resident * group * block * block
    return {"forward": forward, "backward": backward}


def band_form(q_len: int, kv_len: int, dim: int, dim_v: int, *, heads: int, kv_heads: int,
              window: Optional[int], biased: bool = False, block_q: int = DEFAULT_BLOCK,
              block_kv: int = DEFAULT_BLOCK, itemsize: int = 2) -> Optional[str]:
    """Which kernels a :func:`flash_attention` of these shapes and blocks
    runs a band in: None without an effective ``window``; ``resident``, the
    pair above, for an unbiased call whose heads are whole 128-lane tiles,
    whose q and kv blocks are equal whole lane tiles that divide the
    sequence, and whose cell (the group's heads, the resident blocks, the
    ring) fits the one-kernel backward's VMEM budget and the unrolled
    program's bound; else ``skipped_cells``, the causal kernels' banded arm
    (a head of 64, a ragged length, a bias, unequal blocks, a window of too
    many blocks). A rule on what the call can see, nothing a caller sets."""
    if window is None:
        return None
    block = _clamp_block(block_q, q_len)
    whole = (
        not biased and q_len == kv_len and block == _clamp_block(block_kv, kv_len)
        and dim % 128 == 0 and dim_v % 128 == 0 and block % 128 == 0 and q_len % block == 0
    )
    if not whole:
        return "skipped_cells"
    group, resident = heads // kv_heads, band_resident_blocks(q_len, block, window)
    fits = (
        group * resident * (resident + 1) // 2 <= BAND_MAX_UNROLLED_TILES
        and max(band_vmem_bytes(group, dim, dim_v, block=block, resident=resident, itemsize=itemsize).values())
        <= ONE_KERNEL_VMEM_BUDGET
    )
    return "resident" if fits else "skipped_cells"


def band_cells(q_len: int, kv_len: int, *, block_q: int = DEFAULT_BLOCK, block_kv: int = DEFAULT_BLOCK,
               window: int, form: str) -> dict:
    """Static counts of a banded call a head (``form`` as :func:`band_form`
    gives it): ``kv_blocks_visited``, the (q block, kv block) pairs with work
    under the window; ``kv_blocks_causal``, those under the causal mask alone;
    ``kv_blocks_grid``, the pairs the grid spans: the causal square for
    ``skipped_cells``, ``resident`` a q block for ``resident``, of which the
    ``resident (resident - 1) / 2`` before a sequence's start are clipped
    (held by no body); and, ``resident``, the ``flush_cells`` after a
    sequence's last q block in which the backward writes the ring's tail."""
    counts = visited_blocks(q_len, kv_len, block_q=block_q, block_kv=block_kv, window=window)
    block_q, block_kv = _clamp_block(block_q, q_len), _clamp_block(block_kv, kv_len)
    if form != "resident":
        return dict(counts, kv_blocks_grid=-(-q_len // block_q) * -(-kv_len // block_kv))
    resident = band_resident_blocks(q_len, block_q, window)
    return dict(counts, kv_blocks_grid=(q_len // block_q) * resident, flush_cells=resident - 1)


def _band_keeps(count: int, block: int, window: int, transposed: bool) -> list:
    """The element mask of each of a cell's ``count`` tiles (None where no
    edge crosses it): :func:`band_keep` through :func:`_causal_keep`, the q
    block ``t`` blocks on from the kv block (``transposed``: rows on the lanes)."""
    def keep(t):
        edges = _band_tile_edges(t, block, window)
        return _causal_keep(t, 0, block, block, transposed=transposed, window=window, edges=edges) if any(edges) else None
    return [keep(t) for t in range(count)]


def _for_band_cells(i, resident: int, num_blocks: int, cell):
    """Run ``cell(count)`` for q block ``i``: the first ``resident - 1`` cells
    of a sequence hold ``i + 1`` blocks, every later one ``resident``; past
    the last q block (the backward's flush cells) nothing."""
    for count in range(1, resident):
        pl.when(i == count - 1)(functools.partial(cell, count))
    pl.when(jnp.logical_and(i >= resident - 1, i < num_blocks))(functools.partial(cell, resident))


def _band_fwd_kernel(q_ref, *rest, resident: int, group: int, dim: int, dim_v: int, block: int,
                     window: int, scale: float, with_lse: bool, num_blocks: int):
    """One q block of a key/value head's ``group`` query heads against the
    kv blocks it sees; ``rest`` = (k_ref x resident, v_ref x resident, o_ref,
    [lse_ref]). The heads are stacked on the rows (a row is a head and a
    position; the q blocks are turned once a cell), so a kv block meets the
    whole group in one matmul; a row's softmax is whole inside the cell: the
    logits of its tiles, their joint max and sum, ``p v`` summed and
    normalised."""
    k_refs, v_refs = rest[:resident], rest[resident:2 * resident]
    o_ref = rest[2 * resident]
    lse_ref = rest[2 * resident + 1] if with_lse else None

    def cell(count: int):
        q = jnp.concatenate([q_ref[0, g * dim:(g + 1) * dim, :].T for g in range(group)], axis=0)  # [group·block, d]
        keeps = [
            None if keep is None else jnp.concatenate([keep] * group, axis=0)
            for keep in _band_keeps(count, block, window, transposed=False)
        ]
        s = []
        for t in range(count):
            s_t = jax.lax.dot_general(
                q, k_refs[t][0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [group·block_q, block_kv]
            s.append(s_t if keeps[t] is None else jnp.where(keeps[t], s_t, _NEG_INF))
        # Every row sees its own column: the max is finite.
        m = jnp.max(functools.reduce(jnp.maximum, s), axis=1, keepdims=True)
        p = [jnp.exp(s_t - m) for s_t in s]
        l = jnp.sum(functools.reduce(jnp.add, p), axis=1, keepdims=True)
        acc = sum(
            jax.lax.dot_general(
                p[t].astype(v_refs[t].dtype), v_refs[t][0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for t in range(count)
        )  # [group·block_q, d_v]
        out = (acc / l).astype(o_ref.dtype)
        # A head's rows of the output go onto the lanes through the transpose
        # unit; so do the logsumexp's, a lane tile a head, whose first lane is
        # the row.
        lse = jnp.broadcast_to(m + jnp.log(l), (group * block, 128)) if lse_ref is not None else None
        for g in range(group):
            o_ref[0, g * dim_v:(g + 1) * dim_v, :] = out[g * block:(g + 1) * block].T
            if lse_ref is not None:
                lse_ref[0, 0, g:g + 1, :] = lse[g * block:(g + 1) * block].T[0:1, :]

    _for_band_cells(pl.program_id(2), resident, num_blocks, cell)


def _band_kv_specs(resident: int, dim: int, dim_v: int, block: int, num_blocks: int) -> list:
    """k, then v, ``resident`` times each: block ``t`` of a cell is the kv
    block ``t`` before the q block's own (before a sequence's start: block 0
    again, which no body reads and nothing new is fetched for; in the
    backward's flush cells, past the last q block: the last cell's)."""
    def index(t):
        return lambda b, h, i: (b, h, jnp.maximum(jnp.minimum(i, num_blocks - 1) - t, 0))
    return [pl.BlockSpec((1, d, block), index(t)) for d in (dim, dim_v) for t in range(resident)]


def _sequence_off_lanes(x: jax.Array, heads: int) -> jax.Array:
    """``[B, H·D, L]`` -> ``[B, L, H, D]``: :func:`_sequence_on_lanes` undone."""
    batch, rows, length = x.shape
    return jnp.transpose(x.reshape(batch, heads, rows // heads, length), (0, 3, 1, 2))


def _band_geometry(q, k, v, block: int, window: int) -> tuple:
    """``(batch, length, kv_heads, group, dim, dim_v, block, num_blocks,
    resident)`` of a call :func:`band_form` gave the resident pair."""
    batch, length, heads, dim = q.shape
    block = _clamp_block(block, length)
    return (batch, length, k.shape[2], heads // k.shape[2], dim, v.shape[-1], block, length // block,
            band_resident_blocks(length, block, window))


def _band_forward(q, k, v, scale, block, interpret, window, with_lse: bool = False):
    """The resident forward on ``[B, L, H, D]`` operands where they lie; the
    output as the kernel writes it, ``[B, H·D_v, L]``, and with ``with_lse``
    the logsumexp, one float32 a row as ``[B, H_kv, group, L]``."""
    batch, length, kv_heads, group, dim, dim_v, block, num_blocks, resident = _band_geometry(q, k, v, block, window)
    if interpret is None:
        interpret = _backend.default_interpret()
    out_specs = [pl.BlockSpec((1, group * dim_v, block), lambda b, h, i: (b, h, i))]
    out_shape = [jax.ShapeDtypeStruct((batch, kv_heads * group * dim_v, length), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, group, block), lambda b, h, i: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((batch, kv_heads, group, length), jnp.float32))
    k_lanes, v_lanes = _sequence_on_lanes(k), _sequence_on_lanes(v)
    outs = pl.pallas_call(
        functools.partial(
            _band_fwd_kernel, resident=resident, group=group, dim=dim, dim_v=dim_v, block=block,
            window=window, scale=scale, with_lse=with_lse, num_blocks=num_blocks,
        ),
        grid=(batch, kv_heads, num_blocks),
        in_specs=[pl.BlockSpec((1, group * dim, block), lambda b, h, i: (b, h, i))]
        + _band_kv_specs(resident, dim, dim_v, block, num_blocks),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_ONE_KERNEL_VMEM_LIMIT),
        interpret=interpret,
    )(_sequence_on_lanes(q), *([k_lanes] * resident), *([v_lanes] * resident))
    return tuple(outs) if with_lse else outs[0]


def _band_bwd_kernel(q_ref, do_ref, lse_ref, o_ref, *rest, resident: int, group: int, dim: int, dim_v: int,
                     block: int, window: int, scale: float, num_blocks: int):
    """dq of the cell's q block, whole, and the cell's share of dk and dv of
    the kv blocks it sees; ``rest`` = (k_ref x resident, v_ref x resident,
    dq_ref, dk_ref, dv_ref, dk_ring, dv_ring). kv block ``j`` lives in slot
    ``j % resident`` of the float32 rings from cell ``j``, which opens it, to
    cell ``j + resident - 1``, which adds the last share and writes it out.
    The arithmetic of :func:`_in_place_bwd_kernel`: tiles transposed,
    ``delta`` from the o and dO blocks the cell holds."""
    k_refs, v_refs = rest[:resident], rest[resident:2 * resident]
    dq_ref, dk_ref, dv_ref, dk_ring, dv_ring = rest[2 * resident:]
    i = pl.program_id(2)

    def cell(count: int):
        k = [k_refs[t][0] for t in range(count)]  # [d, block]
        k_t = [x.T for x in k]  # [block, d]
        v_t = [v_refs[t][0].T for t in range(count)]  # [block, d_v]
        keeps = _band_keeps(count, block, window, transposed=True)
        dk, dv = [None] * count, [None] * count
        for g in range(group):
            q = q_ref[0, g * dim:(g + 1) * dim, :]  # [d, block]
            do = do_ref[0, g * dim_v:(g + 1) * dim_v, :]  # [d_v, block]
            out = o_ref[0, g * dim_v:(g + 1) * dim_v, :]
            delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=0, keepdims=True)
            lse = lse_ref[0, 0, g:g + 1, :]
            dq = None
            for t in range(count):
                s = jax.lax.dot_general(
                    k_t[t], q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                ) * scale  # [block_kv, block_q]
                p = jnp.exp(s - lse)
                if keeps[t] is not None:
                    p = jnp.where(keeps[t], p, 0.0)
                dp = jax.lax.dot_general(
                    v_t[t], do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                ds = (p * (dp - delta)).astype(q.dtype)
                dv_g = jax.lax.dot_general(
                    p.astype(do.dtype), do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                dk_g = jax.lax.dot_general(ds, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                dq_g = jax.lax.dot_general(k[t], ds, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                dv[t] = dv_g if dv[t] is None else dv[t] + dv_g
                dk[t] = dk_g if dk[t] is None else dk[t] + dk_g
                dq = dq_g if dq is None else dq + dq_g
            dq_ref[0, g * dim:(g + 1) * dim, :] = (dq * scale).astype(dq_ref.dtype)
        for t in range(count):
            slot = jax.lax.rem(i - t, resident)
            if t == 0:  # the cell of a kv block's own q block opens its slot
                dk_ring[slot], dv_ring[slot] = dk[t] * scale, dv[t]
            else:
                dk_ring[slot] += dk[t] * scale
                dv_ring[slot] += dv[t]

    _for_band_cells(i, resident, num_blocks, cell)

    @pl.when(i >= resident - 1)
    def _write():  # kv block i - (resident - 1) has met its last q block
        slot = jax.lax.rem(i + 1, resident)
        dk_ref[0] = dk_ring[slot].astype(dk_ref.dtype).T
        dv_ref[0] = dv_ring[slot].astype(dv_ref.dtype).T


def _band_backward(q, k, v, out, lse, g, scale, block, interpret, window):
    """The resident backward, one call: ``out`` and ``lse`` as
    :func:`_band_forward` wrote them; dq, and dk and dv at the key/value
    heads in the operands' dtype, written ``[B, H·D, L]`` and returned as
    the ``[B, L, H, D]`` views of that; nothing summed after the call."""
    batch, length, kv_heads, group, dim, dim_v, block, num_blocks, resident = _band_geometry(q, k, v, block, window)
    if interpret is None:
        interpret = _backend.default_interpret()
    # Past the last q block the flush cells name the last cell's blocks:
    # nothing is fetched, and the dq block stays where it is until the end.
    q_index = lambda b, h, i: (b, h, jnp.minimum(i, num_blocks - 1))
    # dk and dv of kv block j leave after cell j + resident - 1; until the
    # first block is whole the output block named is block 0, written last.
    dkv_index = lambda b, h, i: (b, h, jnp.maximum(i - (resident - 1), 0))
    k_lanes, v_lanes = _sequence_on_lanes(k), _sequence_on_lanes(v)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _band_bwd_kernel, resident=resident, group=group, dim=dim, dim_v=dim_v, block=block,
            window=window, scale=scale, num_blocks=num_blocks,
        ),
        grid=(batch, kv_heads, num_blocks + resident - 1),
        in_specs=[
            pl.BlockSpec((1, group * dim, block), q_index),
            pl.BlockSpec((1, group * dim_v, block), q_index),
            pl.BlockSpec((1, 1, group, block), lambda b, h, i: (b, h, 0, jnp.minimum(i, num_blocks - 1))),
            pl.BlockSpec((1, group * dim_v, block), q_index),
        ] + _band_kv_specs(resident, dim, dim_v, block, num_blocks),
        out_specs=[
            pl.BlockSpec((1, group * dim, block), q_index),
            pl.BlockSpec((1, dim, block), dkv_index),
            pl.BlockSpec((1, dim_v, block), dkv_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, kv_heads * group * dim, length), q.dtype),
            jax.ShapeDtypeStruct((batch, kv_heads * dim, length), k.dtype),
            jax.ShapeDtypeStruct((batch, kv_heads * dim_v, length), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((resident, block, dim), jnp.float32),
            pltpu.VMEM((resident, block, dim_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_ONE_KERNEL_VMEM_LIMIT),
        interpret=interpret,
    )(_sequence_on_lanes(q), _sequence_on_lanes(g), lse, out, *([k_lanes] * resident), *([v_lanes] * resident))
    return _sequence_off_lanes(dq, q.shape[2]), _sequence_off_lanes(dk, kv_heads), _sequence_off_lanes(dv, kv_heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _band(q, k, v, scale, block, interpret, window):
    return _sequence_off_lanes(_band_forward(q, k, v, scale, block, interpret, window), q.shape[2])


def _band_fwd(q, k, v, scale, block, interpret, window):
    # The residuals as :func:`_flash_fwd` names them: the output and the
    # logsumexp as the kernel wrote them and as the backward kernel reads them.
    kept, lse = _band_forward(q, k, v, scale, block, interpret, window, with_lse=True)
    kept = checkpoint_name(kept, "flash_out")
    return _sequence_off_lanes(kept, q.shape[2]), (q, k, v, kept, checkpoint_name(lse, "flash_lse"))


def _band_bwd(scale, block, interpret, window, residuals, g):
    q, k, v, out, lse = residuals
    return _band_backward(q, k, v, out, lse, g, scale, block, interpret, window)


_band.defvjp(_band_fwd, _band_bwd)


def flash_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_kv: int = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
    causal: bool = False,
    block_b: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused flash attention.

    Args:
      query: ``[B, q_len, heads, head_dim]``.
      key: ``[B, kv_len, kv_heads, head_dim]``; ``kv_heads`` divides
        ``heads`` (grouped-query attention: query head ``h`` reads key/value
        head ``h // (heads / kv_heads)``). In place the kernels find the head
        through their block index; the head-major form repeats k and v in the
        copies it makes anyway. dk and dv are summed over a group either way.
      value: ``[B, kv_len, kv_heads, value_dim]``; ``value_dim`` may differ
        from ``head_dim`` (latent attention: 192 / 128) on the unbiased
        path, and the output then has the value's head size.
      bias: optional additive logits bias, broadcastable to
        ``[B, heads, q_len, kv_len]`` (e.g. BoTNet relative-position logits).
      scale: logit scale, default ``head_dim ** -0.5``.
      block_q / block_kv: VMEM tile sizes (clamped for short sequences),
        :data:`DEFAULT_BLOCK` unless a measured entry says otherwise.
      interpret: force Pallas interpreter mode; default = auto (on for non-TPU).
      causal: position ``i`` attends to ``j <= i`` (``q_len == kv_len``);
        blocks above the diagonal are skipped, forward and backward.
      block_b: batch·head slices per grid cell; default by
        :func:`_pick_block_b`. A long causal sequence wants 1: many kv
        blocks a cell already amortise the grid's step, and VMEM holds
        ``block_b`` tiles of everything.
      window: with ``causal``, position ``i`` attends to ``i - window < j <=
        i`` (itself and the ``window - 1`` before it). A q block meets the
        kv blocks from its first row's farthest column to its diagonal and
        no others, forward and backward; the far edge is masked in the
        blocks it crosses. Which kernels run the band is :func:`band_form`'s
        rule on the shapes: the resident pair, whose grid is the band, or
        the causal kernels' arm, which skips the cells outside it. A window
        no shorter than the sequence is the causal mask and runs the causal
        kernels.

    Returns:
      ``[B, q_len, heads, value_dim]`` in the query dtype.
    """
    if query.ndim != 4:
        raise ValueError(f"expected [B, L, H, D] inputs, got {query.shape}")
    if key.shape[-1] != query.shape[-1]:
        raise ValueError(f"query and key heads differ: {query.shape[-1]} != {key.shape[-1]}")
    if key.shape[2] != value.shape[2] or query.shape[2] % key.shape[2]:
        raise ValueError(
            f"{query.shape[2]} query heads on {key.shape[2]} key and {value.shape[2]} value heads"
        )
    if scale is None:
        scale = query.shape[-1] ** -0.5
    if bias is not None and bias.ndim != 4:
        raise ValueError(f"bias must be 4-D broadcastable, got {bias.shape}")
    window = effective_window(window, causal, query.shape[1])
    banded = band_form(
        query.shape[1], key.shape[1], query.shape[-1], value.shape[-1], heads=query.shape[2],
        kv_heads=key.shape[2], window=window, biased=bias is not None, block_q=block_q, block_kv=block_kv,
        itemsize=query.dtype.itemsize,
    )
    if banded == "resident":
        return _band(query, key, value, float(scale), block_q, interpret, window)
    return _flash(
        query, key, value, bias, float(scale), block_q, block_kv, interpret,
        bool(causal), block_b, window,
    )


def effective_window(window: Optional[int], causal: bool, q_len: int) -> Optional[int]:
    """The window the kernels are built with: None for one that hides
    nothing of a sequence of ``q_len`` (the causal mask alone)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"a window of {window} needs causal attention and at least one position")
    return None if window >= q_len else int(window)


def visited_blocks(q_len: int, kv_len: int, *, block_q: int = DEFAULT_BLOCK, block_kv: int = DEFAULT_BLOCK,
                   window: Optional[int] = None) -> dict:
    """``kv_blocks_visited`` and ``kv_blocks_causal`` of a causal call of
    these lengths and blocks, a batch·head slice: the grid cells with work
    under ``window`` and under the causal mask alone (static, from the grid
    :func:`flash_attention` builds)."""
    block_q, block_kv = _clamp_block(block_q, q_len), _clamp_block(block_kv, kv_len)
    counts = band_blocks(
        -(-q_len // block_q), -(-kv_len // block_kv), block_q, block_kv, effective_window(window, True, q_len)
    )
    return {"kv_blocks_visited": counts["visited"], "kv_blocks_causal": counts["causal"]}

# ---------------------------------------------------------------------------
# BoTNet 2-D relative-position flash attention (SURVEY.md §7 "hard parts"):
# the rel_h + rel_w logits are folded into the flash inner loop instead of
# materializing the [B, heads, L, L] bias in HBM. The learned tables enter
# as *compact* per-axis logits [B, heads, L, 2W-1] (a small XLA einsum);
# the kernel expands them to the block's [block_q, block_kv] bias with iota
# index arithmetic and 2W-1 + 2H-1 unrolled masked adds — no gathers.
# ---------------------------------------------------------------------------


def _rel_selection_mats(ki, block_kv, wp, hp, width):
    """Iota-built 0/1 selection matrices for one kv block:
    ``S_w[r, c] = (kw(ki·block_kv + c) == r)`` (and ``kh`` for S_h), so
    ``bias_blk = rw_abs_blk @ S_w + rh_abs_blk @ S_h`` — two small MXU
    matmuls instead of a gather. Shared by the forward and both backward
    kernels (the backward's ``d_rw = dS @ S_wᵀ`` is the exact transpose)."""

    def selection(rows, key_coord):
        col = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 1
        )
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 0)
        return (key_coord(col) == row).astype(jnp.float32)

    sel_w = selection(wp, lambda c: c % width)
    sel_h = selection(hp, lambda c: c // width)
    return sel_w, sel_h


def _rel_bias_block(rw, rh, sel_w, sel_h):
    bias = jax.lax.dot_general(
        rw, sel_w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return bias + jax.lax.dot_general(
        rh, sel_h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _rel_kernel(
    q_ref,
    k_ref,
    v_ref,
    rw_ref,
    rh_ref,
    o_ref,
    *rest,
    scale: float,
    kv_len: int,
    block_kv: int,
    num_kv_blocks: int,
    width: int,
    with_lse: bool,
):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        (m_scr, l_scr, acc_scr), lse_ref = rest, None
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale

    # Expand the absolute per-axis logits to this block's bias:
    #   bias[q, k] = rw_abs[q, kw(k)] + rh_abs[q, kh(k)].
    # Padded rows of rw/rh are zero and padded selection rows never match,
    # so padding contributes nothing; padded kv columns are masked below.
    rw = rw_ref[0]  # [block_q, pad(W)] f32
    rh = rh_ref[0]  # [block_q, pad(H)] f32
    sel_w, sel_h = _rel_selection_mats(
        ki, block_kv, rw.shape[1], rh.shape[1], width
    )
    s = s + _rel_bias_block(rw, rh, sel_w, sel_h)

    if num_kv_blocks * block_kv != kv_len:
        kcol = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kcol < kv_len, s, _NEG_INF)

    _online_softmax_step(s, v_ref[0], m_scr, l_scr, acc_scr, 0)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        _write_output(o_ref, lse_ref, m_scr, l_scr, acc_scr, 0)


def _rel_forward(q, k, v, rw_abs, rh_abs, height, width, scale, block_q,
                 block_kv, interpret, with_lse=False):
    """q/k/v ``[B, L, H, D]``; rw_abs/rh_abs ``[B, heads, L, W / H]`` f32
    absolute per-axis relative-position logits. ``with_lse`` additionally
    returns the ``[B·H, padded_q_len, 128]`` per-row logsumexp residual the
    blocked backward consumes."""
    batch, q_len, heads, dim = q.shape
    kv_len = k.shape[1]
    if interpret is None:
        interpret = _backend.default_interpret()

    def to_bhld(x):
        b, l, h, d = x.shape
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)

    qf, kf, vf = to_bhld(q), to_bhld(k), to_bhld(v)
    dim_p = _round_up(dim, 128)
    block_q = _clamp_block(block_q, q_len)
    block_kv = _clamp_block(block_kv, kv_len)
    q_len_p = _round_up(q_len, block_q)
    kv_len_p = _round_up(kv_len, block_kv)

    def pad3(x, lp):
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, dim_p - x.shape[2])))

    qf, kf, vf = pad3(qf, q_len_p), pad3(kf, kv_len_p), pad3(vf, kv_len_p)

    def prep_compact(c):
        bb, hh, ll, rr = c.shape
        cf = c.reshape(bb * hh, ll, rr).astype(jnp.float32)
        return jnp.pad(
            cf, ((0, 0), (0, q_len_p - ll), (0, _round_up(rr, 128) - rr))
        )

    rwf, rhf = prep_compact(rw_abs), prep_compact(rh_abs)

    num_q_blocks = q_len_p // block_q
    num_kv_blocks = kv_len_p // block_kv
    grid = (batch * heads, num_q_blocks, num_kv_blocks)
    kernel = functools.partial(
        _rel_kernel,
        scale=scale,
        kv_len=kv_len,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
        width=width,
        with_lse=with_lse,
    )
    out_specs = [
        pl.BlockSpec((1, block_q, dim_p), lambda b, i, j: (b, i, 0))
    ]
    out_shape = [
        jax.ShapeDtypeStruct((batch * heads, q_len_p, dim_p), q.dtype)
    ]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((batch * heads, q_len_p, 128), jnp.float32)
        )
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dim_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, dim_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, dim_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec(
                (1, block_q, rwf.shape[-1]), lambda b, i, j: (b, i, 0)
            ),
            pl.BlockSpec(
                (1, block_q, rhf.shape[-1]), lambda b, i, j: (b, i, 0)
            ),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((1, block_q, 128), jnp.float32),
            pltpu.VMEM((1, block_q, 128), jnp.float32),
            pltpu.VMEM((1, block_q, dim_p), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, rwf, rhf)
    out_raw = outs[0] if with_lse else outs
    out = out_raw[:, :q_len, :dim].reshape(batch, heads, q_len, dim)
    out = jnp.transpose(out, (0, 2, 1, 3))
    if with_lse:
        return out, outs[1]
    return out


def compact_to_absolute(cw: jax.Array, ch: jax.Array, height: int,
                        width: int) -> tuple[jax.Array, jax.Array]:
    """Relative-indexed per-axis logits → absolute-indexed.

    ``cw``: ``[B, heads, L, 2W-1]`` (``cw[..., q, r] = q_vec · rel_w[r]``) →
    ``rw_abs [B, heads, L, W]`` with ``rw_abs[..., q, kw] = cw[..., q,
    kw - qw + W - 1]`` — the pad-reshape-slice ``rel_to_abs`` trick, applied
    once in XLA so the kernel only does matmul expansion. Same for ``ch``
    along the height axis.
    """
    from sav_tpu.ops.relative import rel_to_abs

    b, h, l, _ = cw.shape
    rw = rel_to_abs(cw.reshape(b, h, height, width, 2 * width - 1))
    rw_abs = rw.reshape(b, h, l, width)
    ch_t = jnp.swapaxes(ch.reshape(b, h, height, width, 2 * height - 1), 2, 3)
    rh = rel_to_abs(ch_t)  # [b, h, W, H, H] = [b, n, y, x, X]
    rh_abs = jnp.transpose(rh, (0, 1, 3, 2, 4)).reshape(b, h, l, height)
    return rw_abs, rh_abs


def expand_relative_bias(rw_abs: jax.Array, rh_abs: jax.Array, height: int,
                         width: int) -> jax.Array:
    """Absolute per-axis logits → full ``[B, heads, L, L]`` bias.

    ``bias[q, kh·W + kw] = rh_abs[q, kh] + rw_abs[q, kw]`` — a broadcast
    sum, so its autodiff transpose is the reduction the backward needs.
    """
    b, h, l, _ = rw_abs.shape
    bias = rh_abs[..., :, None] + rw_abs[..., None, :]  # [b, h, L, H, W]
    return bias.reshape(b, h, l, l)


def _rel_recompute_ds(q, k, v, do, rw, rh, lse_row, delta_row, ki, qi, *,
                      scale, q_len, kv_len, block_q, block_kv, width):
    """Shared backward recompute for one (q block, kv block) pair: rebuild
    the biased logits, normalize against the forward lse, mask padded
    rows/cols, and return ``(p, ds)``. Single source of recompute semantics
    for both backward kernels (dq and dk/dv)."""
    sel_w, sel_h = _rel_selection_mats(
        ki, block_kv, rw.shape[1], rh.shape[1], width
    )
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s = s + _rel_bias_block(rw, rh, sel_w, sel_h)
    p = jnp.exp(s - _lanes(lse_row, s.shape[1]))
    if kv_len % block_kv != 0:
        col = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.where(col < kv_len, p, 0.0)
    if q_len % block_q != 0:
        row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        p = jnp.where(row < q_len, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - _lanes(delta_row, s.shape[1]))
    return p, ds, sel_w, sel_h


def _rel_bwd_dq_kernel(q_ref, k_ref, v_ref, rw_ref, rh_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, drw_ref, drh_ref, dq_acc, drw_acc,
                       drh_acc, *, scale: float, q_len: int, kv_len: int,
                       block_q: int, block_kv: int, num_kv_blocks: int,
                       width: int):
    """dq + per-axis relative-logit gradients, kv-innermost grid.

    dS w.r.t. the bias factors through the selection matmuls:
    ``d_rw = dS @ S_wᵀ`` — the row-sum of dS over key columns sharing a
    width coordinate (and S_h for height). Accumulated per q block, so the
    dense ``[B,H,L,L]`` bias gradient never exists in HBM."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        drw_acc[...] = jnp.zeros_like(drw_acc)
        drh_acc[...] = jnp.zeros_like(drh_acc)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    _, ds, sel_w, sel_h = _rel_recompute_ds(
        q, k, v, do, rw_ref[0], rh_ref[0], lse_ref[0], delta_ref[0],
        ki, pl.program_id(1), scale=scale, q_len=q_len, kv_len=kv_len,
        block_q=block_q, block_kv=block_kv, width=width,
    )
    dq_acc[0] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    drw_acc[0] += jax.lax.dot_general(
        ds, sel_w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    drh_acc[0] += jax.lax.dot_general(
        ds, sel_h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == num_kv_blocks - 1)
    def _write():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        drw_ref[...] = drw_acc[...]
        drh_ref[...] = drh_acc[...]


def _rel_bwd_dkv_kernel(q_ref, k_ref, v_ref, rw_ref, rh_ref, do_ref, lse_ref,
                        delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                        scale: float, q_len: int, kv_len: int, block_q: int,
                        block_kv: int, num_q_blocks: int, width: int):
    """dk/dv, q-innermost grid; kv block index is grid axis 1."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    p, ds, _, _ = _rel_recompute_ds(
        q, k, v, do, rw_ref[0], rh_ref[0], lse_ref[0], delta_ref[0],
        ki, qi, scale=scale, q_len=q_len, kv_len=kv_len,
        block_q=block_q, block_kv=block_kv, width=width,
    )
    dv_acc[0] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_acc[0] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(qi == num_q_blocks - 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _rel_backward_pallas(q, k, v, rw_abs, rh_abs, out, lse, g, height, width,
                         scale, block_q, block_kv, interpret):
    """Blocked backward for the fused rel-pos kernel. Mirrors
    ``_flash_backward_pallas`` with the bias rebuilt in-kernel and its
    gradient reduced to the compact per-axis ``[B, H, L, W]/[B, H, L, H]``
    tables — ``[B,H,L,L]`` never materializes in either direction."""
    if interpret is None:
        interpret = _backend.default_interpret()

    geom = _bwd_prep(q, k, v, out, g, block_q, block_kv)
    qf, kf, vf, dof, delta = geom.qf, geom.kf, geom.vf, geom.dof, geom.delta
    q_len, kv_len = geom.q_len, geom.kv_len
    dim_p, block_q, block_kv = geom.dim_p, geom.block_q, geom.block_kv
    q_len_p, kv_len_p = geom.q_len_p, geom.kv_len_p
    batch, heads = geom.batch, geom.heads

    def prep_compact(c):
        bb, hh, ll, rr = c.shape
        cf = c.reshape(bb * hh, ll, rr).astype(jnp.float32)
        return jnp.pad(
            cf, ((0, 0), (0, q_len_p - ll), (0, _round_up(rr, 128) - rr))
        )

    rwf, rhf = prep_compact(rw_abs), prep_compact(rh_abs)
    wp, hp = rwf.shape[-1], rhf.shape[-1]

    num_q_blocks = q_len_p // block_q
    num_kv_blocks = kv_len_p // block_kv
    bh = batch * heads

    qspec = pl.BlockSpec((1, block_q, dim_p), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_kv, dim_p), lambda b, i, j: (b, j, 0))
    rwspec = pl.BlockSpec((1, block_q, wp), lambda b, i, j: (b, i, 0))
    rhspec = pl.BlockSpec((1, block_q, hp), lambda b, i, j: (b, i, 0))
    rowq = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))

    dq, drw, drh = pl.pallas_call(
        functools.partial(
            _rel_bwd_dq_kernel,
            scale=scale,
            q_len=q_len,
            kv_len=kv_len,
            block_q=block_q,
            block_kv=block_kv,
            num_kv_blocks=num_kv_blocks,
            width=width,
        ),
        grid=(bh, num_q_blocks, num_kv_blocks),
        in_specs=[qspec, kspec, kspec, rwspec, rhspec, qspec, rowq, rowq],
        out_specs=[qspec, rwspec, rhspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len_p, dim_p), q.dtype),
            jax.ShapeDtypeStruct((bh, q_len_p, wp), jnp.float32),
            jax.ShapeDtypeStruct((bh, q_len_p, hp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q, dim_p), jnp.float32),
            pltpu.VMEM((1, block_q, wp), jnp.float32),
            pltpu.VMEM((1, block_q, hp), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, rwf, rhf, dof, lse, delta)

    qspec2 = pl.BlockSpec((1, block_q, dim_p), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, block_kv, dim_p), lambda b, j, i: (b, j, 0))
    rwspec2 = pl.BlockSpec((1, block_q, wp), lambda b, j, i: (b, i, 0))
    rhspec2 = pl.BlockSpec((1, block_q, hp), lambda b, j, i: (b, i, 0))
    rowq2 = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _rel_bwd_dkv_kernel,
            scale=scale,
            q_len=q_len,
            kv_len=kv_len,
            block_q=block_q,
            block_kv=block_kv,
            num_q_blocks=num_q_blocks,
            width=width,
        ),
        grid=(bh, num_kv_blocks, num_q_blocks),
        in_specs=[qspec2, kspec2, kspec2, rwspec2, rhspec2, qspec2, rowq2,
                  rowq2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_len_p, dim_p), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_len_p, dim_p), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_kv, dim_p), jnp.float32),
            pltpu.VMEM((1, block_kv, dim_p), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, rwf, rhf, dof, lse, delta)

    def from_compact(x, rr, ref):
        return x[:, :q_len, :rr].reshape(batch, heads, q_len, rr).astype(
            ref.dtype
        )

    return (
        geom.unprep(dq, q_len),
        geom.unprep(dk, kv_len),
        geom.unprep(dv, kv_len),
        from_compact(drw, rw_abs.shape[-1], rw_abs),
        from_compact(drh, rh_abs.shape[-1], rh_abs),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_rel(q, k, v, rw_abs, rh_abs, height, width, scale, block_q,
               block_kv, interpret):
    return _rel_forward(
        q, k, v, rw_abs, rh_abs, height, width, scale, block_q, block_kv,
        interpret,
    )


def _flash_rel_fwd(q, k, v, rw_abs, rh_abs, height, width, scale, block_q,
                   block_kv, interpret):
    out, lse = _rel_forward(
        q, k, v, rw_abs, rh_abs, height, width, scale, block_q, block_kv,
        interpret, with_lse=True,
    )
    return out, (q, k, v, rw_abs, rh_abs, out, lse)


def _flash_rel_bwd(height, width, scale, block_q, block_kv, interpret,
                   residuals, g):
    q, k, v, rw_abs, rh_abs, out, lse = residuals
    return _rel_backward_pallas(
        q, k, v, rw_abs, rh_abs, out, lse, g, height, width, scale, block_q,
        block_kv, interpret,
    )


_flash_rel.defvjp(_flash_rel_fwd, _flash_rel_bwd)


def flash_botnet_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    rel_k_h: jax.Array,
    rel_k_w: jax.Array,
    height: int,
    width: int,
    *,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused BoTNet attention: 2-D relative logits inside the flash kernel.

    Args:
      query/key/value: ``[B, L, heads, D]`` with ``L == height * width``.
      rel_k_h: learned ``[2·height−1, D]`` height-relative table.
      rel_k_w: learned ``[2·width−1, D]`` width-relative table.
      scale: content-logit scale, default ``D ** -0.5``; the relative logits
        use the same scaled query (botnet.py:187-192 semantics).

    Returns:
      ``[B, L, heads, D]`` in the query dtype. Differentiable w.r.t. all
      five tensor inputs; the backward is fully blocked Pallas (dq + compact
      per-axis bias gradients in one kernel, dk/dv in another) — the dense
      ``[B,H,L,L]`` bias/probability tensors exist in neither direction.
    """
    b, l, heads, d = query.shape
    if l != height * width:
        raise ValueError(f"L={l} != height*width={height * width}")
    if scale is None:
        scale = d ** -0.5
    qs = (query * jnp.asarray(scale, query.dtype)).astype(jnp.float32)
    cw = jnp.einsum("blhd,rd->bhlr", qs, rel_k_w.astype(jnp.float32))
    ch = jnp.einsum("blhd,rd->bhlr", qs, rel_k_h.astype(jnp.float32))
    rw_abs, rh_abs = compact_to_absolute(cw, ch, height, width)
    return _flash_rel(
        query, key, value, rw_abs, rh_abs, height, width, float(scale),
        block_q, block_kv, interpret,
    )
