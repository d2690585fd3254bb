"""The causal depthwise convolution over ``[B, S, C]`` as Pallas (Mosaic)
kernels, one a direction, under the two fused forms the models read
(``models/layers/causal_conv.py``)::

    silu:   y = silu(conv(x))                     the delta-rule block's q, k, v
    gated:  y = c * conv(b * x)                   the short-convolution block's core

    conv(u)_t = sum_i kernel[i] u_{t - (W - 1) + i},   zeros before the sequence

A grid step holds a ``[block_s, block_c]`` block of rows of one sequence in
VMEM (channels on the lanes, rows on the sublanes) and walks it in pieces of
``_ROWS`` rows by ``_LANES`` lanes that stay in registers. A tap is a sublane
roll of the float32 piece with the eight rows before it (forward, and the sums
the backward forms again) or after it (the input gradient) put in: no padded
copy exists in HBM and every operand is read once. The S axis of the grid is
sequential and carries those eight rows in VMEM scratch from one step to the
next: forward the last rows of ``x`` (of ``b * x``), backward, where the rows
run from the sequence's end to its start, the first rows of ``dy``. The
backward reads what lies before a block through a 16-row block of the same
array (zeros at the sequence's start).

The backward is one call: it forms the sums again (SiLU's derivative, ``dc``),
writes the input gradients once in the operands' dtype and sums the kernel's
gradient ``[W, C]`` in a float32 VMEM block over the rows a (batch, channel
block) sees, eight partial rows a tap; the last small sum is XLA's. The gated
form reads ``b``, ``c`` and ``x`` where they lie, as thirds of the input
projection's ``[B, S, 3 C]`` result, and writes ``db``, ``dc`` and ``dx`` as
thirds of one ``[B, S, 3 C]`` array, which its backward call keeps in HBM and
fills by copies of its own from two staging slots in VMEM, so that XLA neither
splits the operand nor joins the gradient. The silu form likewise reads the
delta-rule block's projection ``[B, S, H_k (q | k | v | z)]`` a key head's lanes
a step (:func:`key_head_conv_silu_forward`): q, k and v leave as an array each
and the backward writes the projection's gradient whole.

Precision: operands and results in the operands' dtype, every sum and the
gates' arithmetic in float32, ``b * x`` rounded to the operands' dtype before
the taps, as the XLA forms in ``models/layers/causal_conv.py`` compute (they
stay the CPU path and what the tests hold these kernels to).
:func:`conv_form` says which of the two runs, from the backend and the shapes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sav_tpu.ops import attention as _attention

_EDGE = 8  # float32 rows carried from a piece to the next: W - 1 may not pass it
_HALO = 16  # rows of the block before a block: one bfloat16 tile
_ROWS = 64  # rows a trip of a grid step's loop
_LANES = 128  # lanes a piece
BLOCK_S, BLOCK_C = 1024, 512  # the largest block of rows and of channels
_VMEM_LIMIT = 64 << 20  # the gated backward holds four blocks twice and stages three twice: 14 MiB of bfloat16
_F32 = jnp.float32


def conv_form(seq: int, channels: int, width: int, dtype, *, key_head: Optional[tuple] = None,
              on_tpu: Optional[bool] = None) -> dict:
    """Which program computes the convolution, from what the code can observe:
    ``{"conv": "kernel", "block_s": rows, "block_c": channels}`` on a TPU where
    Mosaic takes the shapes, else ``{"conv": "xla", "refused": why}``. The
    kernels want bfloat16 or float32 operands, channels of whole lane tiles,
    rows of whole 16-row tiles and ``W - 1`` rows inside the eight a piece
    carries. The blocks are the largest powers of two up to :data:`BLOCK_S`
    rows and :data:`BLOCK_C` channels that divide the shape.

    ``key_head = (d_k, r d_v)`` says that the channels are the q, k and v of a
    projection laid out by key head (``[q | k | v | z]`` each): the kernel
    form then ``reads`` them ``in_place``, a key head's lanes a step
    (``block_c`` its q, k and v), where each part is whole lane tiles, else
    ``joined`` by XLA."""
    if on_tpu is None:
        on_tpu = _attention._on_tpu()
    if not on_tpu:
        refused = "non-TPU backend"
    elif jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        refused = f"operands of {jnp.dtype(dtype).name}"
    elif channels % 128:
        refused = f"{channels} channels are not whole lane tiles"
    elif seq % _HALO:
        refused = f"{seq} rows are not whole 16-row tiles"
    elif not 1 <= width - 1 <= _EDGE:
        refused = f"width {width} reaches past the {_EDGE} rows a piece carries"
    else:
        form = {"conv": "kernel", "block_s": _largest(seq, BLOCK_S), "block_c": _largest(channels, BLOCK_C)}
        if key_head is None:
            return form
        if key_head[0] % 128 or key_head[1] % 128:
            return {**form, "reads": "joined"}
        most = BLOCK_S  # a step holds the key head's z too: no more than the plain form's two blocks
        while most > _HALO and most * 2 * sum(key_head) > 2 * BLOCK_S * BLOCK_C:
            most //= 2
        return {"conv": "kernel", "block_s": _largest(seq, most), "block_c": 2 * key_head[0] + key_head[1],
                "reads": "in_place"}
    return {"conv": "xla", "refused": refused}


def _largest(size: int, most: int) -> int:
    """The largest power of two up to ``most`` that divides ``size``."""
    block = most
    while size % block:
        block //= 2
    return block


# ------------------------------------------------------------------ the pieces


def _behind(before, piece, width: int) -> list:
    """``out[d][t] = piece[t - d]`` for ``d < width``, the rows before the
    piece read from ``before [8, L]``: one sublane roll a tap."""
    window = jnp.concatenate([before, piece], axis=0)
    return [piece] + [pltpu.roll(window, d, 0)[_EDGE:] for d in range(1, width)]


def _ahead(piece, after, width: int) -> list:
    """``out[d][t] = piece[t + d]`` for ``d < width``, the rows after the
    piece read from ``after [8, L]``."""
    rows = piece.shape[0]
    window = jnp.concatenate([piece, after], axis=0)
    return [piece] + [pltpu.roll(window, rows + _EDGE - d, 0)[:rows] for d in range(1, width)]


def _taps_sum(shifted: list, kernel_ref, at: "_Piece") -> jax.Array:
    """``sum_d kernel[W - 1 - d] shifted[d]``: the convolution where
    ``shifted`` looks behind, its transpose where it looks ahead."""
    terms = [rows * kernel_ref[i:i + 1, at.held].astype(_F32) for i, rows in enumerate(reversed(shifted))]
    return functools.reduce(jnp.add, terms)  # from kernel[0] up, the order the XLA forms sum in


def _eight_rows(x) -> jax.Array:
    """``[R, L] -> [8, L]``: the rows summed tile on tile, on the VPU."""
    return functools.reduce(jnp.add, [x[i:i + _EDGE] for i in range(0, x.shape[0], _EDGE)])


def _add_kernel_gradient(dkernel_ref, dy, behind: list, at: "_Piece") -> None:
    """``dkernel[i] += sum_t dy_t u_{t - (W - 1) + i}`` as eight partial rows."""
    width = len(behind)
    for d, rows in enumerate(behind):
        dkernel_ref[width - 1 - d, :, at.held] += _eight_rows(dy * rows)


class _Piece(NamedTuple):
    """The lanes a trip of a step's loop holds in registers: ``held`` of the
    kernel, of the carried rows and of whatever block spans all the step's
    channels, ``lanes`` of block ``part`` where the channels come as blocks of
    several arrays side by side (one ``part`` and the same lanes otherwise)."""

    part: int
    lanes: slice
    held: slice


def _pieces(refs) -> tuple:
    rows, pieces, start = min(_ROWS, refs[0].shape[0]), [], 0
    for part, ref in enumerate(refs):
        lanes = min(_LANES, ref.shape[1])
        pieces += [_Piece(part, slice(i, i + lanes), slice(start + i, start + i + lanes))
                   for i in range(0, ref.shape[1], lanes)]
        start += ref.shape[1]
    return rows, refs[0].shape[0] // rows, pieces


def _forward_walk(carry_ref, read, write, rows: int, trips: int, lanes: list, width: int, kernel_ref):
    """The block from its first rows to its last, a :class:`_Piece` of
    ``lanes`` at a time: ``read(r0, at)`` gives a float32 piece of what the
    taps read, ``write(r0, at, conv)`` takes the piece's sums; the last eight rows go to the next piece and, in
    ``carry_ref``, to the next grid step."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def trip(i, carried):
        r0 = pl.multiple_of(i * rows, rows)
        out = []
        for before, at in zip(carried, lanes):
            piece = read(r0, at)
            write(r0, at, _taps_sum(_behind(before, piece, width), kernel_ref, at))
            out.append(piece[rows - _EDGE:])
        return tuple(out)

    carried = jax.lax.fori_loop(0, trips, trip, tuple(carry_ref[:, at.held] for at in lanes))
    for rows_after, at in zip(carried, lanes):
        carry_ref[:, at.held] = rows_after


def _backward_walk(start, carry_ref, dkernel_ref, read, read_before, write, rows: int, trips: int, lanes: list,
                   width: int, kernel_ref):
    """The block from its last rows to its first. ``read(r0, at)`` gives
    the float32 piece the taps read, ``read_before(r0, at)`` the eight rows
    before it, ``write(r0, at, conv, ahead_sum)`` takes the piece's sums
    and a function from ``dy`` (the sums' cotangent) to the taps' transposed
    sum, and returns ``dy``; its first eight rows go to the piece before and,
    in ``carry_ref``, to the next grid step, which holds the block before;
    ``start`` says that this is a (batch, channel block)'s first step."""
    @pl.when(start)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        dkernel_ref[...] = jnp.zeros_like(dkernel_ref)

    def trip(i, carried):
        r0 = pl.multiple_of((trips - 1 - i) * rows, rows)
        out = []
        for after, at in zip(carried, lanes):
            behind = _behind(read_before(r0, at), read(r0, at), width)
            dy = write(r0, at, _taps_sum(behind, kernel_ref, at),
                       lambda dy: _taps_sum(_ahead(dy, after, width), kernel_ref, at))
            _add_kernel_gradient(dkernel_ref, dy, behind, at)
            out.append(dy[:_EDGE])
        return tuple(out)

    carried = jax.lax.fori_loop(0, trips, trip, tuple(carry_ref[:, at.held] for at in lanes))
    for rows_before, at in zip(carried, lanes):
        carry_ref[:, at.held] = rows_before


def _rows_before(ref, halo_ref, r0, lanes, first_block):
    """The eight rows before row ``r0`` of a block, float32: the block's own,
    or the last of the block before it (``halo_ref``; zeros where the
    sequence starts)."""
    edge = slice(_HALO - _EDGE, _HALO)
    inside = ref[pl.ds(pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO), _HALO), lanes].astype(_F32)[edge]
    halo = jnp.where(first_block, 0.0, halo_ref[:, lanes].astype(_F32)[edge])
    return jnp.where(r0 == 0, halo, inside)


def _product(b, x, dtype) -> jax.Array:
    """``b * x`` rounded to the operands' dtype, as float32 for the taps."""
    return (b.astype(_F32) * x.astype(_F32)).astype(dtype).astype(_F32)


# ------------------------------------------------------------- the silu form


def _silu_fwd_kernel(x_ref, kernel_ref, *refs, width: int):
    """``refs``: the result's blocks, one an array the step's channels leave
    in (side by side on ``x_ref``'s lanes), then the carried rows."""
    *out_refs, carry_ref = refs
    rows, trips, lanes = _pieces(out_refs)

    def write(r0, at, conv):
        out_refs[at.part][pl.ds(r0, rows), at.lanes] = (conv * jax.nn.sigmoid(conv)).astype(out_refs[at.part].dtype)

    _forward_walk(carry_ref, lambda r0, at: x_ref[pl.ds(r0, rows), at.held].astype(_F32), write,
                  rows, trips, lanes, width, kernel_ref)


def _silu_bwd_kernel(x_ref, before_ref, kernel_ref, *refs, width: int, passed: int):
    """``refs``: the cotangent's blocks, an array each as the forward's result
    left; where ``passed`` lanes of ``x_ref`` beside the step's channels went
    around the forward, their cotangent's block; then ``dx_ref`` over all of
    ``x_ref``'s lanes, the kernel's gradient and the carried rows."""
    dx_ref, dkernel_ref, carry_ref = refs[-3:]
    g_refs = refs[:-3 - bool(passed)]
    rows, trips, lanes = _pieces(g_refs)
    first_block = pl.program_id(2) == pl.num_programs(2) - 1  # the rows run backwards

    def write(r0, at, conv, ahead_sum):
        gate = jax.nn.sigmoid(conv)
        g = g_refs[at.part][pl.ds(r0, rows), at.lanes].astype(_F32)
        dy = g * gate * (1.0 + conv * (1.0 - gate))  # d silu(y) / dy
        dx_ref[pl.ds(r0, rows), at.held] = ahead_sum(dy).astype(dx_ref.dtype)
        return dy

    _backward_walk(
        pl.program_id(2) == 0, carry_ref, dkernel_ref, lambda r0, at: x_ref[pl.ds(r0, rows), at.held].astype(_F32),
        lambda r0, at: _rows_before(x_ref, before_ref, r0, at.held, first_block), write,
        rows, trips, lanes, width, kernel_ref,
    )
    if passed:
        dx_ref[:, dx_ref.shape[1] - passed:] = refs[-4][...]


def _grid(x_shape, block_s: int, block_c: int):
    batch, seq, channels = x_shape
    return (batch, channels // block_c, seq // block_s)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _kernel_gradient_spec(width: int, block_c: int):
    return pl.BlockSpec((None, width, _EDGE, block_c), lambda b, j, *_: (b, 0, 0, j))


@functools.partial(jax.jit, static_argnames=("block_s", "block_c", "interpret"))
def conv_silu_forward(x, kernel, block_s: int, block_c: int, interpret: bool):
    """``silu(conv(x))`` in ``x``'s dtype. Jitted, as every call here is: a
    model's layers of one shape share one trace and one lowering."""
    width = kernel.shape[0]
    block = pl.BlockSpec((None, block_s, block_c), lambda b, j, s: (b, s, j))
    return pl.pallas_call(
        functools.partial(_silu_fwd_kernel, width=width),
        grid=_grid(x.shape, block_s, block_c),
        in_specs=[block, pl.BlockSpec((width, block_c), lambda b, j, s: (0, j))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_EDGE, block_c), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, kernel)


@functools.partial(jax.jit, static_argnames=("block_s", "block_c", "interpret"))
def conv_silu_backward(x, kernel, g, block_s: int, block_c: int, interpret: bool):
    """``(dx, dkernel)`` of :func:`conv_silu_forward` for the cotangent ``g``."""
    width = kernel.shape[0]
    batch, seq, channels = x.shape
    last, tiles = seq // block_s - 1, block_s // _HALO
    block = pl.BlockSpec((None, block_s, block_c), lambda b, j, s: (b, last - s, j))
    before = pl.BlockSpec((None, _HALO, block_c), lambda b, j, s: (b, jnp.maximum((last - s) * tiles - 1, 0), j))
    dx, dkernel = pl.pallas_call(
        functools.partial(_silu_bwd_kernel, width=width, passed=0),
        grid=_grid(x.shape, block_s, block_c),
        in_specs=[block, before, pl.BlockSpec((width, block_c), lambda b, j, s: (0, j)), block],
        out_specs=[block, _kernel_gradient_spec(width, block_c)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, width, _EDGE, channels), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((_EDGE, block_c), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, x, kernel, g)
    return dx, jnp.sum(dkernel, axis=(0, 2)).astype(kernel.dtype)


# ------------------------------------- the silu form on a projection by key head
#
# The delta-rule block's input projection leaves ``[B, S, H_k (2 d_k + 2 r d_v)]``,
# for each key head ``[q d_k | k d_k | v r d_v | z r d_v]``; the taps run over
# q, k and v, whose kernel ``[W, 2 H_k d_k + H_k r d_v]`` lists all the q, then
# all the k, then all the v. A grid step holds one key head's lanes of the
# projection as they lie (z among them), so XLA neither joins q, k and v before
# the call nor splits them after it: the forward leaves q, k and v as an array
# each, the backward takes their cotangents so and writes the projection's
# gradient whole, z's lanes copied in from z's cotangent.


def _by_key_head(kernel, heads: int, key_ch: int, value_ch: int):
    """``[W, (all q | all k | all v)] -> [W, H_k (q | k | v)]``."""
    q, k, v = jnp.split(kernel, [heads * key_ch, 2 * heads * key_ch], axis=1)
    width = kernel.shape[0]
    return jnp.concatenate(
        [q.reshape(width, heads, key_ch), k.reshape(width, heads, key_ch), v.reshape(width, heads, value_ch)], axis=2
    ).reshape(width, -1)


def _by_part(kernel, heads: int, key_ch: int, value_ch: int):
    """:func:`_by_key_head`'s inverse."""
    q, k, v = jnp.split(kernel.reshape(kernel.shape[0], heads, -1), [key_ch, 2 * key_ch], axis=2)
    return jnp.concatenate([t.reshape(kernel.shape[0], -1) for t in (q, k, v)], axis=1)


def _key_head_specs(block_s: int, key_ch: int, value_ch: int, rows):
    """A key head's lanes of the projection, and its q, k and v (or z) as
    blocks of arrays of their own; ``rows(s)`` is the step's block of rows."""
    head = pl.BlockSpec((None, block_s, 2 * key_ch + 2 * value_ch), lambda b, h, s: (b, rows(s), h))
    part = lambda channels: pl.BlockSpec((None, block_s, channels), lambda b, h, s: (b, rows(s), h))
    return head, [part(key_ch), part(key_ch), part(value_ch)]


@functools.partial(jax.jit, static_argnames=("heads", "key_ch", "value_ch", "block_s", "interpret"))
def key_head_conv_silu_forward(qkvz, kernel, heads: int, key_ch: int, value_ch: int, block_s: int, interpret: bool):
    """``silu(conv(.))`` of the q, k and v of ``qkvz [B, S, H_k (2 d_k + 2 r
    d_v)]`` (``value_ch = r d_v``) -> ``q, k [B, S, H_k d_k]``, ``v [B, S, H_k r
    d_v]``."""
    width = kernel.shape[0]
    batch, seq, _ = qkvz.shape
    span = 2 * key_ch + value_ch
    head, parts = _key_head_specs(block_s, key_ch, value_ch, lambda s: s)
    return pl.pallas_call(
        functools.partial(_silu_fwd_kernel, width=width),
        grid=(batch, heads, seq // block_s),
        in_specs=[head, pl.BlockSpec((width, span), lambda b, h, s: (0, h))],
        out_specs=parts,
        out_shape=[jax.ShapeDtypeStruct((batch, seq, heads * ch), qkvz.dtype) for ch in (key_ch, key_ch, value_ch)],
        scratch_shapes=[pltpu.VMEM((_EDGE, span), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(qkvz, _by_key_head(kernel, heads, key_ch, value_ch))


@functools.partial(jax.jit, static_argnames=("heads", "key_ch", "value_ch", "block_s", "interpret"))
def key_head_conv_silu_backward(qkvz, kernel, dq, dk, dv, dz, heads: int, key_ch: int, value_ch: int, block_s: int,
                                interpret: bool):
    """``(dqkvz, dkernel)`` of :func:`key_head_conv_silu_forward` for the
    cotangents of its q, k and v, with ``dz [B, S, H_k r d_v]`` put where z
    lies."""
    width = kernel.shape[0]
    batch, seq, _ = qkvz.shape
    span, stride = 2 * key_ch + value_ch, 2 * key_ch + 2 * value_ch
    last, tiles = seq // block_s - 1, block_s // _HALO
    head, parts = _key_head_specs(block_s, key_ch, value_ch, lambda s: last - s)
    before = pl.BlockSpec((None, _HALO, stride), lambda b, h, s: (b, jnp.maximum((last - s) * tiles - 1, 0), h))
    dqkvz, dkernel = pl.pallas_call(
        functools.partial(_silu_bwd_kernel, width=width, passed=value_ch),
        grid=(batch, heads, seq // block_s),
        in_specs=[head, before, pl.BlockSpec((width, span), lambda b, h, s: (0, h)), *parts, parts[2]],
        out_specs=[head, _kernel_gradient_spec(width, span)],
        out_shape=[
            jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
            jax.ShapeDtypeStruct((batch, width, _EDGE, heads * span), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((_EDGE, span), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(qkvz, qkvz, _by_key_head(kernel, heads, key_ch, value_ch), dq, dk, dv, dz)
    return dqkvz, _by_part(jnp.sum(dkernel, axis=(0, 2)), heads, key_ch, value_ch).astype(kernel.dtype)


# ------------------------------------------------------------ the gated form


def _gated_fwd_kernel(b_ref, c_ref, x_ref, kernel_ref, out_ref, carry_ref, *, width: int):
    rows, trips, lanes = _pieces([x_ref])

    def read(r0, at):
        return _product(b_ref[pl.ds(r0, rows), at.lanes], x_ref[pl.ds(r0, rows), at.lanes], x_ref.dtype)

    def write(r0, at, conv):
        out_ref[pl.ds(r0, rows), at.lanes] = (c_ref[pl.ds(r0, rows), at.lanes].astype(_F32) * conv).astype(out_ref.dtype)

    _forward_walk(carry_ref, read, write, rows, trips, lanes, width, kernel_ref)


def _gated_bwd_kernel(b_ref, c_ref, x_ref, b_before_ref, x_before_ref, g_ref, kernel_ref,
                      dgates_ref, dkernel_ref, carry_ref, staged_ref, sent, *, width: int, thirds: int):
    """``dgates_ref`` is the whole ``[B, S, 3 C]`` array in HBM: a step stages
    its blocks of ``db``, ``dc`` and ``dx`` in one of two slots of
    ``staged_ref`` and sends each to its third itself, so that the copies run
    beside the next step's arithmetic; it waits for a slot's copies before it
    fills the slot again, and for all of them at a (batch, channel block)'s
    last step."""
    rows, trips, lanes = _pieces([x_ref])
    block_s, block_c = x_ref.shape
    b_i, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    steps = pl.num_programs(2)
    first_block = s == steps - 1  # the rows run backwards
    slot = s % 2

    def copies(slot, step):
        row0 = pl.multiple_of((steps - 1 - step) * block_s, block_s)
        return [pltpu.make_async_copy(
            staged_ref.at[slot, n],
            dgates_ref.at[b_i, pl.ds(row0, block_s), pl.ds(pl.multiple_of((n * thirds + j) * block_c, block_c), block_c)],
            sent.at[slot, n],
        ) for n in range(3)]

    @pl.when(s >= 2)
    def _():
        for copy in copies(slot, s - 2):
            copy.wait()

    def read_before(r0, at):
        return _product(
            _rows_before(b_ref, b_before_ref, r0, at.lanes, first_block),
            _rows_before(x_ref, x_before_ref, r0, at.lanes, first_block), x_ref.dtype,
        )

    def write(r0, at, conv, ahead_sum):
        here = pl.ds(r0, rows)
        g = g_ref[here, at.lanes].astype(_F32)
        dconv = g * c_ref[here, at.lanes].astype(_F32)
        du = ahead_sum(dconv)
        for n, third in enumerate((du * x_ref[here, at.lanes].astype(_F32), g * conv, du * b_ref[here, at.lanes].astype(_F32))):
            staged_ref[slot, n, here, at.lanes] = third.astype(staged_ref.dtype)
        return dconv

    _backward_walk(
        s == 0, carry_ref, dkernel_ref,
        lambda r0, at: _product(b_ref[pl.ds(r0, rows), at.lanes], x_ref[pl.ds(r0, rows), at.lanes], x_ref.dtype),
        read_before, write, rows, trips, lanes, width, kernel_ref,
    )
    for copy in copies(slot, s):
        copy.start()

    @pl.when(s == steps - 1)
    def _():
        for copy in copies(slot, s):
            copy.wait()

    @pl.when((s == steps - 1) & (s >= 1))
    def _():
        for copy in copies(1 - slot, s - 1):
            copy.wait()


def _thirds(gates, kernel, block_c: int) -> int:
    channels = kernel.shape[1]
    if gates.shape[2] != 3 * channels:
        raise ValueError(f"gated convolution: gates {gates.shape} beside a kernel {kernel.shape}")
    return channels // block_c


@functools.partial(jax.jit, static_argnames=("block_s", "block_c", "interpret"))
def gated_conv_forward(gates, kernel, block_s: int, block_c: int, interpret: bool):
    """``c * conv(b * x)`` on ``gates = [b | c | x]``, ``[B, S, 3 C]``, read
    where it lies; the result ``[B, S, C]`` in its dtype."""
    width, channels = kernel.shape
    third = _thirds(gates, kernel, block_c)
    batch, seq, _ = gates.shape
    part = lambda n: pl.BlockSpec((None, block_s, block_c), lambda b, j, s: (b, s, n * third + j))
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, width=width),
        grid=_grid((batch, seq, channels), block_s, block_c),
        in_specs=[part(0), part(1), part(2), pl.BlockSpec((width, block_c), lambda b, j, s: (0, j))],
        out_specs=part(0),
        out_shape=jax.ShapeDtypeStruct((batch, seq, channels), gates.dtype),
        scratch_shapes=[pltpu.VMEM((_EDGE, block_c), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(gates, gates, gates, kernel)


@functools.partial(jax.jit, static_argnames=("block_s", "block_c", "interpret"))
def gated_conv_backward(gates, kernel, g, block_s: int, block_c: int, interpret: bool):
    """``(dgates [B, S, 3 C], dkernel)`` of :func:`gated_conv_forward` for the
    cotangent ``g``; ``db``, ``dc`` and ``dx`` are written into their thirds of
    ``dgates`` by the kernel's own copies."""
    width, channels = kernel.shape
    third = _thirds(gates, kernel, block_c)
    batch, seq, _ = gates.shape
    last, tiles = seq // block_s - 1, block_s // _HALO
    part = lambda n: pl.BlockSpec((None, block_s, block_c), lambda b, j, s: (b, last - s, n * third + j))
    before = lambda n: pl.BlockSpec(
        (None, _HALO, block_c), lambda b, j, s: (b, jnp.maximum((last - s) * tiles - 1, 0), n * third + j)
    )
    dgates, dkernel = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, width=width, thirds=third),
        grid=_grid((batch, seq, channels), block_s, block_c),
        in_specs=[
            part(0), part(1), part(2), before(0), before(2),
            pl.BlockSpec((None, block_s, block_c), lambda b, j, s: (b, last - s, j)),
            pl.BlockSpec((width, block_c), lambda b, j, s: (0, j)),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), _kernel_gradient_spec(width, block_c)],
        out_shape=[
            jax.ShapeDtypeStruct(gates.shape, gates.dtype),
            jax.ShapeDtypeStruct((batch, width, _EDGE, channels), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_EDGE, block_c), _F32), pltpu.VMEM((2, 3, block_s, block_c), gates.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(gates, gates, gates, gates, gates, g, kernel)
    return dgates, jnp.sum(dkernel, axis=(0, 2)).astype(kernel.dtype)
