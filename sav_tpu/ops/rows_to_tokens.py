"""The expert layer's sum of rows by token as one Pallas (Mosaic) call::

    out[t] = sum over the live rows r with token[r] == t of float32(rows[r]) * weight[r]

``rows [C, D]`` is a routed buffer of ``models/layers/moe.py``: the sorted
routings' rows, in groups of consecutive rows (one a held expert), the live
rows first. The one stable sort that made them is by (expert, sequence) over
token-major routings, so inside a group the tokens strictly ascend: the rows
of a tile of consecutive tokens are at most one contiguous range a group, and
where each range starts is a count of the rows before it
(:func:`tile_bounds`, a few thousand integers), not a second sort.

A grid step owns a tile of ``tile`` tokens: ``[tile, D]`` float32 in VMEM,
zeroed, summed into and written once. Group by group it copies the tile's
range from the buffer, which stays in HBM, in pieces of ``unit`` rows from the
``unit``-aligned row at or before the range (a copy's size is static and a
bfloat16 tile is 16 rows; what a piece holds outside the range is not read),
into one of two staging slots, the next range's copies in flight while this
one is summed; then row by row ``acc[token[r] - tile start] += float32(row) *
weight[r]``, the row's token and weight scalars in SMEM. Rows past the last
group, which nobody wrote, lie in no range. Every live row is read once in the
dtype it has; a token with no live row is written as zeros.

Precision: the products and the sums in float32, the same terms as
``jax.ops.segment_sum`` of the float32 rows adds (at most ``top_k`` a token,
in group order here; XLA's scatter fixes no order), the result cast once to
the dtype asked for. That form stays the CPU path, what a shape Mosaic would
not take falls back to, and what the tests hold this kernel to;
:func:`sum_form` says which of the two runs, from the backend and the shapes.
"""

from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sav_tpu.ops import attention as _attention

# The most tokens a grid step owns, and the most elements of its sum: a (group,
# tile) step costs about 0.3 us beside its rows' 12 ns each, so the fewer steps
# the better (a v5e at [32768, 2048] -> 32,768 tokens, half of the buffer live:
# 1.24 / 0.92 / 0.73 ms at 128 / 256 / 512 tokens; PERF.md section 6, PR 41).
TOKEN_TILE = 512
TILE_ELEMENTS = 1 << 21
UNIT = 16  # rows a copy: one bfloat16 tile, two float32 tiles (32 rows read 4-8% slower)
ROWS_IN_SMEM = 1 << 16  # a row's token and weight are scalars in SMEM: 512 KiB at this many rows
_TOGETHER = 2  # rows whose loads all come before their stores (8% faster than 1 on a full buffer, 4 no faster)
_VMEM_LIMIT = 64 << 20  # two staging slots, a float32 copy of one, the sum and the result twice: 37 MiB at 512 x 3,584
_F32 = jnp.float32


def sum_form(rows: int, tokens: int, dim: int, held: int, dtype, *, on_tpu: Optional[bool] = None) -> dict:
    """Which program sums ``[rows, dim]`` of ``dtype`` in ``held`` groups onto
    ``tokens`` tokens, from what the code can observe: ``{"sum": "kernel",
    "tile": tokens a grid step, "unit": rows a copy}`` on a TPU where Mosaic
    takes the shapes, else ``{"sum": "xla", "refused": why}``. The kernel
    wants bfloat16 or float32 rows of whole lane tiles, in whole copies, and
    tokens in whole tiles of at least eight; the tile is the largest power of
    two up to :data:`TOKEN_TILE` tokens and :data:`TILE_ELEMENTS` elements
    that divides them."""
    if on_tpu is None:
        on_tpu = _attention._on_tpu()
    tile = TOKEN_TILE
    while tile >= 8 and (tokens % tile or tile * dim > TILE_ELEMENTS):
        tile //= 2
    if not on_tpu:
        refused = "non-TPU backend"
    elif jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        refused = f"rows of {jnp.dtype(dtype).name}"
    elif dim % 128:
        refused = f"{dim} channels are not whole lane tiles"
    elif tile < 8:
        refused = f"{tokens} tokens are not whole 8-row tiles"
    elif rows % UNIT:
        refused = f"{rows} rows are not whole {UNIT}-row copies"
    elif rows > ROWS_IN_SMEM:
        refused = f"{rows} rows' tokens and weights pass {ROWS_IN_SMEM} scalars in SMEM"
    elif held < 1:
        refused = "no group"
    else:
        return {"sum": "kernel", "tile": tile, "unit": UNIT}
    return {"sum": "xla", "refused": refused}


def sum_xla(rows, weight, token, live, tokens: int, dtype):
    """The sum as XLA's scatter-add: the float32 rows (times their weights),
    the dead ones handed an index past the end, which a scatter drops."""
    terms = rows.astype(_F32)
    if weight is not None:
        terms = terms * weight[:, None]
    return jax.ops.segment_sum(terms, jnp.where(live, token, tokens), num_segments=tokens).astype(dtype)


def tile_bounds(token, live, sizes, tile: int, tokens: int):
    """``[G tokens / tile + 1]`` int32: entry ``g tokens / tile + t`` is the
    first row of group ``g`` whose token lies in tile ``t`` or later, so two
    neighbours bound the rows of (group, tile), and the last entry is the live
    rows' count. ``sizes [G]`` are the groups' rows; inside a group the tokens
    ascend, so (group, tile) ascends over the live rows and a bound is a count
    of smaller keys: a compare and a sum, no sort and no scatter."""
    tiles = tokens // tile
    row = jnp.arange(token.shape[0], dtype=jnp.int32)
    group = jnp.sum(row[:, None] >= jnp.cumsum(sizes)[None, :], axis=1, dtype=jnp.int32)
    key = jnp.where(live, group * tiles + token // tile, sizes.shape[0] * tiles)
    return jnp.sum(key[None, :] < jnp.arange(sizes.shape[0] * tiles + 1, dtype=jnp.int32)[:, None], axis=1, dtype=jnp.int32)


def _sum_kernel(*refs, groups: int, tiles: int, tile: int, unit: int, weighted: bool, staged_f32: bool):
    bounds_ref, token_ref, *refs = refs
    weight_ref = refs.pop(0) if weighted else None
    rows_ref, out_ref, stage_ref, *refs = refs
    f32_ref = refs.pop(0) if staged_f32 else None
    acc_ref, arrived = refs
    t = pl.program_id(0)

    def span(g, t):
        """Range ``[lo, hi)`` of (group, tile), the aligned row its copies
        start from and how many copies cover it."""
        lo, hi = bounds_ref[g * tiles + t], bounds_ref[g * tiles + t + 1]
        first = lo // unit * unit
        return lo, hi, first, jnp.where(hi > lo, (hi - first + unit - 1) // unit, 0)

    def copies(first, units, slot, act):
        """``act`` (start or wait) on each copy of ``units`` pieces from row
        ``first`` into ``slot``."""
        def one(u, _):
            act(pltpu.make_async_copy(
                rows_ref.at[pl.ds(pl.multiple_of(first + u * unit, unit), unit)],
                stage_ref.at[slot, pl.ds(pl.multiple_of(u * unit, unit), unit)],
                arrived.at[slot],
            ))
            return _

        jax.lax.fori_loop(0, units, one, 0)

    start, wait = operator.methodcaller("start"), operator.methodcaller("wait")

    @pl.when(t == 0)
    def _():
        copies(*span(0, 0)[2:], 0, start)

    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def group(g, carry):
        step = t * groups + g
        slot = step % 2
        last = g + 1 == groups

        @pl.when(step + 1 < tiles * groups)
        def _():
            copies(*span(jnp.where(last, 0, g + 1), jnp.where(last, t + 1, t))[2:], 1 - slot, start)

        lo, hi, first, units = span(g, t)
        copies(first, units, slot, wait)
        if staged_f32:
            def widen(u, _):
                here = pl.ds(pl.multiple_of(u * unit, unit), unit)
                f32_ref[here, :] = stage_ref[slot, here, :].astype(_F32)
                return _

            jax.lax.fori_loop(0, units, widen, 0)

        def add(r0, rows):
            """``rows`` rows from ``r0`` on: their terms and their tokens' sums
            are all read before any is written back (inside a group no token
            repeats), so that one row's loads do not wait for the store of the
            row before."""
            sums = []
            for r in range(rows):
                r = r0 + r
                at = pl.ds(r - first, 1)
                term = f32_ref[at, :] if staged_f32 else stage_ref[slot, at, :]
                if weighted:
                    term = term * weight_ref[r]
                to = pl.ds(token_ref[r] - t * tile, 1)
                sums.append((to, acc_ref[to, :] + term))
            for to, value in sums:
                acc_ref[to, :] = value
            return 0

        whole = (hi - lo) // _TOGETHER
        jax.lax.fori_loop(0, whole, lambda i, _: add(lo + i * _TOGETHER, _TOGETHER), 0)
        jax.lax.fori_loop(lo + whole * _TOGETHER, hi, lambda r, _: add(r, 1), 0)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tokens", "dtype", "tile", "unit", "interpret"))
def rows_to_tokens(rows, weight, token, live, sizes, *, tokens: int, dtype, tile: int, unit: int, interpret: bool):
    """``[tokens, D]`` of ``dtype``: each token's sum of its live rows of
    ``rows [C, D]`` (times ``weight [C]`` float32 where one is given), by the
    kernel; ``sizes [G]`` are the groups of consecutive live rows inside which
    ``token`` ascends, and ``live`` marks the first ``sum(sizes)`` rows."""
    count, dim = rows.shape
    groups, tiles = sizes.shape[0], tokens // tile
    weighted, staged_f32 = weight is not None, rows.dtype != _F32
    scalars = [tile_bounds(token, live, sizes, tile, tokens), token.astype(jnp.int32)]
    if weighted:
        scalars.append(weight.astype(_F32))
    scratch = [pltpu.VMEM((2, tile + unit, dim), rows.dtype)]
    if staged_f32:
        scratch.append(pltpu.VMEM((tile + unit, dim), _F32))
    return pl.pallas_call(
        functools.partial(_sum_kernel, groups=groups, tiles=tiles, tile=tile, unit=unit, weighted=weighted,
                          staged_f32=staged_f32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, dim), lambda t, *_: (t, 0)),
            scratch_shapes=[*scratch, pltpu.VMEM((tile, dim), _F32), pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, dim), dtype),
        # Sequential: a step starts the next step's first copies.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, rows)
