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

Everything that does not read ``S`` (``gamma``, ``A``, ``T``, the masked ``Q
K^T``) is computed before the ``lax.scan`` over the chunks that carries ``S``
through the last three lines, by one of two programs a decay (below: a decay a
head, or a decay a key lane) that :func:`rule_form` picks from the backend and
the shapes (the choice is a record of the dispatch log,
``ops/attention.py::snapshot_dispatch_log``):

* ``kernel`` (:func:`_prepare_in_vmem`; a TPU, chunks of 16 rows times a
  power of two, key heads of whole lane tiles, a key head's value heads
  filling whole lane tiles side by side): one Pallas call a direction whose
  grid step holds a tile of chunks of one (batch, key head) in VMEM. The
  forward reads the chunks' rows of q and k and the group's ``gamma`` and
  ``beta``, forms the
  decay, ``k k^T`` and ``q k^T`` once a key head, ``A`` and ``T`` for the
  group's value heads, and writes ``T beta`` and the masked ``Q K^T`` in the
  operands' dtype; nothing ``C x C`` in float32 leaves VMEM. The backward is
  written out: from the same four operands and the two cotangents it builds
  ``T`` and the decay again, applies the inverse's own derivative ``dA = -T^T
  dT T^T``, and writes dq and dk (summed over the group inside the products),
  ``d gamma`` and ``d beta``. ``gamma`` comes summed (:func:`rule_operands`).
* ``xla`` (:func:`_prepare`; everything else, and what the tests hold the
  kernels to): the same for all chunks at once as XLA's program, ``k k^T`` and
  ``q k^T`` repeated a value head through HBM, the inverse by
  :func:`_unit_lower_inverse`, the backward JAX's transpose of it under a
  ``jax.checkpoint``.

Every exponent is a difference ``gamma_i - gamma_j`` with ``i >= j`` (or
``gamma_i`` itself), so nothing overflows however fast the state decays; the
upper triangle is masked before ``exp``.

**Two decays.** ``g [B, L, H]`` is one decay a head and token, the rule above
(arXiv:2412.06464). ``g [B, L, H, d_k]`` is a decay a key lane, Kimi Delta
Attention (arXiv:2510.26692, section 3): ``S' = Diag(exp(g_t)) S_{t-1}``, the
rest as written. The scalar decay is the vector with equal lanes, and every
function here takes either; what differs is where the decay can be applied.
With a vector the pair term is ``sum_c k_ic k_jc exp(gamma_ic - gamma_jc)``,
which no product of ``k`` with ``k`` times a table gives: the decay has to
scale the operands' lanes before the product, and a factor ``exp(gamma_i -
ref) exp(ref - gamma_j)`` needs a reference row ``ref`` that keeps both
exponents small. :func:`_prepare_by_lane` cuts a chunk into sub-blocks of
:data:`SUB_BLOCK` (16) rows and gives sub-block ``I`` its own first row as the
reference, for the rows ``i`` of ``I`` against every ``j`` up to ``I``'s end::

    gamma_i - ref <= 0                   i in I (the rows after the reference)
    ref - gamma_j <= 0                   j before I
    0 <= ref - gamma_j <= 15 max|g|      j in I (the diagonal sub-block)

The first two are differences with the later row first, bounded by 0 as the
scalar form's. The third is positive and bounded only if ``g`` is: **the
caller keeps** ``|g| <= 88 / 16 = 5.5`` **a token and lane** (float32's
``exp`` overflows past 88.7). Kimi's safe gate does, ``g = -5 sigmoid(.)``:
15 rows move an exponent by at most 75, ``e^75 = 3.7e32``, and a sum of 128
such lanes stays under float32's 3.4e38 with ``|k| <= 1``. These products
(diagonal and off-diagonal sub-blocks alike) are float32 at ``HIGHEST``; the
upper triangle holds finite numbers and is masked after the product. An
unbounded gate (the paper's own ``-exp(A) softplus(.)``) would need a second
level of chunking inside the sub-block and is refused by nothing here: it
overflows. In the scan every exponent is ``gamma``, or ``gamma_C - gamma``:
``<= 0``. The vector form requires one key head a value head, and has the
same two programs (``decay: vector`` in :func:`rule_form`'s record):

* ``kernel`` (:func:`_prepare_by_lane_in_vmem`; a TPU, chunks of 16 rows times
  a power of two, key heads of whole lane tiles, an even number of chunks in
  tiles of ``LANE_CHUNK_TILE``): one Pallas call a direction whose grid step
  holds a tile of chunks of one (batch, head) in VMEM and takes them two at a
  time, side by side on the lanes (a chunk of 64 alone is half a lane tile),
  so that the masks, the inverse and its derivative run on the wide arrays of
  the scalar form at two value heads a key head. A sub-block's pair terms are
  one product: both chunks' 16 rows of q and of k, scaled by ``exp(gamma_i -
  ref)`` with the reference row a sublane broadcast, against both chunks' ``k
  exp(ref - gamma_j)`` (the rows after the sub-block left out of ``exp`` and
  zero), of which each chunk's rows keep their own chunk's lanes. The backward
  is written out: the decayed operands, the system and ``T`` again, ``dA`` as
  above, then a sub-block at a time ``d rows = ddots before``, ``d before =
  ddots^T rows``, dq and dk through both, and ``d gamma`` a lane, ``+ d rows
  rows`` on the sub-block's rows and ``- d before before`` up to its end (the
  reference row cancels in every pair term and is a constant of the
  derivative). ``gamma`` and ``d gamma``, ``[N, B, H, C, d_k]`` float32, are
  the only float32 arrays of a direction that cross HBM; ``gamma`` comes
  summed (:func:`rule_operands`).
* ``xla`` (:func:`_prepare_by_lane`; everything else, and what the tests hold
  the kernels to): the sub-blocks' products for all chunks at once, each under
  a ``jax.checkpoint`` of its own, the inverse and the backward as the scalar
  form's.

**The operands.** A block holds q and k as its convolution left them (``[B,
L, H_k, d_k]``, flat in memory) and its gate: the log decay a head ``g [B, L,
H]``, or, for a decay a key lane, the pre-activation ``a [B, L, H, d_k]`` of
the safe gate ``g = lower_bound sigmoid(exp(A_log) (a + dt_bias))``.
:func:`gated_delta_rule_from_raw` takes those; :func:`rule_operands` makes of
them what the rule reads, chunk-major: q and k L2-normalised in float32 (q
scaled by ``d_k^-0.5``) and rounded once to their dtype, and ``gamma``, by one
of two programs that :func:`rule_form` picks (``operands`` in its record):

* ``kernel`` (:func:`_operands_in_vmem`; a TPU, a chunk of whole 16-row tiles,
  a key head of whole lane tiles): one Pallas call a direction whose grid step
  reads a tile of chunks of one (batch, head) as rows of the flat arrays where
  they lie and writes them chunk-major (the same block of memory seen as
  ``[tile, C, d_k]``: the turn costs nothing here, and only here). With a
  decay a key lane the float32 gate, 134 MB a layer of the vector-decay cell,
  never crosses HBM: the call writes its running sum inside each chunk
  (:func:`_running_sum`) and the gate's least entry; rows past the sequence
  get ``g = 0, k = 0`` by their index. The backward is written out: the
  norms' and the sigmoid's derivatives and the sum's transpose in VMEM, dq, dk
  and ``da`` written flat where the convolution's backward call and the
  gate's projection read them, ``d A_log`` and ``d dt_bias`` summed over the
  sequential chunk axis. Each result leaves once a reader (the state-free
  part, the scan), so that their cotangents arrive apart and are summed in
  VMEM, not by XLA over 3 x 134 MB.
* ``xla`` (:func:`_operands`; everything else, and what the tests hold the
  kernels to): the blocks' float32 lines of before, :func:`_by_chunk`'s turns
  and ``jnp.cumsum`` as XLA's program under one ``jax.checkpoint``.

A decay a head is 2 MB a layer: its turn and sum stay XLA's in either program.
:func:`gated_delta_rule` itself takes q and k normalised and ``g`` (the tests'
entry; ``operands: given`` in the record) and turns and sums them so.

Precision, in either program: the running sums, ``exp``, the triangular system
and its inverse (exact: the doubling of :func:`_doubled_inverse`, in the kernels
from 8 x 8 blocks eliminated on the VPU, its products float32 at ``HIGHEST``;
no power series, no bfloat16 pass through ``A`` or ``T``) and the carried state
are float32; q, k, ``T beta``, the masked ``Q
K^T``, ``V - e^gamma K S``, ``V'`` and the state enter the matrix products in
the operands' dtype (the model's compute dtype) and are summed in float32, and
so do the cotangents of ``k k^T`` and ``q k^T`` on their way to dq and dk. With
a decay a key lane the pair terms' operands are float32 products of q or k and
an ``exp``: they, and their cotangents' products on the way to dq, dk and ``d
gamma``, are float32 at ``HIGHEST`` in either program. The
scan's backward is JAX's transpose of it; a caller bounds what it keeps with a
remat policy (the state-free part keeps its four operands and nothing else).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sav_tpu.ops import _backend
from sav_tpu.ops import attention as _attention

CHUNK = 64  # the published kernels' chunk
_HIGHEST = jax.lax.Precision.HIGHEST
_TILE = 8  # rows of a float32 sublane tile
CHUNK_TILE = 8  # chunks a grid step; a block of the gates' rows is then whole sublane tiles
# Chunks a trip of a grid step's loop. Two interleave their MXU and VPU work:
# both calls 13.7 ms a layer at the hybrid decoder's cell for 14.8 at one and
# 13.0 at four, whose twice-longer body takes a start 2.3 s to trace and lower
# where two take 1.0 (PERF.md section 6, PR 38). With a decay a key lane a trip
# takes two PAIRS of chunks: both calls 9.09 ms a layer at the vector-decay
# hybrid's cell for 9.51 at one pair (PERF.md section 6, PR 44).
_CHUNKS_A_TRIP = 2
_MAX_WIDTH = 256  # lanes of a wide array: its diagonal blocks are [width, width] float32
SUB_BLOCK = 16  # rows that share a reference row where the decay is a vector: 15 max|g| < 88


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
    PR 37); the doubling reads 14-16 ms there, ten products of ``[8192, 64,
    64]`` through HBM. Since PR 38 this is the ``xla`` form's inverse only
    (:func:`_prepare`): where the kernels run, :func:`_wide_inverse` takes the
    same steps on a chunk's systems in VMEM (PERF.md section 6, PR 38)."""
    return _doubled_inverse(lower)


def _unit_lower_inverse_fwd(lower):
    solved = _doubled_inverse(lower)
    return solved, solved


def _unit_lower_inverse_bwd(solved, g):
    back = -jnp.einsum(
        "...ji,...jk,...lk->...il", solved, g, solved, precision=_HIGHEST
    )
    n = solved.shape[-1]
    return (jnp.where(jnp.arange(n)[:, None] > jnp.arange(n)[None, :], back, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _joined(lower, row, col, size: int):
    """The entries of ``lower`` that join two diagonal blocks of ``size``
    into one of twice that: row in the lower half of a pair, column in its
    left half (``row``, ``col``: every entry's own, broadcast against it)."""
    pair = row // (2 * size) == col // (2 * size)
    return jnp.where(pair & (row % (2 * size) >= size) & (col % (2 * size) < size), lower, 0.0)


def _doubled_inverse(lower: jax.Array) -> jax.Array:
    n = lower.shape[-1]
    row, col = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    solved = jnp.eye(n, dtype=lower.dtype) - _joined(lower, row, col, 1)  # blocks of 2: [[1, 0], [b, 1]]^-1
    size = 2
    while size < n:
        solved = solved - jnp.einsum(
            "...ij,...jk,...kl->...il", solved, _joined(lower, row, col, size), solved, precision=_HIGHEST
        )
        size *= 2
    return solved


def _by_chunk(x: jax.Array, chunk: int) -> jax.Array:
    """``[B, L, H, ...]`` -> ``[N, B, H, C, ...]``, ``L`` padded to whole
    chunks with zeros."""
    batch, length = x.shape[:2]
    x = jnp.pad(x, ((0, 0), (0, -length % chunk)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((batch, -1, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)  # [N, B, C, H, ...] -> [N, B, H, C, ...]


def _summed_by_chunk(g: jax.Array, chunk: int) -> jax.Array:
    """``g [B, L, H(, d_k)]`` -> its running sum inside each chunk, ``[N, B,
    H, C(, d_k)]`` float32, as XLA's program. JAX transposes the sum as
    ``jax.lax.cumsum(reverse=True)``, never as flips (PERF.md section 6, PR
    44)."""
    return jnp.cumsum(_by_chunk(g.astype(jnp.float32), chunk), axis=3)


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _prepare(q, k, gamma, beta, group: int):
    """Everything of a chunk that does not read the state, for all chunks at
    once, on ``[N, B, H, C, ...]`` operands (``gamma`` the running sum of the
    decay's log inside a chunk): ``(T beta, the masked Q K^T)``, ``[N, B, H,
    C, C]`` in q's dtype. Checkpointed:
    the backward pass computes it again from the four operands, after the
    scan's own backward is done with its residuals, and holds the float32 ``C
    x C`` tensors of one direction at a time."""
    chunk, dtype = q.shape[3], q.dtype
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
    return solved, inside


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _prepare_by_lane(q, k, gamma, beta, group: int):
    """:func:`_prepare` where the decay is a vector a key lane: ``gamma [N, B,
    H, C, d_k]``, one key head a value head (``group`` 1). The pair terms
    ``sum_c x_ic k_jc exp(gamma_ic - gamma_jc)`` a sub-block of rows at a
    time, against the rows up to that sub-block's end, with its first row as
    the reference (the module docstring has the bound on every exponent);
    float32 at ``HIGHEST``, the upper triangle masked after the product."""
    del group  # 1: the rule has checked
    chunk, dtype = q.shape[3], q.dtype
    sub = math.gcd(chunk, SUB_BLOCK)

    @functools.partial(jax.checkpoint, static_argnums=(3,))  # a sub-block's float32 operands live for its own turn only
    def rows_of(q, k, gamma, low: int):
        high = low + sub
        ref = gamma[..., low:low + 1, :]
        after = jnp.exp(gamma[..., low:high, :] - ref)  # <= 1
        # <= 1 before the sub-block, <= e^75 inside it
        before = k[..., :high, :].astype(jnp.float32) * jnp.exp(ref - gamma[..., :high, :])

        def pairs(x):
            rows = x[..., low:high, :].astype(jnp.float32) * after
            dots = jnp.einsum("nbhid,nbhjd->nbhij", rows, before, precision=_HIGHEST)
            return jnp.pad(dots, ((0, 0),) * 4 + ((0, chunk - high),))

        return pairs(k), pairs(q)

    kk, qk = zip(*(rows_of(q, k, gamma, low) for low in range(0, chunk, sub)))
    kk, qk = jnp.concatenate(kk, axis=-2), jnp.concatenate(qk, axis=-2)
    rows = jnp.arange(chunk)
    system = jnp.where(rows[:, None] > rows[None, :], beta[..., :, None] * kk, 0.0)
    solved = (_unit_lower_inverse(system) * beta[..., None, :]).astype(dtype)  # T beta
    inside = jnp.where(rows[:, None] >= rows[None, :], qk, 0.0).astype(dtype)
    return solved, inside


# ---------------------------------------------------------------------------
# The same as one Pallas kernel a direction: a grid step holds a tile of
# chunks of one (batch, key head) in VMEM. A chunk's arrays are WIDE, ``[C,
# group x C]``: the ``C x C`` matrices of a key head's value heads side by
# side on the lanes (128 of them at chunk 64 and two value heads a key head),
# so that the masks, ``exp`` and the scalings fill a vector register, ``k k^T``
# and ``q k^T`` are one MXU pass a key head against ``k`` stacked ``group``
# times, and a product of two heads' matrices is one pass against the wide
# right operand laid out as diagonal blocks, ``[group x C, group x C]``.
# ---------------------------------------------------------------------------


def _diagonal_blocks(wide, chunk: int, group: int):
    """``[C, group C]`` -> ``[group C, group C]``: head ``h``'s matrix at
    block ``(h, h)``, zeros elsewhere, so that ``x_wide @ blocks`` is every
    head's ``x_h @ m_h``, wide."""
    if group == 1:
        return wide
    head = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 1) // chunk
    return jnp.concatenate([jnp.where(head == h, wide, 0.0) for h in range(group)], axis=0)


def _tiles_inverse(lower, chunk: int, group: int):
    """``(I + lower_h)^-1`` of the 8 x 8 diagonal blocks of every head of a
    wide array, wide and zero elsewhere, by elimination: the multipliers of a
    unit lower triangle are its own entries, so ``T <- T - a_p T[p, :]`` for
    the columns ``p`` of a block in turn, from ``T = I``. A block's rows are
    one float32 sublane tile: the pivot row is a sublane broadcast, and with
    tile ``b``'s rows turned left by ``8 b`` lanes (every head's block then
    starts at the head's first lane) a column of multipliers is one lane a
    head for all the tiles at once."""
    tiles, width = chunk // _TILE, group * chunk

    def turned(x, lanes):
        return pltpu.roll(x, lanes % width, 1) if lanes % width else x

    a = jnp.stack([turned(lower[b * _TILE:(b + 1) * _TILE], -b * _TILE) for b in range(tiles)])  # [tiles, 8, width]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _TILE, width), 2)
    sub = jax.lax.broadcasted_iota(jnp.int32, (1, _TILE, width), 1)
    solved = jnp.broadcast_to(jnp.where(lane % chunk == sub, 1.0, 0.0), a.shape)
    for p in range(_TILE - 1):
        column = a[:, :, p:p + 1]
        for h in range(1, group):
            column = jnp.where(lane // chunk == h, a[:, :, h * chunk + p:h * chunk + p + 1], column)
        solved = solved - column * solved[:, p:p + 1, :]
    return jnp.concatenate([turned(solved[b], b * _TILE) for b in range(tiles)], axis=0)


def _wide_inverse(lower, chunk: int, group: int):
    """``(I + lower_h)^-1`` of every head of a wide array (``chunk`` 8 times
    a power of two), float32 and exact as :func:`_doubled_inverse` is: the 8 x
    8 diagonal blocks by :func:`_tiles_inverse` on the VPU, then that
    function's doubling, ``X - X L X`` with the products at ``HIGHEST``, for
    the sizes from 8 on, where the rows that change (the lower half of every
    pair of blocks) are whole tiles and the only ones sent through the MXU."""
    row = jax.lax.broadcasted_iota(jnp.int32, lower.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, lower.shape, 1) % chunk

    def product(x, m):
        return jnp.dot(x, _diagonal_blocks(m, chunk, group), precision=_HIGHEST, preferred_element_type=jnp.float32)

    solved = _tiles_inverse(lower, chunk, group)
    size = _TILE
    while size < chunk:
        starts = range(0, chunk, 2 * size)
        low = jnp.concatenate([solved[r + size:r + 2 * size] for r in starts], axis=0)
        low = low - product(product(low, _joined(lower, row, col, size)), solved)
        solved = jnp.concatenate(
            [half for i, r in enumerate(starts) for half in (solved[r:r + size], low[i * size:(i + 1) * size])], axis=0
        )
        size *= 2
    return solved


def _over_lanes(x, chunk: int, group: int):
    """A row's sum over each head's lanes, on every lane of that head."""
    if group == 1:
        return jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)
    head = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // chunk
    out = jnp.zeros_like(x)
    for h in range(group):
        out = jnp.where(head == h, jnp.sum(jnp.where(head == h, x, 0.0), axis=1, keepdims=True), out)
    return out


def _chunk_system(q, k, gamma_row, beta_row, chunk: int, group: int):
    """What both kernels build of a chunk from its operands (``q, k [C,
    d_k]``, a row ``[1, group C]`` of running sums and one of ``beta``), all
    wide float32: the decay ``exp(gamma_i - gamma_j)`` under the mask, ``k
    k^T``, ``q k^T``, ``beta`` down the rows and along them, the strictly
    lower system and its inverse."""
    shape = (chunk, group * chunk)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % chunk
    eye = row == col
    gamma_j, beta_j = jnp.broadcast_to(gamma_row, shape), jnp.broadcast_to(beta_row, shape)
    # A row vector turned into a column: its diagonal, summed over the lanes.
    gamma_i = _over_lanes(jnp.where(eye, gamma_j, 0.0), chunk, group)
    beta_i = _over_lanes(jnp.where(eye, beta_j, 0.0), chunk, group)
    decay = jnp.exp(jnp.where(row >= col, gamma_i - gamma_j, -jnp.inf))
    stacked = jnp.concatenate([k] * group, axis=0)  # [group C, d_k]
    along = (((1,), (1,)), ((), ()))
    kk = jax.lax.dot_general(k, stacked, along, preferred_element_type=jnp.float32)
    qk = jax.lax.dot_general(q, stacked, along, preferred_element_type=jnp.float32)
    system = jnp.where(row > col, beta_i * kk * decay, 0.0)
    return dict(
        row=row, col=col, eye=eye, decay=decay, kk=kk, qk=qk, beta_i=beta_i, beta_j=beta_j,
        stacked=stacked, solved=_wide_inverse(system, chunk, group),
    )


def _for_each_chunk(tile: int, one_chunk) -> None:
    """A grid step's loop over its chunks, ``_CHUNKS_A_TRIP`` of them a trip."""
    a_trip = _CHUNKS_A_TRIP if tile % _CHUNKS_A_TRIP == 0 else 1

    def trip(i, carry):
        for j in range(a_trip):
            one_chunk(i * a_trip + j)
        return carry

    jax.lax.fori_loop(0, tile // a_trip, trip, None)


def _heads(ref, c, chunk: int, group: int):
    """A chunk's ``[group, C, C]`` block of ``ref`` as one wide float32 array."""
    return jnp.concatenate([ref[c, 0, h].astype(jnp.float32) for h in range(group)], axis=1)


def _prepare_fwd_kernel(q_ref, k_ref, gamma_ref, beta_ref, solved_ref, inside_ref, *, chunk: int, group: int):
    def one_chunk(c):
        s = _chunk_system(
            q_ref[c, 0, 0], k_ref[c, 0, 0], gamma_ref[0, 0, pl.ds(c, 1), :], beta_ref[0, 0, pl.ds(c, 1), :],
            chunk, group,
        )
        solved = (s["solved"] * s["beta_j"]).astype(solved_ref.dtype)  # T beta
        inside = (s["qk"] * s["decay"]).astype(inside_ref.dtype)
        for h in range(group):
            solved_ref[c, 0, h] = solved[:, h * chunk:(h + 1) * chunk]
            inside_ref[c, 0, h] = inside[:, h * chunk:(h + 1) * chunk]

    _for_each_chunk(q_ref.shape[0], one_chunk)


def _prepare_bwd_kernel(q_ref, k_ref, gamma_ref, beta_ref, dsolved_ref, dinside_ref,
                        dq_ref, dk_ref, dgamma_ref, dbeta_ref, *, chunk: int, group: int):
    dtype = q_ref.dtype

    def to_row(x_i, eye):  # what is constant along a head's lanes, as a row
        return jnp.sum(jnp.where(eye, x_i, 0.0), axis=0, keepdims=True)

    def one_chunk(c):
        q, k = q_ref[c, 0, 0], k_ref[c, 0, 0]
        s = _chunk_system(q, k, gamma_ref[0, 0, pl.ds(c, 1), :], beta_ref[0, 0, pl.ds(c, 1), :], chunk, group)
        solved, decay, eye = s["solved"], s["decay"], s["eye"]
        dsolved, dinside = _heads(dsolved_ref, c, chunk, group), _heads(dinside_ref, c, chunk, group)
        # T beta: beta scales T's columns.
        dbeta = jnp.sum(dsolved * solved, axis=0, keepdims=True)
        # The inverse's own derivative, dA = -T^T dT T^T under the strict mask.
        blocks = _diagonal_blocks(solved, chunk, group)
        turned = blocks.T  # T_h^T at block (h, h)
        right = jnp.dot(dsolved * s["beta_j"], turned, precision=_HIGHEST, preferred_element_type=jnp.float32)
        turned_wide = sum(turned[h * chunk:(h + 1) * chunk] for h in range(group))
        dsystem = -jnp.dot(
            turned_wide, _diagonal_blocks(right, chunk, group), precision=_HIGHEST, preferred_element_type=jnp.float32
        )
        dsystem = jnp.where(s["row"] > s["col"], dsystem, 0.0)
        by_beta = dsystem * s["kk"] * decay  # d system / d beta_i, entry by entry
        dbeta = dbeta + to_row(_over_lanes(by_beta, chunk, group), eye)
        # gamma enters through the decay alone: d decay x decay, row sums less column sums.
        through = s["beta_i"] * by_beta + dinside * s["qk"] * decay
        dgamma = to_row(_over_lanes(through, chunk, group), eye) - jnp.sum(through, axis=0, keepdims=True)
        dgamma_ref[0, 0, pl.ds(c, 1), :] = dgamma
        dbeta_ref[0, 0, pl.ds(c, 1), :] = dbeta
        # k k^T and q k^T once a key head: their cotangents meet the value
        # heads' sum inside the products (the contraction runs over group C).
        dkk = (dsystem * s["beta_i"] * decay).astype(dtype)
        dqk = (dinside * decay).astype(dtype)
        straight = jnp.dot(jnp.concatenate([dqk, dkk], axis=0), s["stacked"], preferred_element_type=jnp.float32)
        across = jax.lax.dot_general(  # [dkk_h^T k + dqk_h^T q] a head, stacked
            jnp.concatenate([dkk, dqk], axis=0), jnp.concatenate([k, q], axis=0),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        dq_ref[c, 0, 0] = straight[:chunk].astype(dq_ref.dtype)
        dk = straight[chunk:] + sum(across[h * chunk:(h + 1) * chunk] for h in range(group))
        dk_ref[c, 0, 0] = dk.astype(dk_ref.dtype)

    _for_each_chunk(q_ref.shape[0], one_chunk)


def _wide(x: jax.Array, group: int) -> jax.Array:
    """``[N, B, H, C]`` -> ``[B, H_k, N, group C]``: a key head's value heads
    side by side, the chunks down the rows of a block."""
    chunks, batch, heads, chunk = x.shape
    return jnp.transpose(x.reshape(chunks, batch, heads // group, group * chunk), (1, 2, 0, 3))


def _narrow(x: jax.Array, group: int) -> jax.Array:
    """:func:`_wide`'s inverse."""
    batch, key_heads, chunks, width = x.shape
    return jnp.transpose(x, (2, 0, 1, 3)).reshape(chunks, batch, key_heads * group, width // group)


def _specs(q, group: int, tile: int):
    chunks, batch, key_heads, chunk, dk = q.shape
    rows = pl.BlockSpec((tile, 1, 1, chunk, dk), lambda b, j, n: (n, b, j, 0, 0))
    vector = pl.BlockSpec((1, 1, tile, group * chunk), lambda b, j, n: (b, j, n, 0))
    square = pl.BlockSpec((tile, 1, group, chunk, chunk), lambda b, j, n: (n, b, j, 0, 0))
    return (batch, key_heads, chunks // tile), rows, vector, square


@functools.partial(jax.jit, static_argnames=("group", "tile", "interpret"))
def _prepare_forward(q, k, gamma, beta, group: int, tile: int, interpret: bool):
    """Jitted, as :func:`_prepare_backward` is: a model's layers of one shape
    share one trace and one lowering of the kernel (a second and a half a
    layer on a host, at every start)."""
    chunks, batch, key_heads, chunk, _ = q.shape
    grid, rows, vector, square = _specs(q, group, tile)
    out = jax.ShapeDtypeStruct((chunks, batch, key_heads * group, chunk, chunk), q.dtype)
    return pl.pallas_call(
        functools.partial(_prepare_fwd_kernel, chunk=chunk, group=group),
        grid=grid,
        in_specs=[rows, rows, vector, vector],
        out_specs=[square, square],
        out_shape=[out, out],
        interpret=interpret,
    )(q, k, _wide(gamma, group), _wide(beta, group))


@functools.partial(jax.jit, static_argnames=("group", "tile", "interpret"))
def _prepare_backward(q, k, gamma, beta, dsolved, dinside, group: int, tile: int, interpret: bool):
    grid, rows, vector, square = _specs(q, group, tile)
    wide = _wide(gamma, group)
    dq, dk, dgamma, dbeta = pl.pallas_call(
        functools.partial(_prepare_bwd_kernel, chunk=q.shape[3], group=group),
        grid=grid,
        in_specs=[rows, rows, vector, vector, square, square],
        out_specs=[rows, rows, vector, vector],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(wide.shape, jnp.float32), jax.ShapeDtypeStruct(wide.shape, jnp.float32),
        ],
        interpret=interpret,
    )(q, k, wide, _wide(beta, group), dsolved, dinside)
    return dq, dk, _narrow(dgamma, group), _narrow(dbeta, group)


def _interpreted(interpret: Optional[bool]) -> bool:
    return _backend.default_interpret() if interpret is None else interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _prepare_in_vmem(q, k, gamma, beta, group: int, tile: int = CHUNK_TILE, interpret: Optional[bool] = None):
    """:func:`_prepare` as the two kernels above: same operands, same two
    results, nothing ``C x C`` in float32 in HBM in either direction. The
    backward kernel builds the system, its inverse and the decay again from
    the four operands, which are all the forward keeps."""
    return tuple(_prepare_forward(q, k, gamma, beta, group, tile, _interpreted(interpret)))


def _prepare_in_vmem_fwd(q, k, gamma, beta, group, tile, interpret):
    return _prepare_in_vmem(q, k, gamma, beta, group, tile, interpret), (q, k, gamma, beta)


def _prepare_in_vmem_bwd(group, tile, interpret, residuals, cotangents):
    return _prepare_backward(*residuals, *cotangents, group, tile, _interpreted(interpret))


_prepare_in_vmem.defvjp(_prepare_in_vmem_fwd, _prepare_in_vmem_bwd)


# ---------------------------------------------------------------------------
# The decay a key lane, as one Pallas kernel a direction. One key head a value
# head, so a chunk alone is half a lane tile (64 of 128 lanes at chunk 64): a
# trip of a grid step's loop takes TWO chunks of the same head and lays their
# ``C x C`` matrices side by side, ``[C, 2 C]``, which is the wide array of a
# key head with two value heads: the masks, the inverse and its derivative
# run as they do there (``group`` 2). The pair terms cannot be one ``k k^T``
# times a table: a sub-block of SUB_BLOCK rows at a time, both chunks' rows of
# q and k (scaled by ``exp(gamma_i - ref)``) stacked as the left operand, ``[4
# x 16, d_k]``, against both chunks' ``k exp(ref - gamma_j)`` stacked, ``[2 C,
# d_k]``: one full-width product at HIGHEST a sub-block, of which the rows of
# chunk a keep the lanes of chunk a and those of b the lanes of b (the other
# half, one chunk's rows against the other's, is finite and dropped by a
# select). The backward's two products a sub-block run on the same operands
# with the cotangent laid out block-diagonally, nothing wasted.
# ---------------------------------------------------------------------------

LANE_CHUNK_TILE = 2 * CHUNK_TILE  # chunks a grid step: eight pairs, a block of beta's rows whole sublane tiles


def _paired(x: jax.Array) -> jax.Array:
    """``[N, B, H, C]`` -> ``[B, H, N / 2, 2 C]``: chunks ``2 p`` and ``2 p +
    1`` of a head side by side, the pairs down the rows of a block."""
    chunks, batch, heads, chunk = x.shape
    x = jnp.transpose(x.reshape(chunks // 2, 2, batch, heads, chunk), (2, 3, 0, 1, 4))
    return x.reshape(batch, heads, chunks // 2, 2 * chunk)


def _unpaired(x: jax.Array) -> jax.Array:
    """:func:`_paired`'s inverse."""
    batch, heads, pairs, width = x.shape
    x = jnp.transpose(x.reshape(batch, heads, pairs, 2, width // 2), (2, 3, 0, 1, 4))
    return x.reshape(2 * pairs, batch, heads, width // 2)


def _pair_system(q_ref, k_ref, gamma_ref, beta_row, p, chunk: int):
    """What both kernels build of chunks ``2 p`` and ``2 p + 1``: their
    operands stacked, ``[2 C, d_k]`` float32 (chunk a's rows, then b's); a
    sub-block's decayed operands (``x [4 x 16, d_k]``, its rows of q and of k
    of a, then of b, ``after`` their ``exp(gamma - ref)`` and ``rows = x
    after`` the left operand; ``grown = exp(ref - gamma)`` and ``before = k
    grown``, ``[2 C, d_k]``, zero past the sub-block's end: masked by leaving
    those rows out of ``exp``, whose exponent there is unbounded); the pair terms ``kk`` and ``qk``, ``beta``
    down the rows and along them, the strictly lower system and its inverse,
    wide (``[C, 2 C]``) and float32."""
    sub = SUB_BLOCK
    q, k, gamma = (
        jnp.concatenate([ref[2 * p, 0, 0], ref[2 * p + 1, 0, 0]], axis=0).astype(jnp.float32)
        for ref in (q_ref, k_ref, gamma_ref)
    )
    shape = (chunk, 2 * chunk)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = lane % chunk
    # A sub-block's rows of q and of k: chunk a's lanes, then b's (an iota of its own: Mosaic keeps one along the
    # lanes as a single row of registers and cannot slice it by rows)
    of_a = jax.lax.broadcasted_iota(jnp.int32, (2 * sub, 2 * chunk), 1) < chunk
    blocks = []
    for low in range(0, chunk, sub):
        high = low + sub
        after, grown, x = [], [], []
        for side, at in enumerate((0, chunk)):  # chunk a, chunk b
            ref = gamma_ref[2 * p + side, 0, 0, low:low + 1, :]  # the sub-block's first row, down the sublanes
            decay = jnp.exp(gamma[at + low:at + high] - ref)  # <= 1
            after += [decay, decay]
            x += [q[at + low:at + high], k[at + low:at + high]]
            # <= 1 before the sub-block, <= e^75 inside it; the rows after it are never exponentiated
            grown.append(jnp.exp(ref - gamma[at:at + high]))
            if high < chunk:
                grown.append(jnp.zeros((chunk - high, gamma.shape[1]), jnp.float32))
        after, grown = jnp.concatenate(after, axis=0), jnp.concatenate(grown, axis=0)
        x = jnp.concatenate(x, axis=0)
        rows, before = x * after, k * grown
        dots = jax.lax.dot_general(
            rows, before, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32
        )  # [4 x 16, 2 C]
        wide = jnp.where(of_a, dots[:2 * sub], dots[2 * sub:])  # q's rows, then k's
        blocks.append(dict(low=low, high=high, x=x, after=after, grown=grown, rows=rows, before=before, wide=wide))
    qk = jnp.concatenate([b["wide"][:sub] for b in blocks], axis=0)
    kk = jnp.concatenate([b["wide"][sub:] for b in blocks], axis=0)
    eye = row == col
    beta_j = jnp.broadcast_to(beta_row, shape)
    beta_i = _over_lanes(jnp.where(eye, beta_j, 0.0), chunk, 2)  # a row vector turned into a column
    system = jnp.where(row > col, beta_i * kk, 0.0)
    return dict(
        k=k, row=row, of_a=of_a, col=col, eye=eye, blocks=blocks, qk=qk, kk=kk, beta_i=beta_i, beta_j=beta_j,
        solved=_wide_inverse(system, chunk, 2),
    )


def _prepare_by_lane_fwd_kernel(q_ref, k_ref, gamma_ref, beta_ref, solved_ref, inside_ref, *, chunk: int):
    def one_pair(p):
        s = _pair_system(q_ref, k_ref, gamma_ref, beta_ref[0, 0, pl.ds(p, 1), :], p, chunk)
        solved = (s["solved"] * s["beta_j"]).astype(solved_ref.dtype)  # T beta
        inside = jnp.where(s["row"] >= s["col"], s["qk"], 0.0).astype(inside_ref.dtype)
        for side in range(2):
            solved_ref[2 * p + side, 0, 0] = solved[:, side * chunk:(side + 1) * chunk]
            inside_ref[2 * p + side, 0, 0] = inside[:, side * chunk:(side + 1) * chunk]

    _for_each_chunk(q_ref.shape[0] // 2, one_pair)  # two pairs a trip


def _prepare_by_lane_bwd_kernel(q_ref, k_ref, gamma_ref, beta_ref, dsolved_ref, dinside_ref,
                                dq_ref, dk_ref, dgamma_ref, dbeta_ref, *, chunk: int):
    sub = SUB_BLOCK

    def one_pair(p):
        s = _pair_system(q_ref, k_ref, gamma_ref, beta_ref[0, 0, pl.ds(p, 1), :], p, chunk)
        solved, row, col, of_a = s["solved"], s["row"], s["col"], s["of_a"]
        dsolved, dinside = (
            jnp.concatenate([ref[2 * p, 0, 0], ref[2 * p + 1, 0, 0]], axis=1).astype(jnp.float32)
            for ref in (dsolved_ref, dinside_ref)
        )
        # T beta: beta scales T's columns.
        dbeta = jnp.sum(dsolved * solved, axis=0, keepdims=True)
        # The inverse's own derivative, dA = -T^T dT T^T under the strict mask.
        turned = _diagonal_blocks(solved, chunk, 2).T  # T^T of chunk a at block (0, 0), of b at (1, 1)
        right = jnp.dot(dsolved * s["beta_j"], turned, precision=_HIGHEST, preferred_element_type=jnp.float32)
        dsystem = -jnp.dot(
            turned[:chunk] + turned[chunk:], _diagonal_blocks(right, chunk, 2),
            precision=_HIGHEST, preferred_element_type=jnp.float32,
        )
        dsystem = jnp.where(row > col, dsystem, 0.0)
        by_beta = _over_lanes(dsystem * s["kk"], chunk, 2)  # d system / d beta_i, summed along the row
        dbeta_ref[0, 0, pl.ds(p, 1), :] = dbeta + jnp.sum(jnp.where(s["eye"], by_beta, 0.0), axis=0, keepdims=True)
        dkk, dqk = dsystem * s["beta_i"], jnp.where(row >= col, dinside, 0.0)
        # The pair terms, a sub-block at a time: dots = rows before^T, rows = x after, before = k grown.
        through = jnp.zeros_like(s["k"])  # d before x grown, [2 C, d_k]: every sub-block's rows up to its end
        dk_rows, dgamma_rows = ([], []), ([], [])  # through ``rows``: a sub-block's own, of chunk a and of b
        for b in s["blocks"]:
            low, high = b["low"], b["high"]
            ddots = jnp.concatenate([dqk[low:high], dkk[low:high]], axis=0)  # [2 x 16, 2 C]
            # Chunk a's rows keep a's lanes, b's rows b's: the forward's select, transposed.
            ddots = jnp.concatenate([jnp.where(of_a, ddots, 0.0), jnp.where(of_a, 0.0, ddots)], axis=0)
            drows = jnp.dot(ddots, b["before"], precision=_HIGHEST, preferred_element_type=jnp.float32)
            dbefore = jax.lax.dot_general(
                ddots, b["rows"], (((0,), (0,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32
            )
            through = through + dbefore * b["grown"]
            dx = drows * b["after"]  # [q of a | k of a | q of b | k of b]
            by_gamma = dx * b["x"]  # d rows x rows
            for side in range(2):
                at = 2 * side * sub
                dq_ref[2 * p + side, 0, 0, low:high, :] = dx[at:at + sub].astype(dq_ref.dtype)
                dk_rows[side].append(dx[at + sub:at + 2 * sub])
                dgamma_rows[side].append(by_gamma[at:at + sub] + by_gamma[at + sub:at + 2 * sub])
        # gamma enters through the two exponents alone, + on a sub-block's own rows and - on the rows up to its
        # end; the reference row cancels in every pair term and is a constant of the derivative.
        dk = through + jnp.concatenate(dk_rows[0] + dk_rows[1], axis=0)
        dgamma = jnp.concatenate(dgamma_rows[0] + dgamma_rows[1], axis=0) - through * s["k"]
        for side in range(2):
            dk_ref[2 * p + side, 0, 0] = dk[side * chunk:(side + 1) * chunk].astype(dk_ref.dtype)
            dgamma_ref[2 * p + side, 0, 0] = dgamma[side * chunk:(side + 1) * chunk]

    _for_each_chunk(q_ref.shape[0] // 2, one_pair)  # two pairs a trip


def _by_lane_specs(q, tile: int):
    """:func:`_specs` at one key head a value head, with ``beta``'s block the
    pairs' rows of :func:`_paired`."""
    chunk = q.shape[3]
    grid, rows, _, square = _specs(q, 1, tile)
    return grid, rows, pl.BlockSpec((1, 1, tile // 2, 2 * chunk), lambda b, j, n: (b, j, n, 0)), square


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _prepare_by_lane_forward(q, k, gamma, beta, tile: int, interpret: bool):
    """Jitted, as :func:`_prepare_forward` is and for its reason."""
    chunks, batch, heads, chunk, _ = q.shape
    grid, rows, vector, square = _by_lane_specs(q, tile)
    out = jax.ShapeDtypeStruct((chunks, batch, heads, chunk, chunk), q.dtype)
    return pl.pallas_call(
        functools.partial(_prepare_by_lane_fwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[rows, rows, rows, vector],
        out_specs=[square, square],
        out_shape=[out, out],
        interpret=interpret,
    )(q, k, gamma, _paired(beta))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _prepare_by_lane_backward(q, k, gamma, beta, dsolved, dinside, tile: int, interpret: bool):
    grid, rows, vector, square = _by_lane_specs(q, tile)
    paired = _paired(beta)
    dq, dk, dgamma, dbeta = pl.pallas_call(
        functools.partial(_prepare_by_lane_bwd_kernel, chunk=q.shape[3]),
        grid=grid,
        in_specs=[rows, rows, rows, vector, square, square],
        out_specs=[rows, rows, rows, vector],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(gamma.shape, jnp.float32), jax.ShapeDtypeStruct(paired.shape, jnp.float32),
        ],
        interpret=interpret,
    )(q, k, gamma, paired, dsolved, dinside)
    return dq, dk, dgamma, _unpaired(dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _prepare_by_lane_in_vmem(q, k, gamma, beta, group: int, tile: int = LANE_CHUNK_TILE,
                             interpret: Optional[bool] = None):
    """:func:`_prepare_by_lane` as the two kernels above (``tile`` chunks a
    grid step, an even number): same operands, same two results. Of a
    direction's float32 arrays only ``gamma`` and ``d gamma`` cross HBM, ``[N,
    B, H, C, d_k]`` each. The backward kernel builds the decayed operands, the
    system and its inverse again from the four operands, which are all the
    forward keeps."""
    del group  # 1: the rule has checked
    return tuple(_prepare_by_lane_forward(q, k, gamma, beta, tile, _interpreted(interpret)))


def _prepare_by_lane_in_vmem_fwd(q, k, gamma, beta, group, tile, interpret):
    return _prepare_by_lane_in_vmem(q, k, gamma, beta, group, tile, interpret), (q, k, gamma, beta)


def _prepare_by_lane_in_vmem_bwd(group, tile, interpret, residuals, cotangents):
    return _prepare_by_lane_backward(*residuals, *cotangents, tile, _interpreted(interpret))


_prepare_by_lane_in_vmem.defvjp(_prepare_by_lane_in_vmem_fwd, _prepare_by_lane_in_vmem_bwd)


# ---------------------------------------------------------------------------
# The rule's operands, as one Pallas kernel a direction: a grid step holds a
# tile of chunks of one (batch, key head), read as rows of the flat ``[B, L, H
# d]`` arrays the convolution and the projection wrote (a ``[tile x C, d_k]``
# block of the flat array IS a ``[tile, C, d_k]`` block of the chunk-major one:
# writing chunk-major is the one place the turn is free), and walks them a
# chunk at a time in registers. Bandwidth-bound bodies: a lane reduction a row
# for each norm, a sigmoid a lane for the gate, the running sum inside a chunk
# by :func:`_running_sum`.
# ---------------------------------------------------------------------------

OPERANDS_TILE = 16  # chunks a grid step: 1,024 rows of one head
# How a chunk's rows are summed in VMEM: "rolls" (log2 C sublane rolls and
# adds) or "triangle" (a [C, C] triangle of ones against the chunk at HIGHEST).
# tools/kda_prepare_micro.py times both (PERF.md section 6, PR 45).
_SUM_FORM = "rolls"


_NORM_EPS = 1e-6


def l2_normalise(x: jax.Array, eps: float = _NORM_EPS) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _running_sum(x, reverse: bool = False):
    """The running sum down the rows of ``x [C, lanes]`` float32 (up them,
    from the last row, where ``reverse``: the sum's transpose), to float32
    rounding what ``jnp.cumsum`` gives."""
    chunk = x.shape[0]
    if _SUM_FORM == "triangle":
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        ones = jnp.where(row <= col if reverse else row >= col, 1.0, 0.0)
        return jnp.dot(ones, x, precision=_HIGHEST, preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    step = 1
    while step < chunk:  # row i gains row i - step's sum of step rows
        if reverse:
            x = x + jnp.where(row < chunk - step, pltpu.roll(x, chunk - step, 0), 0.0)
        else:
            x = x + jnp.where(row >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _chunk_rows(ref, c, chunk: int, live):
    """Chunk ``c``'s rows of a flat block, float32, zero past the sequence."""
    x = ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :].astype(jnp.float32)
    return x if live is None else jnp.where(live, x, 0.0)


def _live_rows(tile: int, chunk: int, lanes: int, length: int):
    """``live(c)``: which rows of chunk ``c`` of this grid step lie inside the
    sequence (``None`` where every block does: ``length`` is whole tiles)."""
    if length % (tile * chunk) == 0:
        return lambda c: None
    first = pl.program_id(2) * tile  # read here: the interpreter knows no program_id inside a loop's body
    return lambda c: (first + c) * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 0) < length


def _operands_fwd_kernel(*refs, chunk: int, length: int, lower_bound):
    """``refs``: q's and k's rows, with a decay a key lane the gate's ``a``
    and the head's rate and offsets; then the results: q and k normalised,
    chunk-major, and with the gate ``gamma`` and the least ``g`` a lane so
    far (one block a (batch, head), over the sequential chunk axis)."""
    by_lane = len(refs) > 4
    operands = 5 if by_lane else 2
    q_ref, k_ref, *gate_refs = refs[:operands]
    q_out, k_out, *gate_outs = refs[operands:]
    tile, dk = q_out.shape[0], q_ref.shape[-1]
    if by_lane:
        a_ref, rate_ref, offset_ref = gate_refs
        gamma_out, least_out = gate_outs

        @pl.when(pl.program_id(2) == 0)
        def _():
            least_out[...] = jnp.zeros_like(least_out)  # g <= 0

    live_rows = _live_rows(tile, chunk, dk, length)

    def one_chunk(c):
        live = live_rows(c)

        def unit(ref):  # l2_normalise's arithmetic; a row past the sequence is 0
            x = _chunk_rows(ref, c, chunk, live)
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + _NORM_EPS)

        q_out[c, 0, 0] = (unit(q_ref) * dk ** -0.5).astype(q_out.dtype)
        k_out[c, 0, 0] = unit(k_ref).astype(k_out.dtype)
        if by_lane:
            g = lower_bound * jax.nn.sigmoid(rate_ref[...] * (_chunk_rows(a_ref, c, chunk, None) + offset_ref[...]))
            g = g if live is None else jnp.where(live, g, 0.0)
            gamma_out[c, 0, 0] = _running_sum(g)
            least_out[0, 0] = jnp.minimum(least_out[0, 0], jnp.min(g, axis=0, keepdims=True))

    _for_each_chunk(tile, one_chunk)


def _operands_bwd_kernel(*refs, chunk: int, length: int, lower_bound):
    """``refs``: the forward's operands; the chunk-major cotangents of q and k
    normalised and, with the gate, of ``gamma``, once a reader of the
    forward's results (the state-free part, then the scan) and summed here;
    then the results: dq, dk and ``da`` as rows of the flat arrays, and the
    sums over this (batch, head)'s rows so far of ``d offsets`` and ``d rate``
    a lane."""
    by_lane = len(refs) > 8
    operands, n = (5, 3) if by_lane else (2, 2)  # the forward's operands, its results a reader
    q_ref, k_ref, *gate_refs = refs[:operands]
    once, again, results = refs[operands:operands + n], refs[operands + n:operands + 2 * n], refs[operands + 2 * n:]
    if by_lane:
        a_ref, rate_ref, offset_ref = gate_refs
        dq_ref, dk_ref, da_ref, sums_ref = results

        @pl.when(pl.program_id(2) == 0)
        def _():
            sums_ref[...] = jnp.zeros_like(sums_ref)
    else:
        dq_ref, dk_ref = results
    tile, dk = once[0].shape[0], q_ref.shape[-1]
    live_rows = _live_rows(tile, chunk, dk, length)

    def one_chunk(c):
        live = live_rows(c)
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        dqn, dkn, *dgamma = (a[c, 0, 0].astype(jnp.float32) + b[c, 0, 0].astype(jnp.float32) for a, b in zip(once, again))

        def through_unit(ref, dy, scale):  # y = scale x r, r = rsqrt(x . x + eps): dx = scale r (dy - r^2 x (dy . x))
            x = _chunk_rows(ref, c, chunk, live)
            r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + _NORM_EPS)
            return (scale * r) * (dy - (r * r * jnp.sum(dy * x, axis=1, keepdims=True)) * x)

        dq_ref[rows, :] = through_unit(q_ref, dqn, dk ** -0.5).astype(dq_ref.dtype)
        dk_ref[rows, :] = through_unit(k_ref, dkn, 1.0).astype(dk_ref.dtype)
        if by_lane:
            dg = _running_sum(dgamma[0], reverse=True)
            dg = dg if live is None else jnp.where(live, dg, 0.0)  # a row past the sequence has g = 0 whatever a is
            rate = rate_ref[...]
            shifted = _chunk_rows(a_ref, c, chunk, live) + offset_ref[...]
            s = jax.nn.sigmoid(rate * shifted)
            dscaled = dg * (lower_bound * s * (1.0 - s))  # d (rate x shifted)
            da = dscaled * rate
            da_ref[rows, :] = da.astype(da_ref.dtype)
            sums_ref[0, 0, 0:1, :] += jnp.sum(da, axis=0, keepdims=True)
            sums_ref[0, 0, 1:2, :] += jnp.sum(dscaled * shifted, axis=0, keepdims=True)

    _for_each_chunk(tile, one_chunk)


def _operands_specs(q, chunk: int, tile: int):
    batch, length, key_heads, dk = q.shape
    grid = (batch, key_heads, -(-length // chunk) // tile)
    flat = pl.BlockSpec((None, tile * chunk, dk), lambda b, h, n: (b, n, h))
    major = pl.BlockSpec((tile, 1, 1, chunk, dk), lambda b, h, n: (n, b, h, 0, 0))
    a_head = pl.BlockSpec((None, 1, dk), lambda b, h, n: (h, 0, 0))  # of [H, 1, d_k]
    params = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))
    return grid, flat, major, a_head, params


def _flat(x):  # [B, L, H, d] -> [B, L, H d]: the array as the convolution's call wrote it
    return x.reshape(x.shape[:2] + (-1,))


def _operands_inputs(q, k, gate, flat, a_head):
    """Both calls' operands and their specs: q's and k's rows and, with the
    gate, ``a``'s, the rate ``[H]`` and the offsets ``[H d_k]`` as ``[H, 1,
    d_k]`` float32."""
    if gate is None:
        return [_flat(q), _flat(k)], [flat, flat]
    heads, dk = q.shape[2:]
    a, *by_head = gate
    by_head = [jnp.broadcast_to(x.astype(jnp.float32).reshape(heads, 1, -1), (heads, 1, dk)) for x in by_head]
    return [_flat(q), _flat(k), _flat(a)] + by_head, [flat, flat, flat, a_head, a_head]


@functools.partial(jax.jit, static_argnames=("chunk", "lower_bound", "tile", "interpret"))
def _operands_forward(q, k, gate, chunk: int, lower_bound, tile: int, interpret: bool):
    """``q, k [B, L, H_k, d_k]`` and ``gate`` ``None`` or ``(a [B, L, H, d_k],
    rate [H], offsets [H d_k])`` -> q and k normalised ``[N, B, H_k, C,
    d_k]`` and, with the gate, ``gamma [N, B, H, C, d_k]`` float32 and the
    least ``g`` a (batch, head, lane). Jitted, as :func:`_prepare_forward` is
    and for its reason."""
    batch, length, heads, dim = q.shape
    grid, flat, major, a_head, params = _operands_specs(q, chunk, tile)
    chunks = grid[2] * tile
    by_chunk = jax.ShapeDtypeStruct((chunks, batch, heads, chunk, dim), q.dtype)
    operands, in_specs = _operands_inputs(q, k, gate, flat, a_head)
    out_specs, out_shape = [major, major], [by_chunk, by_chunk]
    if gate is not None:
        out_specs += [major, pl.BlockSpec((1, 1, 1, dim), lambda b, h, n: (b, h, 0, 0))]
        out_shape += [
            jax.ShapeDtypeStruct(by_chunk.shape, jnp.float32), jax.ShapeDtypeStruct((batch, heads, 1, dim), jnp.float32)
        ]
    return pl.pallas_call(
        functools.partial(_operands_fwd_kernel, chunk=chunk, length=length, lower_bound=lower_bound),
        grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=params, interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("chunk", "lower_bound", "tile", "interpret"))
def _operands_backward(q, k, gate, cotangents, chunk: int, lower_bound, tile: int, interpret: bool):
    """The forward's operands and the cotangents of its results (of q and k
    normalised and, with the gate, of ``gamma``: a reader's, then the
    other's) -> ``dq, dk`` and, with the gate, ``(da, d rate, d offsets)``,
    as the operands lie."""
    batch, length, heads, dim = q.shape
    grid, flat, major, a_head, params = _operands_specs(q, chunk, tile)
    rows = jax.ShapeDtypeStruct(_flat(q).shape, q.dtype)
    operands, in_specs = _operands_inputs(q, k, gate, flat, a_head)
    out_specs, out_shape = [flat, flat], [rows, rows]
    if gate is not None:
        a, _, offsets = gate
        out_specs += [flat, pl.BlockSpec((1, 1, 2, dim), lambda b, h, n: (b, h, 0, 0))]
        out_shape += [jax.ShapeDtypeStruct(rows.shape, a.dtype), jax.ShapeDtypeStruct((batch, heads, 2, dim), jnp.float32)]
    dq, dk, *dgate = pl.pallas_call(
        functools.partial(_operands_bwd_kernel, chunk=chunk, length=length, lower_bound=lower_bound),
        grid=grid, in_specs=in_specs + [major] * len(cotangents), out_specs=out_specs, out_shape=out_shape,
        compiler_params=params, interpret=interpret,
    )(*operands, *cotangents)
    dq, dk = dq.reshape(q.shape), dk.reshape(k.shape)
    if gate is None:
        return dq, dk, None
    da, sums = dgate
    sums = jnp.sum(sums, axis=0)  # over the batch: [H, 2, d_k], kilobytes
    return dq, dk, (da.reshape(a.shape), jnp.sum(sums[:, 1], axis=-1), sums[:, 0].reshape(offsets.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _operands_in_vmem(q, k, gate, chunk: int, lower_bound, tile: int = OPERANDS_TILE,
                      interpret: Optional[bool] = None):
    """:func:`_operands` as the two kernels above: same operands, same
    results. Nothing of them crosses HBM but the arrays read where they lie
    and the chunk-major results; the backward kernel computes the norms and
    the gate again from the operands, which are all the forward keeps. The
    results leave once a reader (the same arrays twice: JAX adds the
    cotangents of a result with two readers before a backward sees them, 3 x
    134 MB through HBM for ``gamma`` a layer of the vector-decay cell; two
    results' arrive apart and are summed in VMEM, in float32)."""
    if gate is None:
        q, k = _operands_forward(q, k, None, chunk, lower_bound, tile, _interpreted(interpret))
        return (q, k, None), (q, k, None), None
    a, a_log, dt_bias = gate
    *operands, least = _operands_forward(
        q, k, (a, jnp.exp(a_log), dt_bias), chunk, lower_bound, tile, _interpreted(interpret)
    )
    return tuple(operands), tuple(operands), jnp.min(least)


def _operands_in_vmem_fwd(q, k, gate, chunk, lower_bound, tile, interpret):
    return _operands_in_vmem(q, k, gate, chunk, lower_bound, tile, interpret), (q, k, gate)


def _operands_in_vmem_bwd(chunk, lower_bound, tile, interpret, residuals, cotangents):
    q, k, gate = residuals
    once, again, _ = cotangents
    if gate is None:
        return _operands_backward(q, k, None, once[:2] + again[:2], chunk, lower_bound, tile, _interpreted(interpret))
    a, a_log, dt_bias = gate
    rate = jnp.exp(a_log)
    dq, dk, (da, drate, doffsets) = _operands_backward(
        q, k, (a, rate, dt_bias), once + again, chunk, lower_bound, tile, _interpreted(interpret)
    )
    return dq, dk, (da, (drate * rate).astype(a_log.dtype), doffsets.astype(dt_bias.dtype))


_operands_in_vmem.defvjp(_operands_in_vmem_fwd, _operands_in_vmem_bwd)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))  # float32 inside; the backward pass starts from the operands
def _operands(q, k, gate, chunk: int, lower_bound):
    """What a block hands the rule, as XLA's program: ``q, k [B, L, H_k,
    d_k]`` as the convolution left them -> L2-normalised (q scaled by
    ``d_k^-0.5``), rounded once to their dtype, chunk-major ``[N, B, H_k, C,
    d_k]``; with ``gate = (a [B, L, H, d_k], A_log [H], dt_bias [H d_k])``,
    the safe gate ``g = lower_bound sigmoid(exp(A_log) (a + dt_bias))`` a key
    lane, float32, and its running sum inside each chunk. ``((q, k, gamma),
    the same again, the least g)``: once a reader; ``None`` for ``gamma`` and
    the least without a gate."""
    dtype, dk = q.dtype, q.shape[-1]
    q = _by_chunk((l2_normalise(q) * dk ** -0.5).astype(dtype), chunk)
    k = _by_chunk(l2_normalise(k).astype(dtype), chunk)
    if gate is None:
        return (q, k, None), (q, k, None), None
    a, a_log, dt_bias = gate
    rate = jnp.exp(a_log)[:, None] * (a.astype(jnp.float32) + dt_bias.reshape(a.shape[2:]))
    g = lower_bound * jax.nn.sigmoid(rate)
    operands = (q, k, _summed_by_chunk(g, chunk))
    return operands, operands, jnp.min(g)


def rule_form(chunks: int, chunk: int, key_dim: int, group: int, *, by_lane: bool = False,
              on_tpu: Optional[bool] = None) -> dict:
    """Which programs compute the rule's operands and the part that does not
    read the state, from what the code can observe. The state-free part:
    ``{"rule": "kernel", "chunk_tile": n}`` on a TPU where Mosaic takes the
    shapes, else ``{"rule": "xla", "refused": why}``; with a decay a key lane
    (``by_lane``) either record also says ``"decay": "vector"``. The kernels
    want a chunk of whole bfloat16 tiles (16 rows) that is a power of two (the
    inverse doubles its blocks from 8 rows up), a key head of whole lane tiles,
    the wide arrays whole lane tiles and no wider than ``_MAX_WIDTH`` (``group
    x chunk`` lanes: a key head's value heads side by side; with a decay a key
    lane ``2 x chunk``, two chunks of one head, so the chunks have to pair up),
    and the chunks in tiles of ``CHUNK_TILE`` (``LANE_CHUNK_TILE`` with a decay
    a key lane), or all of them in one. The operands (:func:`rule_operands`),
    beside either: ``"operands": "kernel", "operands_tile": n`` (the most
    chunks up to ``OPERANDS_TILE`` that divide the sequence's) on a TPU at a
    chunk of whole 16-row tiles and a key head of whole lane tiles, else
    ``"operands": "xla", "operands_refused": why``."""
    if on_tpu is None:
        on_tpu = _attention._on_tpu()
    decay = {"decay": "vector"} if by_lane else {}
    tile = LANE_CHUNK_TILE if by_lane else CHUNK_TILE
    width = (2 if by_lane else group) * chunk
    lanes = f"key head {key_dim} is not whole lane tiles" if key_dim % 128 else None
    if not on_tpu:
        refused = operands_refused = "non-TPU backend"
    elif chunk % 16 or chunk & (chunk - 1):
        refused = f"chunk {chunk} is not a power of two of whole 16-row tiles"
        operands_refused = f"chunk {chunk} is not whole 16-row tiles" if chunk % 16 else lanes
    elif lanes:
        refused = operands_refused = lanes
    else:
        operands_refused = None
        if width % 128 or width > _MAX_WIDTH:
            side_by_side = "two chunks side by side" if by_lane else f"{group} value heads a key head"
            refused = f"{side_by_side} x chunk {chunk} = {width} lanes"
        elif by_lane and chunks % 2:
            refused = f"{chunks} chunks do not pair up"
        elif chunks % tile and chunks > tile:
            refused = f"{chunks} chunks are not whole tiles of {tile}"
        else:
            refused = None
    form = {"rule": "xla", **decay, "refused": refused} if refused else {
        "rule": "kernel", **decay, "chunk_tile": min(tile, chunks)
    }
    if operands_refused:
        return {**form, "operands": "xla", "operands_refused": operands_refused}
    most = next(n for n in range(min(chunks, OPERANDS_TILE), 0, -1) if chunks % n == 0)
    return {**form, "operands": "kernel", "operands_tile": most}


def rule_operands(q, k, gate, chunk: int = CHUNK, *, a_log=None, dt_bias=None, lower_bound=None):
    """A block's arrays -> the rule's operands, chunk-major: ``(q, k [N, B,
    H_k, C, d_k]`` L2-normalised (q scaled by ``d_k^-0.5``) in their dtype,
    ``gamma [N, B, H, C(, d_k)]``: the running sum of the decay's log inside
    each chunk, float32``)``, once for the state-free part and once for the
    scan (the same arrays), and the least log decay of the call.

    ``q, k [B, L, H_k, d_k]`` as the convolution left them. What ``gate`` is
    tells the decay: ``g [B, L, H]``, the log of a decay a head, summed by
    XLA (2 MB a layer); or ``a [B, L, H, d_k]``, the pre-activation of the
    safe gate a key lane ``g = lower_bound sigmoid(exp(a_log) (a +
    dt_bias))``, which never crosses HBM where the kernels run
    (:func:`_operands_in_vmem`; ``form["operands"]``, :func:`rule_form`'s to
    say), only its running sum does."""
    by_lane = gate.ndim == 4
    form = rule_form(-(-q.shape[1] // chunk), chunk, q.shape[-1], gate.shape[2] // q.shape[2], by_lane=by_lane)
    packed = (gate, a_log, dt_bias) if by_lane else None
    if form["operands"] == "kernel":
        for_prepare, for_scan, least = _operands_in_vmem(q, k, packed, chunk, lower_bound, form["operands_tile"])
    else:
        for_prepare, for_scan, least = _operands(q, k, packed, chunk, lower_bound)
    if not by_lane:
        gamma, least = _summed_by_chunk(gate, chunk), jnp.min(gate)
        for_prepare, for_scan = for_prepare[:2] + (gamma,), for_scan[:2] + (gamma,)
    return for_prepare, for_scan, least


def _checked_form(q, k, v, g, beta, chunk: int) -> tuple:
    """The rule's shapes checked; ``(its form, the state-free part's program)``."""
    batch, length, key_heads, dk = q.shape
    heads = v.shape[2]
    by_lane = g.ndim == 4
    lanes_fit = g.shape[3:] == (dk,) and heads == key_heads if by_lane else True
    if (heads % key_heads or k.shape != q.shape or g.shape[:3] != v.shape[:3] or beta.shape != g.shape[:3]
            or not lanes_fit):
        raise ValueError(
            f"gated delta rule: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape}"
        )
    form = rule_form(-(-length // chunk), chunk, dk, heads // key_heads, by_lane=by_lane)
    if form["rule"] == "kernel":
        prepare = functools.partial(_prepare_by_lane_in_vmem if by_lane else _prepare_in_vmem, tile=form["chunk_tile"])
    else:
        prepare = _prepare_by_lane if by_lane else _prepare
    return form, prepare


def _log_form(q, v, chunk: int, form: dict) -> None:
    _attention.log_rule_form(q.shape, v.shape[2], chunk, jnp.dtype(v.dtype).name, form)


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked gated delta rule.

    Args:
      q, k: ``[B, L, H_k, d_k]`` (L2-normalised and scaled by the caller);
        ``H_k`` divides ``H``, and key head ``j`` feeds value heads
        ``j H / H_k .. (j + 1) H / H_k - 1``.
      v: ``[B, L, H, d_v]``.
      g: ``[B, L, H]`` float32, the log of the decay (``<= 0``), one a head;
        or ``[B, L, H, d_k]``, one a key lane (then ``H_k = H``, and ``|g| <=
        5.5``: the module docstring's bound on the exponents).
      beta: ``[B, L, H]`` float32, the write strength in ``[0, 1]``.
      chunk: tokens a chunk; ``L`` is padded to whole chunks with rows
        ``g = 0, beta = 0, k = 0`` that leave the state as it is.

    Returns:
      ``(o [B, L, H, d_v]`` in ``v``'s dtype, the final state ``[B, H, d_k,
      d_v]`` float32``)``.

    Which program computes the part that does not read the state is
    :func:`rule_form`'s to say, from the backend and the shapes; the choice
    is one record of the dispatch log (``ops/attention.py``), with
    ``operands: given``: they are turned chunk-major and summed by XLA. A
    block hands the rule its arrays as they lie:
    :func:`gated_delta_rule_from_raw`.
    """
    form, prepare = _checked_form(q, k, v, g, beta, chunk)
    form = {name: value for name, value in form.items() if not name.startswith("operands")}
    _log_form(q, v, chunk, {**form, "operands": "given"})
    operands = (_by_chunk(q, chunk), _by_chunk(k, chunk), _summed_by_chunk(g, chunk))
    return _chunked(prepare, operands, operands, v, beta)


def gated_delta_rule_from_raw(q, k, v, gate, beta, chunk: int = CHUNK, *, a_log=None, dt_bias=None, lower_bound=None):
    """:func:`gated_delta_rule` from a block's own arrays: ``q, k [B, L, H_k,
    d_k]`` as the convolution left them, normalised here, and ``gate`` the
    log decay a head ``g [B, L, H]`` or the safe gate's pre-activation a key
    lane ``a [B, L, H, d_k]`` with its ``a_log [H]``, ``dt_bias [H d_k]`` and
    ``lower_bound`` (:func:`rule_operands`: one Mosaic call a direction where
    :func:`rule_form` says ``operands: kernel``). Returns ``(o, the final
    state, the least log decay of the call)``."""
    form, prepare = _checked_form(q, k, v, gate, beta, chunk)
    _log_form(q, v, chunk, form)
    for_prepare, for_scan, least = rule_operands(q, k, gate, chunk, a_log=a_log, dt_bias=dt_bias, lower_bound=lower_bound)
    return _chunked(prepare, for_prepare, for_scan, v, beta) + (least,)


def _chunked(prepare, for_prepare, for_scan, v, beta):
    """The rule on chunk-major operands ``(q, k [N, B, H_k, C, d_k], gamma
    [N, B, H, C(, d_k)])``, :func:`rule_operands`' results: once for the
    state-free part and once for the scan; ``v [B, L, H, d_v]`` and ``beta
    [B, L, H]`` as the block has them. The state-free part is computed by
    ``prepare(q, k, gamma, beta, group)``: :func:`_prepare`,
    :func:`_prepare_in_vmem` or, for a decay a key lane, their ``by_lane``
    forms. The scan is one for both decays but for where the decay goes: a
    scalar scales rows of the float32 results, a vector the lanes of q and k
    before the products."""
    chunks, batch, key_heads, chunk, dk = for_scan[0].shape
    length, heads, dv = v.shape[1:]
    group, dtype = heads // key_heads, v.dtype
    by_lane = for_scan[2].ndim == 5
    v = _by_chunk(v, chunk)  # [N, B, H, C, d_v]
    beta = _by_chunk(beta.astype(jnp.float32), chunk)

    solved, inside = prepare(*for_prepare, beta, group)
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
        if by_lane:  # e^gamma, e^{gamma_C - gamma} on the lanes of q and k: every exponent <= 0
            grown = jnp.exp(gamma)
            q, k_read = (q * grown).astype(dtype), (k * grown).astype(dtype)
            k_write = (k * jnp.exp(gamma[..., -1:, :] - gamma)).astype(dtype)
            grown = left_rows = 1.0
            kept = jnp.exp(gamma[..., -1, :])[..., None]  # Diag(e^{gamma_C}) S
        else:
            grown = jnp.exp(gamma)[..., None]  # e^gamma, a row
            k_read = k_write = k
            left_rows = jnp.exp(gamma[..., -1:] - gamma)[..., None]
            kept = jnp.exp(gamma[..., -1])[..., None, None]
        read = jnp.einsum("bjck,bjrkv->bjrcv", k_read, held, preferred_element_type=jnp.float32)
        unread = (v.astype(jnp.float32) - grown * read.reshape(v.shape)).astype(dtype)  # V - e^gamma K S
        fresh = jnp.einsum("bhij,bhjv->bhiv", solved, unread, preferred_element_type=jnp.float32)  # V'
        out = grown * jnp.einsum(
            "bjck,bjrkv->bjrcv", q, held, preferred_element_type=jnp.float32
        ).reshape(v.shape)
        out = out + jnp.einsum("bhij,bhjv->bhiv", inside, fresh.astype(dtype), preferred_element_type=jnp.float32)
        left = by_key_head((left_rows * fresh).astype(dtype))
        written = jnp.einsum("bjck,bjrcv->bjrkv", k_write, left, preferred_element_type=jnp.float32)
        state = kept * state + written.reshape(state.shape)
        return state, out.astype(dtype)

    start = jnp.zeros((batch, heads, dk, dv), jnp.float32)
    q, k, gamma = for_scan
    final, out = jax.lax.scan(step, start, (q, k, v, solved, inside, gamma))
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1)  # [B, N, C, H, d_v]
    return out.reshape(batch, chunks * chunk, heads, dv)[:, :length], final


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The rule as its equations state it, a token at a time in float32 (the
    module docstring's three lines); shapes and results as
    :func:`gated_delta_rule`'s, the output float32. ``g [B, L, H, d_k]`` decays
    each key lane of the state by its own ``exp(g)``."""
    batch, _, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    group = heads // key_heads
    q, k = (jnp.repeat(x.astype(jnp.float32), group, axis=2) for x in (q, k))
    v, g, beta = (x.astype(jnp.float32) for x in (v, g, beta))

    def step(state, xs):
        q, k, v, g, beta = xs  # [B, H, d], [B, H]
        state = (jnp.exp(g)[..., None] if g.ndim == 3 else jnp.exp(g)[..., None, None]) * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k, precision=jax.lax.Precision.HIGHEST)
        write = beta[..., None] * (v - read)
        state = state + k[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q, precision=jax.lax.Precision.HIGHEST)

    start = jnp.zeros((batch, heads, dk, dv), jnp.float32)
    final, out = jax.lax.scan(step, start, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), final
