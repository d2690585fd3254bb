"""Ulysses (all-to-all) sequence parallelism: head-scatter exact attention.

The second sequence-parallel strategy next to :mod:`ring_attention`
(long-context capability the reference lacked — SURVEY.md §5). Where ring
attention streams K/V shards around a ``ppermute`` ring, Ulysses re-shards
once: inputs arrive sequence-sharded ``[B, L/n, H, D]``, an all-to-all over
the ``seq`` axis swaps the sharded dimension from sequence to heads
(``[B, L, H/n, D]``), every device then runs ordinary *full-sequence*
attention on its head group, and a reverse all-to-all restores sequence
sharding. Two collectives total per attention call (vs. n-1 ppermute steps
for ring), so Ulysses wins when ``heads % n == 0`` and the sequence fits in
HBM once re-gathered per head group; ring wins for extreme lengths where
even one head's full [L, L] tile is too large.

Both collectives are ``jax.lax.all_to_all`` → XLA AllToAll riding ICI.
Differentiable (all_to_all is its own transpose up to axis swap); numerics
cross-checked against the dense XLA core in ``tests/test_ulysses.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from sav_tpu.parallel.mesh import SEQ_AXIS


def _ulysses_shard_fn(q, k, v, *, axis_name: str, scale: float,
                      backend: str = "xla",
                      valid_len: Optional[int] = None):
    """Per-shard body. q/k/v: ``[B, L_loc, H, D]`` (sequence shards).

    ``valid_len`` (static, XLA backend only) masks key positions
    ``>= valid_len`` — the pad-and-mask path
    :mod:`sav_tpu.parallel.seq_parallel` uses for CLS-odd lengths; after
    the all-to-all the whole (padded) sequence is local, so a plain iota
    mask suffices.
    """

    def seq_to_heads(x):
        # [B, L/n, H, D] → [B, L, H/n, D]: split heads across the axis
        # group, gather the full sequence.
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if backend == "pallas":
        # Fused kernel (blocked fwd AND bwd) on the full-sequence head
        # group: local memory stays O(L·D) — the long-context setting.
        from sav_tpu.ops import flash_attention

        out = flash_attention(q, k, v, scale=scale)
    else:
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        if valid_len is not None:
            key_pos = jax.lax.iota(jnp.int32, k.shape[1])
            s = jnp.where(
                key_pos[None, None, None, :] < valid_len, s, float("-inf")
            )
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", p, v, preferred_element_type=jnp.float32
        ).astype(q.dtype)
    return heads_to_seq(out)


def ulysses_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    mesh: Mesh,
    seq_axis: str = SEQ_AXIS,
    batch_axis: Optional[str] = None,
    scale: Optional[float] = None,
    backend: str = "xla",
) -> jax.Array:
    """Exact attention over sequence-sharded inputs via head all-to-all.

    Args:
      query/key/value: global ``[B, L, H, D]`` arrays; ``L`` and ``H`` must
        both divide by the ``seq_axis`` mesh size. Under jit the arrays
        should already be sharded ``P(batch_axis, seq_axis, None, None)``.
      mesh: mesh containing ``seq_axis`` (and optionally ``batch_axis``).
      scale: logits scale, default ``D ** -0.5``.
      backend: ``'xla'`` (dense local core, numerics reference) or
        ``'pallas'`` (fused flash kernel with blocked backward on the local
        head group — O(L·D) local memory for long contexts).

    Returns:
      ``[B, L, H, D]``, sharded like the query.
    """
    if scale is None:
        scale = query.shape[-1] ** -0.5
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown ulysses attention backend: {backend!r}")
    axis_size = mesh.shape[seq_axis]
    if query.shape[1] % axis_size:
        raise ValueError(
            f"sequence length {query.shape[1]} not divisible by "
            f"{seq_axis}={axis_size}"
        )
    if query.shape[2] % axis_size:
        raise ValueError(
            f"head count {query.shape[2]} not divisible by "
            f"{seq_axis}={axis_size}; use ring_attention for H < mesh size"
        )
    spec = P(batch_axis, seq_axis, None, None)
    fn = shard_map(
        functools.partial(
            _ulysses_shard_fn, axis_name=seq_axis, scale=float(scale),
            backend=backend,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(query, key, value)
