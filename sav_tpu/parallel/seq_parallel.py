"""Model-facing sequence parallelism: pad-and-mask routing into ring/Ulysses.

:mod:`sav_tpu.parallel.ring_attention` and :mod:`sav_tpu.parallel.ulysses`
are exact SP attention *ops* over already-divisible sequence lengths. Vision
transformers produce awkward lengths (a CLS token makes ViT's 224²/16² grid
197 tokens), so the model seam lives here: pad the sequence to a multiple of
the ``seq`` mesh axis, mask the padded keys out of every softmax (via the
shard bodies' ``valid_len`` parameter — one implementation of the ring /
all-to-all numerics, shared with the bare ops), run the sequence-parallel
op, slice the padding back off. This is what
``AttentionBlock(seq_parallel=...)`` calls — the TrainConfig-reachable path
(``train.py --sp N``), closing SURVEY.md §5's long-context gap at the
*framework* level rather than as a bare library op.

Masking is key-side only: padded *query* rows compute garbage that the final
slice discards, while padded *key* columns must not receive probability
mass. Softmax statistics run in f32 (an online-softmax requirement for
ring's running max/denominator); ``attention_logits_dtype='bfloat16'`` does
not apply under SP — see ``TrainConfig.sequence_parallel``.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from sav_tpu.parallel.mesh import SEQ_AXIS, batch_axes
from sav_tpu.parallel.ring_attention import (
    _ring_shard_fn,
    _ring_talking_heads_shard_fn,
)
from sav_tpu.parallel.ulysses import _ulysses_shard_fn

METHODS = ("ring", "ulysses")

# ---------------------------------------------------------------------------
# Batch-replication fallback observability. Replicating the batch across
# the sequence group is *correct* but multiplies per-device attention
# memory/compute by the data-axis product — a silent footgun at training
# scale, so degraded-parallelism runs must be machine-visible. Listeners
# (Trainer.fit registers one per fit: once-per-fit warning +
# SpanTracer.instant + manifest note) take precedence; without any, the
# module warns once per (batch, group) shape per process instead of
# per trace.

_replication_listeners: list = []
_replication_warned: set = set()


def on_batch_replication(callback):
    """Register ``callback(info_dict)`` for replication-fallback events;
    returns a zero-arg unsubscribe. Listener exceptions are swallowed —
    observability must never fail a trace."""
    _replication_listeners.append(callback)

    def unsubscribe():
        try:
            _replication_listeners.remove(callback)
        except ValueError:
            pass

    return unsubscribe


def _replication_fallback(b: int, group: int) -> None:
    info = {"batch": int(b), "data_axis_product": int(group)}
    handled = False
    for callback in list(_replication_listeners):
        try:
            callback(dict(info))
            handled = True
        except Exception:
            pass
    key = (int(b), int(group))
    if not handled and key not in _replication_warned:
        _replication_warned.add(key)
        warnings.warn(
            f"sequence_parallel_attention: batch {b} does not divide the "
            f"mesh's data-axis product {group}; replicating the batch "
            "across all sequence-group members. Size the global batch as "
            "a multiple of the data axes for training-scale calls.",
            stacklevel=3,
        )


def sequence_parallel_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    mesh: Mesh,
    method: str = "ring",
    seq_axis: str = SEQ_AXIS,
    batch_axis=None,
    scale: Optional[float] = None,
    talking_heads: Optional[tuple] = None,
) -> jax.Array:
    """Exact SP attention for arbitrary (CLS-token-odd) sequence lengths.

    Args:
      query/key/value: global ``[B, L, H, D]`` self-attention projections
        (equal lengths — this is the model seam, not a cross-attention op).
      mesh: mesh containing ``seq_axis``.
      method: ``'ring'`` (ppermute K/V streaming — any head count, the
        long-context default) or ``'ulysses'`` (two all-to-alls — requires
        ``H % mesh[seq_axis] == 0``).
      batch_axis: mesh axes the batch dim shards over; default: the mesh's
        batch axes when the batch divides them, else replicated.
      scale: logits scale, default ``D ** -0.5``.
      talking_heads: optional ``(w_pre, w_post)`` pair of ``[H, H]`` head-
        mixing matrices (CaiT trunk). Ring only: the mixing couples heads
        across the softmax, handled exactly by head-pair accumulators
        (:func:`sav_tpu.parallel.ring_attention._ring_talking_heads_shard_fn`);
        Ulysses scatters heads across devices, which the mix would have to
        cross — rejected.

    Returns:
      ``[B, L, H, D]`` like the inputs.
    """
    if method not in METHODS:
        raise ValueError(
            f"unknown sequence-parallel method {method!r}; choose from {METHODS}"
        )
    if talking_heads is not None and method != "ring":
        raise ValueError(
            "talking-heads sequence parallelism is ring-only (Ulysses "
            "shards heads across devices; the head mix would cross them)"
        )
    if query.shape != key.shape or key.shape != value.shape:
        raise ValueError(
            "sequence_parallel_attention is a self-attention seam: q/k/v "
            f"shapes must match, got {query.shape}/{key.shape}/{value.shape}"
        )
    if scale is None:
        scale = query.shape[-1] ** -0.5
    n = mesh.shape[seq_axis]
    b, length, heads, dim = query.shape
    if batch_axis is None:
        axes = batch_axes(mesh)
        group = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        # Shard the batch over the data axes when it divides; replicate it
        # otherwise (correct for any batch — each seq-group member then
        # holds the full batch, which is what small interactive calls and
        # single-example debugging want).
        batch_axis = axes if axes and b % group == 0 else None
        if batch_axis is None and axes and group > 1:
            # Fine for debugging, a footgun at training scale: route the
            # event through the observability hook above (listeners or a
            # once-per-shape process warning). Fires at trace time only.
            _replication_fallback(b, group)
    if method == "ulysses" and heads % n:
        raise ValueError(
            f"ulysses needs head count ({heads}) divisible by the "
            f"'{seq_axis}' axis ({n}); use method='ring'"
        )

    pad = (-length) % n
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        query = jnp.pad(query, widths)
        key = jnp.pad(key, widths)
        value = jnp.pad(value, widths)
    # valid_len=None compiles the unmasked shard bodies (no extra ops).
    valid_len = length if pad else None

    spec = P(batch_axis, seq_axis, None, None)
    if talking_heads is not None:
        w_pre, w_post = talking_heads
        rep = P()  # [H, H] mixing matrices replicate across the mesh
        shard_fn = functools.partial(
            _ring_talking_heads_shard_fn,
            axis_name=seq_axis,
            axis_size=n,
            scale=float(scale),
            valid_len=valid_len,
        )
        out = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, rep, rep),
            out_specs=spec,
            check_vma=False,
        )(query, key, value, w_pre, w_post)
        if pad:
            out = out[:, :length]
        return out
    if method == "ring":
        shard_fn = functools.partial(
            _ring_shard_fn,
            axis_name=seq_axis,
            axis_size=n,
            scale=float(scale),
            valid_len=valid_len,
        )
    else:
        shard_fn = functools.partial(
            _ulysses_shard_fn,
            axis_name=seq_axis,
            scale=float(scale),
            valid_len=valid_len,
        )
    out = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(query, key, value)
    if pad:
        out = out[:, :length]
    return out
