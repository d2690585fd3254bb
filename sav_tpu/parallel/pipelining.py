"""GPipe-style microbatch pipeline parallelism over a mesh axis.

Capability headroom beyond the reference, which had data parallelism only
(SURVEY.md §2.7 — TP/PP/SP/EP all absent). Stages are laid out over the
``pipe`` mesh axis; parameters for stage *i* live only on that device slice,
and activations circulate stage-to-stage with ``jax.lax.ppermute`` — XLA
collective-permute, i.e. neighbor-to-neighbor ICI traffic, the same physics
as the ring-attention rotation (:mod:`sav_tpu.parallel.ring_attention`).

Design (the scaling-book collective-pipelining recipe, TPU-idiomatic):

- The batch is split into ``M`` microbatches. A single ``lax.scan`` runs
  ``M + S - 1`` ticks; on each tick every stage applies its block to its
  current activation and the results rotate one hop around the ring. Stage 0
  feeds fresh microbatches, stage ``S-1`` produces outputs — the classic
  GPipe schedule with bubble fraction ``(S-1)/(M+S-1)``, expressed as one
  compiled program (no per-stage Python dispatch, no dynamic shapes).
- Per-stage parameters are *stacked* along a leading stage axis and sharded
  ``P('pipe')`` so each device holds exactly its own stage's weights; inside
  ``shard_map`` the leading axis has local size 1 and is squeezed away.
- Everything is differentiable: ``ppermute`` has a transpose rule (the
  backward pass rotates gradients the opposite direction), so pipeline-
  parallel training falls out of ``jax.grad`` with no hand-written backward
  schedule.

Composes with data parallelism by passing ``batch_axis``: activations are
then sharded ``P('data')`` on the batch dim while circulating over
``pipe``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from sav_tpu.parallel.mesh import PIPE_AXIS

StageFn = Callable[[Any, jax.Array], jax.Array]


def module_stage_fn(module, **apply_kwargs) -> StageFn:
    """Adapt a Flax module into a pipeline stage function.

    ``module`` is any shape-preserving block (the model-zoo case: a ViT
    ``EncoderBlock`` — every stage then runs one or more transformer layers
    on its ``[mb, L, C]`` activation slice). ``apply_kwargs`` are forwarded
    to ``module.apply`` (e.g. ``is_training=False``; pipeline training with
    dropout would need per-stage RNG plumbing — sow a need before wiring).

    The per-stage parameter trees come from initializing ``module`` once
    per stage (identical structure, different values), then
    :func:`stack_stage_params`.
    """

    def stage_fn(params, x):
        return module.apply({"params": params}, x, **apply_kwargs)

    return stage_fn


def stack_stage_params(param_trees: Sequence[Any]) -> Any:
    """Stack per-stage parameter pytrees along a new leading stage axis.

    Each leaf of the result has shape ``[S, ...]``; shard it ``P('pipe')``
    (see :func:`stage_param_shardings`) so stage *i*'s weights live on pipe
    slice *i* only.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_trees)


def stage_param_shardings(stacked_params: Any, mesh: Mesh, pipe_axis: str = PIPE_AXIS) -> Any:
    """``NamedSharding`` tree placing the leading stage axis over ``pipe``."""
    return jax.tree.map(
        lambda _: NamedSharding(mesh, P(pipe_axis)), stacked_params
    )


def _per_device(
    params: Any,
    x: jax.Array,
    *,
    stage_fn: StageFn,
    axis: str,
    num_stages: int,
    num_microbatches: int,
):
    """Per-shard pipeline body. ``x``: ``[B_loc, ...]`` local batch."""
    i = jax.lax.axis_index(axis)
    params = jax.tree.map(lambda p: p[0], params)  # [1, ...] shard → this stage
    mb = x.shape[0] // num_microbatches
    x_mb = x.reshape(num_microbatches, mb, *x.shape[1:])
    num_ticks = num_microbatches + num_stages - 1
    perm = [(j, (j + 1) % num_stages) for j in range(num_stages)]

    def tick(state, t):
        # Stage 0 reads fresh microbatches (clamped index during drain);
        # later stages read what rotated in from the previous stage.
        feed = x_mb[jnp.minimum(t, num_microbatches - 1)]
        inp = jnp.where(i == 0, feed, state)
        out = stage_fn(params, inp)
        nxt = jax.lax.ppermute(out, axis, perm)
        return nxt, out

    _, outs = jax.lax.scan(tick, jnp.zeros_like(x_mb[0]), jnp.arange(num_ticks))
    # Stage S-1 produced valid microbatch outputs on ticks S-1 .. T-1.
    outs = outs[num_stages - 1 :]
    # Replicate the result across the pipe axis (mask + psum: only the last
    # stage contributes).
    mask = (i == num_stages - 1).astype(outs.dtype)
    outs = jax.lax.psum(outs * mask, axis)
    return outs.reshape(x.shape[0], *outs.shape[2:])


def pipeline(
    stage_fn: StageFn,
    stacked_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
    batch_axis: Optional[str] = None,
) -> jax.Array:
    """Run ``x`` through ``S`` pipelined stages of ``stage_fn``.

    Args:
      stage_fn: ``(stage_params, activation [mb, ...]) -> activation`` — one
        pipeline stage (e.g. a group of transformer blocks). Activation
        shapes must match across stages.
      stacked_params: per-stage params stacked ``[S, ...]`` on every leaf
        (:func:`stack_stage_params`), sharded over ``pipe_axis``.
      x: batch ``[B, ...]``; ``B`` (the per-``batch_axis``-shard size) must
        divide by ``num_microbatches``.
      mesh: mesh containing ``pipe_axis`` (and optionally ``batch_axis``).
      num_microbatches: GPipe microbatch count ``M``; bubble fraction is
        ``(S-1)/(M+S-1)`` — use ``M >= 4·S`` for <20% bubble.
      batch_axis: optional mesh axis sharding the batch dim (DP × PP).

    Returns:
      ``[B, ...]`` outputs, replicated over ``pipe_axis``.
    """
    num_stages = mesh.shape[pipe_axis]
    batch_shards = mesh.shape[batch_axis] if batch_axis else 1
    local_b = x.shape[0] // batch_shards
    if local_b % num_microbatches:
        raise ValueError(
            f"per-shard batch {local_b} (global {x.shape[0]} over "
            f"{batch_shards} '{batch_axis}' shards) must be divisible by "
            f"num_microbatches={num_microbatches}"
        )
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
        if leaf.shape[0] != num_stages:
            name = "/".join(str(k) for k in path)
            raise ValueError(
                f"stacked param {name!r} has {leaf.shape[0]} stages on its "
                f"leading axis but mesh axis {pipe_axis!r} has {num_stages} "
                "devices — a mismatch would silently drop stages"
            )
    spec = P(batch_axis)
    fn = shard_map(
        functools.partial(
            _per_device,
            stage_fn=stage_fn,
            axis=pipe_axis,
            num_stages=num_stages,
            num_microbatches=num_microbatches,
        ),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(pipe_axis), stacked_params), spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(stacked_params, x)
