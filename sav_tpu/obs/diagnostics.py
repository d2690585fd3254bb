"""In-jit training diagnostics (TrainConfig.diagnostics).

Per-step optimization signals computed *inside* the jitted train step and
returned in the step-metrics dict, so they ride the trainer's existing
per-log ``device_get`` — zero extra host<->device transfers, which is
the difference between free diagnostics and a logged step that waits on
the host (docs/benchmarking.md).

The signal set follows the DeiT-recipe ablation practice (Touvron et al.
2021) of watching grad/update norms for recipe instability, plus the
nonfinite counters that matter under bf16 compute:

- ``param_norm`` / ``update_norm`` — global l2 norms of the parameter tree
  and of the post-optimizer update.
- ``update_to_param_ratio`` — the effective relative step size; a healthy
  Adam run sits around 1e-3, collapse/blow-up shows here first.
- ``grad_norm/<group>`` — per-layer-group grad norms (group = top-level
  parameter-tree module, e.g. ``encoder_block_3``), the per-depth view the
  global norm hides.
- ``nonfinite_grads`` / ``nonfinite_params`` — counts of NaN/Inf elements
  (complements ``utils.debug.global_norm_nonfinite``: a count localizes
  "how bad", the flag only says "bad").

Everything here is pure jnp on pytrees: safe under ``jit``, ``scan``, and
any mesh sharding (the reductions partition like any other loss term).
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import optax

_EPS = 1e-12


def nonfinite_count(tree: Any) -> jax.Array:
    """In-graph count of NaN/Inf elements across a pytree's float leaves."""
    counts = [
        jnp.sum(~jnp.isfinite(x))
        for x in jax.tree.leaves(tree)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
    ]
    if not counts:
        return jnp.zeros((), jnp.int32)
    return jnp.sum(jnp.stack([c.astype(jnp.int32) for c in counts]))


def _group_of(path) -> str:
    """Top-level module name of a parameter path (the layer group)."""
    for key in path:
        name = str(getattr(key, "key", getattr(key, "name", key)))
        if name:
            return name
    return "params"


def grad_group_norms(grads: Any, prefix: str = "grad_norm/") -> dict:
    """Per-layer-group global norms, keyed ``<prefix><group>``.

    Groups are the top-level names of the parameter tree (``patch_embed``,
    ``encoder_block_0``, ..., ``head``), matching how ViT-family models in
    this repo lay out their params — the per-depth signal the single
    global norm averages away.
    """
    groups: dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            continue
        groups.setdefault(_group_of(path), []).append(leaf)
    return {
        prefix + name: optax.global_norm(leaves)
        for name, leaves in sorted(groups.items())
    }


def diagnostics_metrics(
    *,
    grads: Any,
    params: Any,
    updates: Any,
    per_group: bool = True,
) -> Mapping[str, jax.Array]:
    """The diagnostics dict merged into the trainer's step metrics.

    ``grads`` are pre-clip gradients, ``updates`` the post-optimizer deltas
    (what actually moves the weights — LR, clipping and weight decay
    included), ``params`` the pre-update parameters. All reductions are
    f32 scalars regardless of compute dtype.
    """
    param_norm = optax.global_norm(params)
    update_norm = optax.global_norm(updates)
    out = {
        "param_norm": param_norm,
        "update_norm": update_norm,
        "update_to_param_ratio": update_norm / (param_norm + _EPS),
        "nonfinite_grads": nonfinite_count(grads),
        "nonfinite_params": nonfinite_count(params),
    }
    if per_group:
        out.update(grad_group_norms(grads))
    return out
