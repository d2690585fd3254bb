"""Parameter sharding rules — now thin consumers of
:mod:`sav_tpu.parallel.layout`.

The reference had DP only (SURVEY.md §2.7). Early rounds made TP a
first-class option by pattern-matching flattened parameter paths against
regex rules; since the SpecLayout round the canonical statement of a
layout lives in :class:`~sav_tpu.parallel.layout.SpecLayout`, and this
module keeps the historical surface (``DEFAULT_TP_RULES``,
``param_path_specs``, ``param_shardings``, ``shard_params``) as
derivations from it:

- ``DEFAULT_TP_RULES`` / ``DEFAULT_EP_RULES`` / ``DEFAULT_PP_RULES`` are
  generated by the matching single-arm layouts' ``param_rules()`` — byte
  for byte the rules earlier rounds hand-wrote (pinned by
  tests/test_layout.py), so existing callers and checkpoints see no
  change.
- ``param_shardings(params, mesh)`` without custom rules builds the
  layout the mesh implies (:func:`~sav_tpu.parallel.layout.
  layout_from_mesh`) and derives everything — rule selection by axis
  presence, FSDP augmentation — from it. Caller-supplied rules are still
  honored verbatim (they may target other mesh axes, e.g. ``seq``).

Under ``jax.jit`` the partitioner propagates the resulting layouts
through the computation and inserts the right collectives over ICI.
The default transformer TP layout (Megatron-style, over ``model``):
QKV heads column-split, output merge row-split, MLP hidden split — one
AllReduce per block (docs/parallelism.md has the full layer-role table).

Replicated by falling through every rule (a path no rule matches gets
``P()``): norm scales, the routers, and a hyper-connection's maps
(``hc_attn|hc_ffn/{kernel,scale,bias}``: every chip mixes its own tokens'
streams by the same maps; tests/test_xing.py pins it under the TP and the
expert rules). The rule lists themselves stay the pinned historical ones.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sav_tpu.parallel.layout import (
    SpecLayout,
    add_fsdp_axis,
    layout_from_mesh,
    specs_from_rules,
)
from sav_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
)

__all__ = [
    "DEFAULT_TP_RULES",
    "DEFAULT_EP_RULES",
    "DEFAULT_PP_RULES",
    "add_fsdp_axis",
    "param_path_specs",
    "param_shardings",
    "shard_params",
]

# The single-arm layouts the legacy rule lists are derived from. The 1D
# Megatron TP layout (heads/hidden over 'model'), the MoE expert layout,
# and the pipeline stage layout — each rule list is exactly what its
# layout's param_rules() yields.
_TP_LAYOUT = SpecLayout(
    name="tp",
    mesh_axes=((DATA_AXIS, -1), (MODEL_AXIS, 2)),
    tp_heads_axis=MODEL_AXIS,
)
_EP_LAYOUT = SpecLayout(
    name="ep",
    mesh_axes=((DATA_AXIS, -1), (EXPERT_AXIS, 2)),
    expert_axis=EXPERT_AXIS,
)
_PP_LAYOUT = SpecLayout(
    name="pp",
    mesh_axes=((DATA_AXIS, -1), (PIPE_AXIS, 2)),
    pipe_axis=PIPE_AXIS,
)

DEFAULT_TP_RULES: list[tuple[str, Any]] = _TP_LAYOUT.param_rules()
DEFAULT_EP_RULES: list[tuple[str, Any]] = _EP_LAYOUT.param_rules()
DEFAULT_PP_RULES: list[tuple[str, Any]] = _PP_LAYOUT.param_rules()


def param_path_specs(
    params: Any, rules: list[tuple[str, Any]] | None = None
) -> Any:
    """Tree of ``PartitionSpec`` matching ``params``, from path-regex rules
    (default: the 1D TP layout's rules)."""
    rules = DEFAULT_TP_RULES if rules is None else rules
    return specs_from_rules(params, rules)


def param_shardings(
    params: Any,
    mesh: Mesh,
    rules: list[tuple[str, Any]] | None = None,
    *,
    layout: Optional[SpecLayout] = None,
    fsdp_min_elements: int = 2**16,
) -> Any:
    """Tree of ``NamedSharding`` for ``params``.

    Resolution order: explicit ``rules`` (honored verbatim, plus FSDP
    augmentation when the mesh has that axis) → explicit ``layout`` →
    the layout the mesh's axis names imply (TP rules when a ``model``
    axis is present, EP when ``expert``, PP when ``pipe``, FSDP
    augmentation when ``fsdp`` — exactly the pre-layout behavior).
    """
    if rules is None:
        if layout is None:
            import dataclasses

            layout = layout_from_mesh(mesh)
            if fsdp_min_elements != layout.fsdp_min_elements:
                layout = dataclasses.replace(
                    layout, fsdp_min_elements=fsdp_min_elements
                )
        return layout.param_shardings(params, mesh)
    specs = specs_from_rules(params, rules)
    if FSDP_AXIS in mesh.axis_names:
        fsdp_size = mesh.shape[FSDP_AXIS]
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        spec_leaves = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        new_leaves = [
            add_fsdp_axis(
                s, leaf.shape, fsdp_size, min_elements=fsdp_min_elements,
                path="/".join(str(getattr(k, "key", k)) for k in path),
            )
            for s, (path, leaf) in zip(spec_leaves, flat)
        ]
        treedef = jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        specs = jax.tree_util.tree_unflatten(treedef, new_leaves)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Any, mesh: Mesh, rules=None) -> Any:
    """Place a parameter tree onto the mesh according to the rules."""
    shardings = param_shardings(params, mesh, rules)
    return jax.tree.map(jax.device_put, params, shardings)
