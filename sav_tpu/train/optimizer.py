"""Schedules and the masked AdamW optimizer.

Reference behavior rebuilt: warmup-cosine schedule (train.py:215-220) and the
jaxline per-group optimizer that applied weight decay to weights but not
biases (experiments/base.py:84-104) — expressed here as a single
``optax.adamw`` with a mask over parameter paths instead of two reflected
optimizers, plus global-norm clipping (train.py:25).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


class EmaState(NamedTuple):
    """Exponential moving average of the *parameters* (not gradients)."""

    ema: Any


def track_params_ema(decay: float) -> optax.GradientTransformation:
    """Maintain ``ema = decay·ema + (1-decay)·params`` as optimizer state.

    Must sit LAST in the optax chain: it applies the (final) updates to the
    incoming params to see the post-step values, and passes the updates
    through unchanged. Living inside ``opt_state`` means the EMA rides
    checkpoints, sharding rules (path-suffix matching places the mirror
    tree like its parameters), and donation for free — no TrainState
    change, so checkpoints from EMA-less configs keep restoring.
    """
    if not 0.0 <= decay <= 1.0:
        raise ValueError(f"ema decay must be in [0, 1], got {decay}")

    def init_fn(params):
        return EmaState(ema=jax.tree.map(lambda p: p.astype(jnp.float32), params))

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("track_params_ema requires params")
        new_params = optax.apply_updates(params, updates)
        ema = jax.tree.map(
            lambda e, p: decay * e + (1.0 - decay) * p.astype(e.dtype),
            state.ema,
            new_params,
        )
        return updates, EmaState(ema=ema)

    return optax.GradientTransformation(init_fn, update_fn)


def ema_params(opt_state) -> Optional[Any]:
    """Extract the EMA parameter tree from an optimizer state, or None."""
    found = [
        s.ema
        for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: isinstance(x, EmaState)
        )
        if isinstance(s, EmaState)
    ]
    return found[0] if found else None


def warmup_cosine_schedule(
    learning_rate: float,
    *,
    steps_per_epoch: int,
    warmup_epochs: int,
    num_epochs: int,
    end_lr: float = 1e-5,
) -> optax.Schedule:
    warmup_steps = max(1, warmup_epochs * steps_per_epoch)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=learning_rate,
        warmup_steps=warmup_steps,
        # optax requires decay_steps > warmup_steps; short runs (warmup
        # longer than the whole schedule) degenerate to warmup-only.
        decay_steps=max(warmup_steps + 1, num_epochs * steps_per_epoch),
        end_value=end_lr,
    )


def weight_decay_mask(params: Any) -> Any:
    """True (decay) for rank≥2 kernels; False for biases, norm scales,
    position tables, CLS tokens, LayerScale — the reference's weight/bias
    split (base.py:95-103) generalized by rank + name."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def decays(path, leaf):
        path_str = "/".join(k.key if hasattr(k, "key") else str(k) for k in path)
        if leaf.ndim < 2:
            return False
        no_decay_names = ("pos_embed", "cls", "rel_emb_h", "rel_emb_w")
        return not any(n in path_str for n in no_decay_names)

    leaves = [decays(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), leaves)


def make_optimizer(
    schedule: optax.Schedule,
    *,
    weight_decay: float = 0.05,
    clip_grad_norm: Optional[float] = 1.0,
    fused: bool = False,
    ema_decay: Optional[float] = None,
) -> optax.GradientTransformation:
    """Masked AdamW, one pass over each parameter.

    Per leaf (the default) the TPU's compiler makes the whole update of a
    parameter one fusion: it reads the gradient, both moments and the
    parameter and writes the moments and the parameter, with the clip's
    scale, the bias corrections, the masked decay, the rate and
    ``apply_updates`` inside it (``tests/test_tpu_compile.py`` holds the
    compiled step to that).

    ``fused=True`` wraps ``scale_by_adam`` in ``optax.flatten``: the same
    arithmetic element by element on one vector of all parameters. On the
    TPU flattening a tiled matrix to 1-D is a copy, and so are the
    concatenate and the split back, so that layout moves about three times
    the bytes (PERF.md section 6, PR 29). No trainer builds it (a
    checkpoint that holds it is refused); it stays for
    ``benchmark/tests/test_reference.py``, which compares the two (ROADMAP
    D1). The decay mask and the global-norm clip are tree-wise in both.
    """
    chain = []
    if clip_grad_norm is not None:
        chain.append(optax.clip_by_global_norm(clip_grad_norm))
    adam = optax.scale_by_adam()
    if fused:
        adam = optax.flatten(adam)
    chain += [
        adam,
        optax.add_decayed_weights(weight_decay, mask=weight_decay_mask),
        optax.scale_by_learning_rate(schedule),
    ]
    if ema_decay is not None:
        # Last: sees the final updates, so the EMA tracks post-step params.
        chain.append(track_params_ema(ema_decay))
    return optax.chain(*chain)
