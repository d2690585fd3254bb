"""Mixture-of-Experts feed-forward with expert parallelism.

Capability headroom beyond the reference (which has no MoE/EP —
SURVEY.md §2.7): a token-choice top-k routed FF block designed for the TPU
partitioner. Dispatch and combine are dense one-hot einsums over static
``[groups, tokens/group, experts, capacity]`` tensors — no scatter/gather,
no dynamic shapes, so XLA tiles everything onto the MXU and, with the expert weights
sharded ``P('expert', ...)`` (``sav_tpu.parallel.sharding.DEFAULT_EP_RULES``),
inserts the dispatch/return all-to-alls over ICI on its own.

Router math runs in fp32 regardless of compute dtype (routing decisions are
precision-sensitive); a Switch-Transformer-style load-balancing loss is
sown into the ``'losses'`` collection as ``moe_aux_loss`` for the trainer
to pick up.

:class:`SparseMoEBlock` is the language family's expert layer: dropless,
sigmoid-scored, with a shared expert, told which experts of the router's
range it holds. It sorts the routings that land on them, runs grouped
matmuls over the ragged groups and gathers the weighted results back; it
builds nothing of size tokens x experts beyond the ``[T, E]`` scores.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu import megablox

from sav_tpu.models.layers.feedforward import GatedFFBlock
from sav_tpu.ops import _backend
from sav_tpu.ops.quant import quantize_channelwise

Dtype = Any


def router_scores(x: jax.Array, router: jax.Array) -> jax.Array:
    """``x W_r`` in float32 whatever the compute dtype: a routing decision is
    a comparison, and turns on the last bits."""
    return jnp.einsum(
        "...d,de->...e",
        x.astype(jnp.float32),
        router.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


class MoEFFBlock(nn.Module):
    """Token-choice top-k mixture-of-experts transformer MLP.

    Drop-in replacement for :class:`FFBlock` on ``[B, L, D]`` token inputs.
    Each batch row is a routing group (GShard-style): tokens pick their
    top-``top_k`` experts, and each expert accepts at most
    ``capacity_factor · k · L / E`` tokens *per group* — overflow tokens
    fall through the residual unmodified (standard Switch/GShard behavior),
    and the dispatch tensors stay linear in total token count.
    """

    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    expand_ratio: Optional[float] = 4.0
    hidden_ch: Optional[int] = None
    dropout_rate: float = 0.0
    # Router z-loss (ST-MoE): mean(logsumexp(router logits)²), sown
    # alongside the balance loss. Keeps router logits from drifting to
    # magnitudes where the fp32 softmax saturates and routing gradients
    # vanish. Every sown loss is scaled by TrainConfig.aux_loss_weight
    # (0.01 default) in the trainer, so the default here (0.1) makes the
    # EFFECTIVE coefficient 0.1 x 0.01 = 1e-3 — the ST-MoE paper value.
    # 0 disables (and keeps the sown-losses set of older configs).
    router_z_loss_weight: float = 0.1
    activation_fn: Callable = nn.gelu
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, is_training: bool) -> jax.Array:
        g, s, d = inputs.shape  # groups (batch rows) × tokens/group × dim
        hidden = self.hidden_ch or int(d * self.expand_ratio)
        n_exp, k = self.num_experts, self.top_k
        if not 1 <= k <= n_exp:
            raise ValueError(f"top_k={k} must be in [1, num_experts={n_exp}]")
        x = inputs

        # --- Router (fp32) -------------------------------------------------
        router = self.param(
            "router", nn.initializers.normal(stddev=0.02), (d, n_exp)
        )
        logits = router_scores(x, router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [G, S, k] each
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        # Load-balancing aux loss (Switch eq. 4), over all tokens globally:
        # E · Σ_e f_e · P_e where f_e = fraction of tokens whose top-1 choice
        # is e, P_e = mean router probability for e. Minimized (=1) by a
        # uniform router.
        # Sown-loss convention: every 'losses' entry is a ready-to-sum
        # penalty at its RELATIVE scale — balance at coefficient 1, z-loss
        # pre-multiplied by router_z_loss_weight — and the trainer's single
        # aux_loss_weight converts relative units to loss units for the
        # whole collection (trainer.py loss_fn).
        top1_frac = jnp.mean(jax.nn.one_hot(expert_idx[..., 0], n_exp), axis=(0, 1))
        aux_loss = n_exp * jnp.sum(top1_frac * jnp.mean(probs, axis=(0, 1)))
        self.sow("losses", "moe_aux_loss", aux_loss)
        if self.router_z_loss_weight:
            z = jax.nn.logsumexp(logits, axis=-1)  # [G, S]
            self.sow(
                "losses",
                "moe_router_z_loss",
                self.router_z_loss_weight * jnp.mean(z * z),
            )

        # --- Capacity-based dispatch/combine, GShard-style grouped --------
        # Capacity is per *group* (each batch row routes independently), so
        # the dispatch tensors are [G, S, E, C] with C ∝ S/E — total memory
        # and FLOPs stay linear in token count instead of quadratic.
        capacity = max(k, math.ceil(self.capacity_factor * k * s / n_exp))
        counts = jnp.zeros((g, n_exp), jnp.int32)
        dispatch = jnp.zeros((g, s, n_exp, capacity), jnp.float32)
        combine = jnp.zeros((g, s, n_exp, capacity), jnp.float32)
        for slot in range(k):  # k is static and tiny — unrolled
            onehot = jax.nn.one_hot(expert_idx[..., slot], n_exp, dtype=jnp.int32)
            # Position of each token in its expert's buffer: running
            # per-(group, expert) count from earlier slots + cumulative count
            # within this slot. one_hot maps positions ≥ capacity to the
            # all-zero row, which is exactly the overflow-drop semantics.
            pos = jnp.cumsum(onehot, axis=1) - 1 + counts[:, None, :]
            pos_tok = jnp.sum(pos * onehot, axis=-1)  # [G, S]
            slot_mask = (
                onehot.astype(jnp.float32)[..., None]
                * jax.nn.one_hot(pos_tok, capacity)[..., None, :]
            )
            dispatch = dispatch + slot_mask
            combine = combine + slot_mask * gate_vals[..., slot][..., None, None]
            counts = counts + jnp.sum(onehot, axis=1)

        # --- Expert computation (batched over the expert dim) -------------
        fan_init = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal")
        w1 = self.param("experts_w1", fan_init, (n_exp, d, hidden))
        b1 = self.param("experts_b1", nn.initializers.zeros, (n_exp, hidden))
        w2 = self.param("experts_w2", fan_init, (n_exp, hidden, d))
        b2 = self.param("experts_b2", nn.initializers.zeros, (n_exp, d))

        cdt = self.dtype
        xe = jnp.einsum("gsec,gsd->egcd", dispatch.astype(cdt), x.astype(cdt))
        h = self.activation_fn(
            jnp.einsum("egcd,edh->egch", xe, w1.astype(cdt))
            + b1.astype(cdt)[:, None, None, :]
        )
        h = nn.Dropout(rate=self.dropout_rate)(h, deterministic=not is_training)
        ye = jnp.einsum("egch,ehd->egcd", h, w2.astype(cdt)) + b2.astype(cdt)[
            :, None, None, :
        ]
        y = jnp.einsum("gsec,egcd->gsd", combine.astype(cdt), ye)
        y = nn.Dropout(rate=self.dropout_rate)(y, deterministic=not is_training)
        return y.astype(inputs.dtype)


# --------------------------------------------------------------------------
# The dropless expert layer.
# --------------------------------------------------------------------------


@jax.custom_vjp
def _rows_of_tokens(x, order, inverse, held):
    """``x [T, D]`` -> one row a routing in sorted order, ``[T k, D]``: row
    ``r`` is the token of routing ``order[r]`` (routings count token-major,
    ``k`` a token). The transpose is a gather too (``inverse`` is
    ``order``'s inverse permutation), never a scatter-add; rows of routings
    that are not ``held`` ``[T, k]`` give nothing back."""
    del inverse, held
    return jnp.take(x, order // (order.shape[0] // x.shape[0]), axis=0)


def _rows_of_tokens_fwd(x, order, inverse, held):
    return _rows_of_tokens(x, order, inverse, held), (order, inverse, held, x.shape[0])


def _rows_of_tokens_bwd(res, g):
    order, inverse, held, tokens = res
    by_slot = jnp.take(g, inverse, axis=0).reshape(tokens, -1, g.shape[-1])
    by_slot = jnp.where(held[..., None], by_slot.astype(jnp.float32), 0.0)
    return jnp.sum(by_slot, axis=1).astype(g.dtype), None, None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _rows_by_slot(rows, order, inverse):
    """Sorted rows ``[T k, D]`` back in routing order (the inverse of the
    sort); its transpose is the sort's gather."""
    del order
    return jnp.take(rows, inverse, axis=0)


def _rows_by_slot_fwd(rows, order, inverse):
    return _rows_by_slot(rows, order, inverse), (order,)


def _rows_by_slot_bwd(res, g):
    return jnp.take(g, res[0], axis=0), None, None


_rows_by_slot.defvjp(_rows_by_slot_fwd, _rows_by_slot_bwd)


def _fake_int8(a: jax.Array, contract_axes) -> jax.Array:
    """``a`` rounded to the int8 grid of its channel and back, with a
    straight-through gradient: the grouped matmuls' int8 arm."""
    q, scale = quantize_channelwise(a, contract_axes)
    rounded = (q.astype(jnp.float32) * scale).astype(a.dtype)
    return a + jax.lax.stop_gradient(rounded - a)


# (rows, contraction, columns) of a tile of the Mosaic grouped matmul; chosen
# on a v5e at [65,536, 2,048] x [16, 2,048, 768] with about 256 rows a group
# (tools/moe_micro.py; PERF.md section 6).
GMM_TILING = (256, 1024, 768)


def grouped_matmul(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``rows [R, K]`` in groups of consecutive rows, group ``e`` times
    ``kernels[e] [K, N]``. Rows past the last group belong to no held expert:
    no tile of theirs is computed and what stands there is not read.

    On a TPU a Mosaic grouped matmul (jax's megablox kernel, forward and both
    transposes), which keeps its caller's scope in the compiled step's
    ``op_name``; XLA's own lowering of ``ragged_dot`` is a Mosaic kernel too
    but names its calls ``ragged-dot-none``, and no reader of a trace could
    tell whose time that is. Elsewhere, and for a handful of rows that fill no
    tile (``init``'s trace), ``jax.lax.ragged_dot``."""
    if _backend.default_interpret() or rows.shape[0] % GMM_TILING[0]:
        return jax.lax.ragged_dot(rows, kernels, group_sizes)
    return megablox.gmm(
        rows, kernels, group_sizes, rows.dtype, GMM_TILING, None, None, False, False
    )


class _ExpertGateUp(nn.Module):
    """The routed experts' input matmuls, ``silu(rows W_gate[e]) * rows
    W_up[e]``: one leaf each with a leading axis of the experts held."""

    held: int
    hidden_ch: int
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, rows: jax.Array, group_sizes: jax.Array) -> jax.Array:
        shape = (self.held, rows.shape[-1], self.hidden_ch)
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=1, out_axis=2)
        out = []
        for name in ("gate", "up"):
            kernel = self.param(f"{name}_experts_w1", init, shape).astype(self.dtype)
            if self.quant:
                kernel = _fake_int8(kernel, (1,))
            out.append(grouped_matmul(rows, kernel, group_sizes))
        return nn.silu(out[0]) * out[1]


class _ExpertDown(nn.Module):
    held: int
    out_ch: int
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, rows: jax.Array, group_sizes: jax.Array) -> jax.Array:
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=1, out_axis=2)
        kernel = self.param(
            "experts_w2", init, (self.held, rows.shape[-1], self.out_ch)
        ).astype(self.dtype)
        if self.quant:
            rows, kernel = _fake_int8(rows, (1,)), _fake_int8(kernel, (1,))
        return grouped_matmul(rows, kernel, group_sizes)


class _RoutedExperts(nn.Module):
    """``E_e(x) = W_down[e](silu(W_gate[e] x) * W_up[e] x)`` on sorted rows
    (GatedFFBlock's formula and scopes: ``fc1``, ``fc2``)."""

    held: int
    hidden_ch: int
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, rows: jax.Array, group_sizes: jax.Array) -> jax.Array:
        if self.quant:
            rows = _fake_int8(rows, (1,))
        hidden = _ExpertGateUp(self.held, self.hidden_ch, self.quant, self.dtype, name="fc1")(
            rows, group_sizes
        )
        return _ExpertDown(self.held, rows.shape[-1], self.quant, self.dtype, name="fc2")(
            hidden, group_sizes
        )


class _Router(nn.Module):
    """Sigmoid scores over all ``num_experts``, the top ``top_k`` of score
    plus selection bias, and the selected scores (without the bias)
    normalised to ``routed_scale``."""

    num_experts: int
    top_k: int
    routed_scale: float

    @nn.compact
    def __call__(self, x: jax.Array, select_bias: jax.Array):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], self.num_experts)
        )
        scores = jax.nn.sigmoid(router_scores(x, kernel))  # [T, E] float32
        _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(select_bias), self.top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = self.routed_scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
        # Tagged for a caller's remat policy: a few megabytes that spare the
        # backward pass the router's matmul and a second top-k.
        return tuple(checkpoint_name(t, "moe_route") for t in (scores, chosen, weights))


class SparseMoEBlock(nn.Module):
    """Dropless mixture of experts with a shared expert, on ``[B, S, D]``.

    ``y = sum_{i selected and held} g_i E_i(x) + E_shared(x)``: every token
    scores all ``num_experts`` (sigmoid), the ``top_k`` of score plus
    ``select_bias`` are selected, ``g`` is the selected scores over their sum
    times ``routed_scale``. ``experts_held = (offset, count)`` names the
    experts whose weights live here (one chip's share of an expert-parallel
    layer; ``None`` holds all): the router, the counts and the balance loss
    stay ``num_experts`` wide, the routed leaves have a leading axis of
    ``count``, and what the absent experts would add is left out. No token
    is dropped and nothing has a capacity.

    The path (scopes ``route``, ``dispatch``, ``experts``, ``combine``,
    ``shared``): one stable sort of the ``T k`` routings by (expert, sequence)
    with the held experts first; the rows of the sorted routings gathered;
    grouped matmuls over the held experts' ragged groups (rows past them are
    not read back); the results gathered back by routing and summed by token
    with their weights. The largest tensors are ``[T k, D]``.

    Returns ``(y, counts, balance)``: ``counts [B, num_experts]`` float32,
    each sequence's routings by expert (no gradient: what the caller steps
    ``select_bias`` by), and ``balance``, the mean over sequences of
    ``sum_e f_e P_e`` with ``f_e = E / (k S) counts_e`` and ``P_e`` the mean
    of ``s_e / sum s`` (arXiv:2412.19437 eq. 17-20 at ``alpha`` 1).
    """

    num_experts: int
    top_k: int
    hidden_ch: int
    routed_scale: float = 1.0
    experts_held: Optional[Any] = None  # (offset, count); None = all
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, select_bias: jax.Array):
        batch, seq, dim = inputs.shape
        experts, k = self.num_experts, self.top_k
        offset, held = self.experts_held or (0, experts)
        if not (0 <= offset and 0 < held and offset + held <= experts and k <= experts):
            raise ValueError(f"experts_held {self.experts_held} / top_k {k} do not fit {experts} experts")
        x = inputs.reshape(batch * seq, dim)

        scores, chosen, weights = _Router(experts, k, self.routed_scale, name="route")(
            x, select_bias
        )

        with jax.named_scope("dispatch"):
            # Held experts become 0..held-1, so their routings sort first.
            local = (chosen - offset) % experts  # [T, k]
            sequence = jnp.arange(batch * seq, dtype=jnp.int32)[:, None] // seq
            keys = (local * batch + sequence).reshape(-1)
            order = jnp.argsort(keys, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            edges = jnp.searchsorted(
                jnp.take(keys, order), jnp.arange(experts * batch + 1, dtype=keys.dtype)
            )
            by_local = jnp.diff(edges).reshape(experts, batch)  # [E, B], local order
            # The sort's result, for a caller's remat policy (three int32 vectors).
            order, inverse, by_local = (checkpoint_name(t, "moe_order") for t in (order, inverse, by_local))
            group_sizes = jnp.sum(by_local[:held], axis=1).astype(jnp.int32)
            is_held = local < held
            rows = _rows_of_tokens(x.astype(self.dtype), order, inverse, is_held)

        out_rows = _RoutedExperts(held, self.hidden_ch, self.quant, self.dtype, name="experts")(
            rows, group_sizes
        )

        with jax.named_scope("combine"):
            by_slot = _rows_by_slot(out_rows, order, inverse).reshape(batch * seq, k, dim)
            gate = jnp.where(is_held, weights, 0.0)[..., None]
            routed = jnp.sum(
                jnp.where(is_held[..., None], by_slot.astype(jnp.float32), 0.0) * gate, axis=1
            )

        shared = GatedFFBlock(hidden_ch=self.hidden_ch, quant=self.quant, dtype=self.dtype, name="shared")(
            x.astype(self.dtype)
        )
        y = (routed + shared.astype(jnp.float32)).astype(self.dtype)

        with jax.named_scope("route"):
            counts = jnp.roll(by_local, offset, axis=0).T.astype(jnp.float32)  # [B, E]
            counts = jax.lax.stop_gradient(counts)
            share = scores / jnp.sum(scores, axis=-1, keepdims=True)
            mean_share = jnp.mean(share.reshape(batch, seq, experts), axis=1)  # [B, E]
            balance = jnp.mean(jnp.sum(counts * (experts / (k * seq)) * mean_share, axis=-1))
        return y.reshape(batch, seq, dim), counts, balance
